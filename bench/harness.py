"""The benchmark harness: one cell of `BENCHMARK.json` per process.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (`bench/configs/<config>.json`) and a traffic
mix (`bench/traffic/<traffic>.json`); the configuration's `substrate` names
the driver (`bench/substrates/<substrate>.py`) that builds the system, warms
it up, runs the measured window and checks what the window produced against
the plain reference (`bench/reference/`). Each per-layer metric is a reader
of its own (`bench/metrics/<metric>.py`). A new cell, configuration, traffic
mix or metric is a new file and a new entry, never an edit here.

A new substrate (a system the benchmark drives) brings only new files:
- `bench/substrates/<s>.py`: `Driver(cell, seed, devices, tracer)` with
  `setup`, `run_window`, `check` and `layer_context`, and `small(cell)`,
  which shrinks a loaded cell to a size a CPU test run holds
  (`tests/bench/cpu_run.py`);
- `bench/reference/<s>.py`: the plain reference `check` compares with;
- its traffic generator, `bench/generators/<name>.py` with
  `generate(params, ...)` (`traffic_gen.generator`), and its traffic mixes,
  `bench/traffic/<traffic>.json`, whose `generator` key names it;
- its work and roofline counts in a module of its own beside `work.py`
  (never an edit to `work.py`);
- its per-layer metrics' readers under `bench/metrics/`;
- its control, `tests/bench/controls/<s>.py` with `in_place()`, which
  `tests/bench/control.py` enters with every other.
`tests/bench/test_bench_spec.py` holds every substrate file to this. The
one edit to an existing entry a new cell needs is its name appended to the
`workloads` list of each end-to-end metric it reports.

Set-up (`setup_s`) runs from the process's start to the first timed step or
call. With `--trace 0` the result line carries the cell's end-to-end
metrics; with `--trace 1` the driver traces part of the window and the line
carries the per-layer metrics, the device's busy and window seconds, and a
breakdown of device time and idle gaps.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class Refused(SystemExit):
    """Exit without a result line (non-zero status)."""


def process_start() -> float:
    """`time.perf_counter()` reading of this process's start (Linux
    /proc), or of now where that cannot be read."""
    now = time.perf_counter()
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        start = int(pathlib.Path("/proc/self/stat").read_text()
                    .rsplit(")", 1)[1].split()[19]) / ticks
        uptime = float(pathlib.Path("/proc/uptime").read_text().split()[0])
        return now - max(uptime - start, 0.0)
    except (OSError, ValueError, IndexError):
        return now


def load_cell(name: str) -> dict:
    spec_path = ROOT / "BENCHMARK.json"
    if not spec_path.is_file():
        raise Refused(f"bench: no BENCHMARK.json at {spec_path}")
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"bench: no workload {name!r} in BENCHMARK.json "
                      f"(have {sorted(cells)})")
    cell = dict(cells[name])
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cell["config_file"] = json.loads((ROOT / conf["file"]).read_text())
    from traffic_gen import load
    cell["traffic_file"] = load(cell["traffic"])
    cell["end_to_end"] = [m for m in spec["end_to_end"]
                          if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in spec["per_layer"]
                         if name in m.get("workloads", [name])
                         and m["moves"] in e2e_names]
    return cell


def require_devices(chips: int):
    """The cell's chips, or a refusal that names what JAX found."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise Refused(f"bench: JAX found no accelerator ({e})") from None
    d0 = devs[0]
    if d0.platform != "tpu":
        raise Refused(f"bench: needs a TPU; JAX found {len(devs)} "
                      f"{d0.platform} device(s) ({d0.device_kind})")
    if len(devs) < chips:
        raise Refused(f"bench: cell needs {chips} chips; JAX found "
                      f"{len(devs)} {d0.device_kind}")
    return devs


def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """JAX's compile events with their host times, to count what compiles
    inside the window and what tracing and compiling cost per call.

    `compile_s` sums tracing, lowering and the backend compile. JAX times
    the backend compile around its persistent-cache lookup, so on a cache
    hit that event holds the load; `cache_load_s` reports the load apart
    and is not added again."""

    KEYS = ("/jax/core/compile/jaxpr_trace_duration",
            "/jax/core/compile/jaxpr_to_mlir_module_duration",
            "/jax/core/compile/backend_compile_duration")
    CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    _instance = None

    def __init__(self):
        from jax import monitoring

        self.events = []   # (t, name, seconds)
        monitoring.register_event_duration_secs_listener(self._dur)
        monitoring.register_event_listener(self._ev)

    @classmethod
    def get(cls) -> "CompileLog":
        """The process's one log (JAX keeps listeners for the process)."""
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _dur(self, name, secs, **_):
        self.events.append((time.perf_counter(), name, secs))

    def _ev(self, name, **_):
        self.events.append((time.perf_counter(), name, 0.0))

    def between(self, t0, t1):
        out = {"compiles": 0, "cache_hits": 0, "compile_s": 0.0,
               "cache_load_s": 0.0}
        for t, name, secs in self.events:
            if not t0 <= t <= t1:
                continue
            if name == "/jax/compilation_cache/cache_misses":
                out["compiles"] += 1
            elif name == "/jax/compilation_cache/cache_hits":
                out["cache_hits"] += 1
            elif name == self.CACHE_LOAD:
                out["cache_load_s"] += secs
            elif name in self.KEYS:
                out["compile_s"] += secs
        return out


class Tracer:
    """Profiler trace of a span the driver chooses, reduced after the run.
    The driver calls `start()` and `stop()`; with `--trace 0` both do
    nothing."""

    def __init__(self, enabled: bool, out_dir: pathlib.Path):
        self.enabled = enabled
        self.dir = out_dir
        self._ann = None

    def start(self):
        if not self.enabled:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench_window")
        self._ann.__enter__()

    def stop(self):
        if not self.enabled or self._ann is None:
            return
        import jax

        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()

    def reduce(self):
        import trace_reduce
        import xspace

        files = sorted(self.dir.glob("**/*.xplane.pb"))
        if not files:
            raise RuntimeError(f"no trace written under {self.dir}")
        planes = xspace.read(str(files[-1]))
        spans = [(e.start_ps, e.start_ps + e.dur_ps, e.name)
                 for p in planes if p.name.startswith("/host")
                 for ln in p.lines for e in ln.events
                 if e.name.startswith("bench_")]
        win = [s for s in spans if s[2] == "bench_window"]
        if not win:
            raise RuntimeError("trace holds no bench_window annotation")
        return trace_reduce.reduce(planes, (win[0][0], win[0][1]), spans)


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def load_module(path: pathlib.Path, kind: str):
    """The module in the file ``path``, a ``kind`` found by its name."""
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} {path.stem!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind.replace(' ', '_')}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    return load_module(BENCH / "metrics" / f"{metric}.py",
                       "metric reader").read


def memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")
    if args.seconds <= 0:
        ap.error("--seconds must be positive")
    return args


def main(argv=None, t_start: float | None = None) -> int:
    t_start = process_start() if t_start is None else t_start
    args = parse_args(argv)
    cell = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    devs = require_devices(int(cell["chips"]))
    cache_dir = enable_compile_cache()
    clog = CompileLog.get()
    import jax

    import peaks as peaks_mod

    pk = peaks_mod.peaks(devs[0].device_kind)
    drv_mod = importlib.import_module(
        f"substrates.{cell['config_file']['substrate']}")
    out_dir = ROOT / ".bench_out" / f"{args.workload}.{os.getpid()}"
    tracer = Tracer(bool(args.trace), out_dir / "trace")
    drv = drv_mod.Driver(cell, args.seed, devs[:int(cell["chips"])], tracer)
    drv.setup()
    setup_s = time.perf_counter() - t_start
    t0 = time.perf_counter()
    e2e = drv.run_window(args.seconds)
    t1 = time.perf_counter()
    window_compiles = clog.between(t0, t1)
    mem = memory_peak(drv.devices)
    checks = drv.check()          # frees the program's state, then compares
    correct = all(c["ok"] for c in checks.values())

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    metrics = {}
    line = {"correct": correct, "attempted": drv.attempted,
            "failed": drv.failed}
    if args.trace:
        red = tracer.reduce()
        ctx = drv.layer_context(red, pk, clog)
        for m in cell["per_layer"]:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        import trace_reduce

        device["busy_s"] = sum(red.busy_ps) / len(red.busy_ps) / 1e12
        device["window_s"] = red.window_ps / 1e12
        line["breakdown"] = {"device_ops": trace_reduce.top_ops(red),
                             "idle_gaps": trace_reduce.top_gaps(red)}
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    info = {"setup_s": setup_s, "window_s": t1 - t0, "cache_dir": cache_dir,
            **{f"window_{k}": v for k, v in window_compiles.items()},
            **drv.info}
    print(json.dumps({"info": info}), file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    jax.clear_caches()
    return 0
