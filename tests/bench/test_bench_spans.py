"""The readers of the program's own spans: device idle time under the
simulator's host spans (`program_spans.py`), the scope readers of the
claim sweep, fluid transfer, route/admit and KV append on hand-made traces,
and the host spans of a real CPU profile of a tiny `simulate`."""
import os
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

import harness  # noqa: E402
import program_spans as ps  # noqa: E402
import trace_reduce as tr  # noqa: E402
import xspace  # noqa: E402


def _red(ops, window=(0, 1000)):
    """A reduced one-device trace of ``ops`` [(start, dur, scope)]."""
    evs = [xspace.Event(s, d, f"%fusion.{i} = ...", sc)
           for i, (s, d, sc) in enumerate(ops)]
    planes = [xspace.Plane("/device:TPU:0",
                           [xspace.Line("XLA Ops", evs)])]
    return tr.reduce(planes, window, [])


def test_gap_straddling_two_spans_is_split():
    # busy [0, 100) and [700, 1000): one gap [100, 700)
    red = _red([(0, 100, "a/x:"), (700, 300, "a/y:")])
    assert red.gaps[0] == [(100, 700, tr.Timeline.NONE)]
    prepare, scan = [(50, 300)], [(300, 650)]
    assert ps.idle_overlap_ps(red, prepare) == 200
    assert ps.idle_overlap_ps(red, scan) == 350
    # the union of overlapping intervals counts once; disjoint ones add
    assert ps.idle_overlap_ps(red, [(200, 400), (300, 500)]) == 300
    assert ps.idle_overlap_ps(red, [(0, 150), (650, 900)]) == 100
    assert ps.idle_overlap_ps(red, [(0, 100)]) == 0


def test_overlap_averages_over_devices():
    evs = [[xspace.Event(0, 500, "%f.0 = ...", "a/x:")],
           [xspace.Event(0, 900, "%f.0 = ...", "a/x:")]]
    planes = [xspace.Plane(f"/device:TPU:{i}", [xspace.Line("XLA Ops", e)])
              for i, e in enumerate(evs)]
    red = tr.reduce(planes, (0, 1000), [])
    assert ps.idle_overlap_ps(red, [(0, 1000)]) == (500 + 100) / 2


SIM = "jit(step)/while/body/closed_call/vmap(jit(_window_step))"
ENGINE = "jit(step)/vmap()"


@pytest.mark.parametrize("metric,ops,ctx,want", [
    ("claim_sweep_us.sim",
     [(0, 400, f"{SIM}/mgmt_round/claim_sweep/while/body/gather:"),
      (400, 100, f"{SIM}/mgmt_round/gt:")],
     {"windows": 2, "calls": 1}, 400 / 2 / 1e6),
    ("fluid_transfer_us.sim",
     [(0, 300, f"{SIM}/fluid_transfer/mul:"), (300, 100, f"{SIM}/add:")],
     {"windows": 3, "calls": 1}, 300 / 3 / 1e6),
    ("route_admit_ms.engine",
     [(0, 200, f"{ENGINE}/route/while:"), (200, 100, f"{ENGINE}/admit/or:"),
      (300, 50, "jit(step)/vmap(mgmt_round)/claim_sweep/gt:")],
     {"steps": 4}, 300 / 4 / 1e9),
    ("kv_append_ms.engine",
     [(0, 80, f"{ENGINE}/kv_append/scatter:"), (80, 20, f"{ENGINE}/add:")],
     {"steps": 2}, 80 / 2 / 1e9),
])
def test_scope_readers(metric, ops, ctx, want):
    read = harness.load_reader(metric)
    assert read(dict(ctx, reduced=_red(ops))) == pytest.approx(want)
    # a program without the scope (the parent of this change): no reading
    bare = [(s, d, "jit(step)/other/add:") for s, d, _ in ops]
    assert read(dict(ctx, reduced=_red(bare))) is None


@pytest.mark.parametrize("metric,span,want", [
    ("prepare_idle_s.sim", "xbof.sim.prepare", 200 / 1e12),
    ("scan_build_idle_s.sim", "xbof.sim.scan", 350 / 1e12),
])
def test_idle_readers(monkeypatch, metric, span, want):
    red = _red([(0, 100, "a/x:"), (700, 300, "a/y:")])
    found = {"xbof.sim.prepare": [(50, 300)],
             "xbof.sim.scan": [(300, 650)]}
    monkeypatch.setattr(ps, "spans", lambda name: found.get(name, []))
    read = harness.load_reader(metric)
    assert read({"reduced": red, "calls": 1}) == pytest.approx(want)
    monkeypatch.setattr(ps, "spans", lambda name: [])
    assert read({"reduced": red, "calls": 1}) is None


def test_host_spans_of_a_cpu_profile(tmp_path):
    """`simulate` names its three host phases in order, inside the
    caller's annotation, where `program_spans` finds them."""
    import jax

    from repro.jbof import platforms, sim, workloads as wl

    wls = [wl.micro(False, 4.0, qd=4, random_access=True)] * 2 \
        + [wl.idle()] * 2
    arr = wl.arrivals(wls, 3, seed=7)
    cfg = sim.SimConfig(warmup=1, n_enclosures=2)
    out = tmp_path / ".bench_out" / f"sim.cell.{os.getpid()}" / "trace"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench_simulate"):
            res = sim.simulate(platforms.xbof(), wls, arr, cfg)
            jax.block_until_ready(res.throughput_bps)
    finally:
        jax.profiler.stop_trace()
    path = ps.this_trace(tmp_path)
    assert path is not None
    events = ps.host_events(str(path))
    assert [n for _, _, n in events] == [
        "xbof.sim.prepare", "xbof.sim.scan", "xbof.sim.finish"]
    for (_, end, _), (start, _, _) in zip(events, events[1:]):
        assert end <= start
    caller = [(e.start_ps, e.start_ps + e.dur_ps)
              for p in xspace.read(str(path)) if p.name.startswith("/host")
              for ln in p.lines for e in ln.events
              if e.name == "bench_simulate"]
    assert len(caller) == 1
    lo, hi = caller[0]
    assert lo <= events[0][0] and events[-1][1] <= hi
