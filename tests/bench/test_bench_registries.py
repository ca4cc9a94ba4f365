"""The benchmark finds a substrate's parts by file: a traffic generator
under `bench/generators/`, the small sizes of a CPU test run from the
substrate's own `small(cell)`, its control under `tests/bench/controls/`;
and the compile log counts a persistent-cache load once."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(ROOT / "bench"), str(ROOT / "src")]

import control  # noqa: E402
import cpu_run  # noqa: E402
import harness  # noqa: E402
import traffic_gen  # noqa: E402

# What `shrink` gives each cell: the loaded files with these keys, as the
# sizes sat in `tests/bench/cpu_run.py` before they moved into the
# substrates' `small` hooks.
SMALL = {
    "engine.skew": {
        "config_file": {
            "hidden_size": 128, "num_attention_heads": 4,
            "num_key_value_heads": 2, "num_hidden_layers": 1,
            "engine": {"n_replicas": 8, "n_shards": 2, "cross_shard": True,
                       "page": 16, "max_pages": 16, "pages_per_replica": 16,
                       "link_pages_per_step": 2, "seq_slots": 8,
                       "shadow_slots": 2, "kv_quant": "none"}},
        "traffic_file": {"generator": "engine_poisson",
                         "requests_per_step": 2.5, "zipf_s": 1.0},
    },
    "sim.table2": {
        "config_file": {"platform": "XBOF", "n_enclosures": 4,
                        "ssds_per_enclosure": 16, "n_windows": 60,
                        "window_s": 0.001, "warmup": 20,
                        "fabric_federation": True},
        "traffic_file": {"generator": "sim_bursts", "busy_per_enclosure": 8,
                         "phase_stagger": True},
    },
}


def test_generator_from_a_file_resolves(tmp_path, monkeypatch):
    (tmp_path / "steady.py").write_text(textwrap.dedent("""\
        def generate(params, n, seed):
            return [params["rate"]] * n, seed
        """))
    monkeypatch.setattr(traffic_gen, "GENERATORS_DIR", tmp_path)
    gen = traffic_gen.generator("steady")
    assert gen({"rate": 3}, 2, 3_000_000_041) == ([3, 3], 3_000_000_041)


def test_unknown_generator_raises(tmp_path, monkeypatch):
    monkeypatch.setattr(traffic_gen, "GENERATORS_DIR", tmp_path)
    with pytest.raises(FileNotFoundError, match="traffic generator 'nope'"):
        traffic_gen.generator("nope")


def test_generators_in_the_module_stay_there():
    assert traffic_gen.generator("engine_poisson") \
        is traffic_gen.engine_poisson
    assert traffic_gen.generator("sim_bursts") is traffic_gen.sim_bursts


def test_shrink_raises_for_a_substrate_without_small(monkeypatch):
    bare = types.ModuleType("substrates.bare")
    bare.Driver = object
    monkeypatch.setitem(sys.modules, "substrates.bare", bare)
    with pytest.raises(NotImplementedError, match="'bare'"):
        cpu_run.shrink({"config_file": {"substrate": "bare"},
                        "traffic_file": {}})


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_shrink_gives_the_small_sizes(cell):
    loaded = harness.load_cell(cell)
    shrunk = cpu_run.shrink(harness.load_cell(cell))
    for part, keys in SMALL[cell].items():
        assert set(keys) <= set(loaded[part])
        assert shrunk[part] == {**loaded[part], **keys}
    rest = {k: v for k, v in shrunk.items() if k not in SMALL[cell]}
    assert rest == {k: loaded[k] for k in rest}


def test_every_control_file_is_in_place(tmp_path, monkeypatch):
    log = tmp_path / "log"
    for name in ("a", "b"):
        (tmp_path / f"{name}.py").write_text(textwrap.dedent(f"""\
            import contextlib

            @contextlib.contextmanager
            def in_place():
                with open({str(log)!r}, "a") as f:
                    f.write("{name}+")
                yield
                with open({str(log)!r}, "a") as f:
                    f.write("{name}-")
            """))
    monkeypatch.setattr(control, "CONTROLS", tmp_path)
    with control.reference_in_place():
        assert log.read_text() == "a+b+"
    assert log.read_text() == "a+b+b-a-"


def test_compile_log_counts_a_cache_load_once(tmp_path):
    """JAX times the backend compile around its persistent-cache lookup, so
    a load is in `compile_s` already; `cache_load_s` reports it apart."""
    script = textwrap.dedent(f"""\
        import json, sys, time
        sys.path.insert(0, {str(ROOT / "bench")!r})
        import harness
        import jax, jax.numpy as jnp

        harness.enable_compile_cache()
        log = harness.CompileLog.get()

        def f(x):
            return jnp.sin(x) @ x

        t0 = time.perf_counter()
        jax.jit(f)(jnp.ones((64, 64))).block_until_ready()
        t1 = time.perf_counter()
        jax.clear_caches()
        jax.jit(f)(jnp.ones((64, 64))).block_until_ready()
        t2 = time.perf_counter()
        keep = (log.CACHE_LOAD, "/jax/core/compile/backend_compile_duration")
        print(json.dumps([log.between(t0, t1), log.between(t1, t2),
                          [(n, s) for t, n, s in log.events
                           if t1 <= t <= t2 and n in keep]]))
        """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    miss, hit, events = json.loads(p.stdout.strip().splitlines()[-1])
    assert miss["compiles"] >= 1 and miss["cache_load_s"] == 0.0
    assert hit["compiles"] == 0 and hit["cache_hits"] >= 1
    assert 0.0 < hit["cache_load_s"] <= hit["compile_s"]
    # each load is followed by the backend compile event that spans it
    loads = [i for i, (n, _) in enumerate(events) if n == harness.CompileLog
             .CACHE_LOAD]
    assert len(loads) == hit["cache_hits"]
    for i in loads:
        assert events[i + 1][0] != harness.CompileLog.CACHE_LOAD
        assert events[i + 1][1] >= events[i][1]
