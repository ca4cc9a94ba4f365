"""The profiler span registry (`repro.obs.export.SPANS`): every device scope
reaches the name stacks of the lowered programs, every span is read by a
per-layer metric of the benchmark (or has a stated operator use), and no
span is named outside the registry."""
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.jbof import platforms, sim, workloads as wl
from repro.obs import export as obs_x
from repro.obs import metrics as obs_m
from repro.serving import engine as E

REPO = pathlib.Path(__file__).resolve().parents[1]
DEVICE = sorted(s.name for s in obs_x.SPANS.values() if s.kind == "device")
HOST = sorted(s.name for s in obs_x.SPANS.values() if s.kind == "host")

# the scopes each substrate's program runs at the shapes below
ENGINE_SCOPES = {"mgmt_round", "claim_sweep", "hier_exchange", "route",
                 "admit", "kv_append", "paged_attention", "obs_record"}
SIM_SCOPES = {"mgmt_round", "claim_sweep", "fluid_transfer",
              "hier_exchange"}


def _stacks(text: str, name: str) -> list:
    """The name stacks of the lowered ``text`` that hold ``name`` as a
    whole component (`jit(step)/vmap(mgmt_round)/claim_sweep/while`)."""
    comp = re.compile(rf"(^|[/(]){re.escape(name)}([/):]|$)")
    return [s for s in re.findall(r'loc\("([^"]*/[^"]*)"', text)
            if not s.endswith(".py") and comp.search(s)]


@pytest.fixture(scope="module")
def engine_text():
    cfg = E.EngineConfig(
        n_replicas=4, seq_slots=2, shadow_slots=1, pages_per_replica=8,
        page=4, max_pages=4, n_shards=2, cross_shard=True,
        link_pages_per_step=2, n_heads=2, kv_heads=1, head_dim=8,
        obs=obs_m.ObsConfig(enabled=True, ring_depth=4, event_capacity=16))
    state = E.init(cfg, jax.random.key(0))
    arrivals = jnp.zeros((cfg.n_replicas,), jnp.int32)
    return E.step.lower(cfg, state, arrivals).as_text(debug_info=True)


@pytest.fixture(scope="module")
def sim_text():
    wls = [wl.micro(False, 4.0, qd=4, random_access=True)] * 2 \
        + [wl.idle()] * 2
    arr = wl.arrivals(wls, 3, seed=7)
    cfg = sim.SimConfig(warmup=1, n_enclosures=2)
    fn = jax.jit(lambda a: sim.simulate(platforms.xbof(), wls, a, cfg)
                 .throughput_bps)
    return fn.lower(arr).as_text(debug_info=True)


def test_registry_kinds_and_readers():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    per_layer = {m["name"] for m in spec["per_layer"]}
    assert set(DEVICE) | set(HOST) == set(obs_x.SPANS)
    for span in obs_x.SPANS.values():
        assert span.kind in ("device", "host") and span.read_by
        for use in span.read_by:
            if use.startswith("operator: "):
                continue
            # the metric exists and its reader keys on this very name
            assert use in per_layer, (span.name, use)
            reader = (REPO / "bench" / "metrics" / f"{use}.py").read_text()
            assert f'"{span.name}"' in reader, (span.name, use)


def test_unregistered_names_raise():
    with pytest.raises(KeyError):
        obs_x.scope("no_such_scope")
    with pytest.raises(KeyError):
        obs_x.annotate("xbof.sim.nothing")
    with pytest.raises(KeyError):            # a host span is not a scope
        obs_x.scope("xbof.sim.scan")
    with pytest.raises(KeyError):
        obs_x.annotate("mgmt_round")


def test_no_span_named_outside_the_registry():
    raw = re.compile(r"named_scope\(|TraceAnnotation\(")
    for path in (REPO / "src").rglob("*.py"):
        if path.name == "export.py" and path.parent.name == "obs":
            continue
        assert not raw.search(path.read_text()), path


@pytest.mark.parametrize("name", DEVICE)
def test_device_scope_in_lowered_programs(name, engine_text, sim_text):
    assert name in ENGINE_SCOPES | SIM_SCOPES
    assert bool(_stacks(engine_text, name)) == (name in ENGINE_SCOPES)
    if name in SIM_SCOPES:
        assert _stacks(sim_text, name)


def test_claim_sweep_nests_in_the_round(engine_text, sim_text):
    for text in (engine_text, sim_text):
        stacks = _stacks(text, "claim_sweep")
        assert stacks and all(s.index("mgmt_round") < s.index("claim_sweep")
                              for s in stacks)


def test_persistent_cache_keeps_span_sets_apart(tmp_path, monkeypatch):
    """An executable cached under one span set is not loaded under
    another: its op names would otherwise be the other code's."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def compile_once():
        jax.clear_caches()
        with obs_x.scope("mgmt_round"):
            jax.jit(lambda x: x * 3 + 1).lower(jnp.ones(5)).compile()
        return len(list(tmp_path.iterdir()))

    opts = {"jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0,
            "jax_persistent_cache_min_entry_size_bytes": 0}
    old = {k: getattr(jax.config, k) for k in opts}
    try:
        for k, v in opts.items():
            jax.config.update(k, v)
        cc.reset_cache()
        first = compile_once()
        assert first >= 1 and compile_once() == first
        monkeypatch.setitem(obs_x.SPANS, "new_scope",
                            obs_x.Span("new_scope", "device", ("m",)))
        assert compile_once() > first
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()
        jax.clear_caches()
