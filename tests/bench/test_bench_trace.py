"""The trace reduction: busy union, self time and scope attribution of
nested ops, and idle gaps by host annotation, on hand-made events and on
four engine steps recorded on a TPU v5 lite (`data/`)."""
import gzip
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

import trace_reduce as tr  # noqa: E402
import xspace  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _ev(s, d, name, scope=""):
    return xspace.Event(s, d, name, scope)


def _planes(events, host=()):
    dev = xspace.Plane("/device:TPU:0", [xspace.Line("XLA Ops", events)])
    h = xspace.Plane("/host:CPU", [xspace.Line("main", list(host))])
    return [dev, h]


def test_nested_ops_self_time_and_scope():
    # a while op (no name stack) holding two body ops of one scope, then a
    # kernel op, then a gap
    evs = [_ev(0, 100, "%while.1 = ..."),
           _ev(10, 30, "%fusion.1 = ...", "jit(step)/vmap(mgmt_round)/gather:"),
           _ev(50, 40, "%fusion.2 = ...", "jit(step)/vmap(mgmt_round)/scatter:"),
           _ev(120, 50, "%call.3 = ...",
               "jit(step)/vmap(paged_attention)/pallas_call:")]
    host = [_ev(0, 200, "bench_window"), _ev(100, 30, "bench_readback")]
    spans = [(e.start_ps, e.start_ps + e.dur_ps, e.name) for e in host]
    red = tr.reduce(_planes(evs, host), (0, 200), spans)
    assert red.busy_ps == [150]
    ops = {o.name: o for o in red.devices[0]}
    assert ops["while.1"].self_ps == 100 - 70
    assert tr.in_scope(ops["while.1"].scope, "mgmt_round")
    assert tr.scope_ps(red, "mgmt_round") == 100
    assert tr.scope_ps(red, "paged_attention") == 50
    assert tr.scope_ps(red, "hier_exchange") is None
    gaps = dict(tr.top_gaps(red))
    assert gaps["bench_readback"] == pytest.approx(20e-12)
    assert gaps["bench_window"] == pytest.approx(30e-12)


def test_scope_components_match_whole_names():
    assert tr.in_scope("jit(step)/vmap(mgmt_round)/while", "mgmt_round")
    assert tr.in_scope("a/hier_exchange/enclosure/b", "hier_exchange")
    assert not tr.in_scope("jit(step)/mgmt_round_extra/x", "mgmt_round")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    raw = gzip.decompress((DATA / "engine_skew_4steps.xplane.pb.gz")
                          .read_bytes())
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(raw)
    planes = xspace.read(str(path))
    spans = [(e.start_ps, e.start_ps + e.dur_ps, e.name) for p in planes
             if p.name.startswith("/host") for ln in p.lines
             for e in ln.events if e.name.startswith("bench_")]
    win = next(s for s in spans if s[2] == "bench_window")
    return planes, spans, tr.reduce(planes, win[:2], spans)


def test_recorded_busy_is_the_union_of_ops(recorded):
    planes, _, red = recorded
    lo = red.devices[0][0].start
    ops = [e for p in planes if p.name == "/device:TPU:0" for ln in p.lines
           if ln.name == "XLA Ops" for e in ln.events]
    # brute force on a 1 ns grid: which instants have an op running
    win = next(s for s in recorded[1] if s[2] == "bench_window")
    busy = set()
    for e in ops:
        s, t = max(e.start_ps, win[0]), min(e.start_ps + e.dur_ps, win[1])
        busy.update(range(s // 1000, -(-t // 1000)))
    assert red.busy_ps[0] == pytest.approx(len(busy) * 1000, rel=2e-3)
    assert lo >= win[0]
    # self times partition the busy time; gaps fill the rest of the window
    assert sum(o.self_ps for o in red.devices[0]) == red.busy_ps[0]
    assert sum(e - s for s, e, _ in red.gaps[0]) \
        == red.window_ps - red.busy_ps[0]


def test_recorded_scopes_and_gaps(recorded):
    _, _, red = recorded
    paged = tr.scope_ps(red, "paged_attention")
    mgmt = tr.scope_ps(red, "mgmt_round")
    # four steps: the kernel dominates the device, the round is small
    assert 0.5 * red.busy_ps[0] < paged < red.busy_ps[0]
    assert 0 < mgmt < 0.1 * red.busy_ps[0]
    assert tr.top_ops(red)[0][0].startswith("vmap(paged_attention)")
    assert tr.top_gaps(red)[0][0] == "bench_readback"
