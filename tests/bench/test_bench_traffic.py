"""Seeded determinism and offered rate of both traffic generators, and the
burst generator against the program's own (`jbof.workloads.arrivals`)."""
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

import traffic_gen as T  # noqa: E402

BIG_SEED = 3_000_000_017


def _engine(name, seed, steps=4096 + 100):
    g = T.engine_poisson(T.load(name), 32, seed)
    return np.stack([g.step(i) for i in range(steps)])


def test_engine_traffic_is_seeded():
    a, b = _engine("skew", BIG_SEED), _engine("skew", BIG_SEED)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, _engine("skew", BIG_SEED + 1))


def test_engine_traffic_offers_its_rate():
    for name in ("skew", "uniform"):
        rate = T.load(name)["requests_per_step"]
        a = _engine(name, 11, steps=20000)
        assert abs(a.sum(axis=1).mean() - rate) < 0.05 * rate


def test_skew_is_zipf_and_uniform_is_flat():
    rate = T.load("skew")["requests_per_step"]
    skew = _engine("skew", 5, steps=20000).mean(axis=0)
    flat = _engine("uniform", 5, steps=20000).mean(axis=0)
    # Zipf(1) over 32 replicas: the busiest gets 1/H(32) of the load
    h32 = (1.0 / np.arange(1, 33)).sum()
    assert abs(skew.max() - rate / h32) < 0.1
    assert abs(flat.max() - rate / 32) < 0.05
    assert abs(flat.min() - rate / 32) < 0.05


def test_sim_traffic_is_seeded_per_call():
    p = T.load("table2")
    r1, a1 = T.sim_bursts(p, 4, 16, 60, 1e-3, BIG_SEED, 0)
    r2, a2 = T.sim_bursts(p, 4, 16, 60, 1e-3, BIG_SEED, 0)
    _, a3 = T.sim_bursts(p, 4, 16, 60, 1e-3, BIG_SEED, 1)
    assert r1 == r2 and np.array_equal(a1, a2)
    assert not np.array_equal(a1, a3)
    busy = p["busy_per_enclosure"]
    assert all(r == p["idle"] for e in range(4)
               for r in r1[e * 16 + busy:(e + 1) * 16])


def test_sim_traffic_offers_its_rate():
    # a steady row offers intensity x its capacity every window, within
    # the lognormal(0, 0.08) noise
    row = dict(T.load("table2")["traces"][0], duty=1.0, intensity=0.5)
    a = T.burst_arrivals([row] * 64, 200, 1e-3, np.random.default_rng(3))
    offered = a.sum(axis=2).mean()
    want = 0.5 * T.capacity_bps(row) * 1e-3 * np.exp(0.08 ** 2 / 2)
    assert abs(offered - want) < 0.01 * want


def test_burst_generator_matches_the_program():
    from repro.jbof import workloads as W

    p = T.load("table2")
    rows = [p["traces"][i % len(p["traces"])] for i in range(40)] \
        + [p["idle"]] * 8
    mine = T.burst_arrivals(rows, 80, 1e-3, np.random.default_rng(9))
    theirs = np.asarray(W.arrivals([W.Workload(**r) for r in rows], 80,
                                   seed=9))
    assert np.array_equal(mine, theirs)
