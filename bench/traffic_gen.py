"""General traffic generators. A traffic mix is a JSON file under
`bench/traffic/` whose `generator` key names a generator (`generator`: one
of `GENERATORS` below, or the `generate` function of
`bench/generators/<name>.py`) and whose other keys are its parameters;
everything is drawn from the run's seed, so the same seed gives the same
traffic.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"
GENERATORS_DIR = pathlib.Path(__file__).resolve().parent / "generators"
BLOCK_STEPS = 512


def load(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def rng(seed: int, *tags: int) -> np.random.Generator:
    """An independent stream per (seed, tags); any non-negative seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *tags]))


def key_seed(seed: int, *tags: int) -> int:
    """A 31-bit integer for `jax.random.key`, derived like `rng`."""
    ss = np.random.SeedSequence([int(seed), *tags])
    return int(ss.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)


class EnginePoisson:
    """Open-loop arrivals per engine step: each replica receives a Poisson
    number of 16-token requests a step, with mean `requests_per_step` times
    its popularity. Popularity is Zipf(`zipf_s`) over a seed-permuted order
    of the replicas (`zipf_s` 0 is uniform).

    Every seed gets the same arrivals in another order: the counts of each
    block of `BLOCK_STEPS` steps are drawn, by popularity rank, from one
    stream common to all seeds, and the seed permutes the steps within the
    block and maps ranks to replicas. So the requests offered over whole
    blocks do not depend on the seed, and a run's rate differs from seed to
    seed only by the order of its traffic."""

    def __init__(self, params: dict, n_replicas: int, seed: int):
        rank_pop = 1.0 / np.arange(1, n_replicas + 1) \
            ** float(params["zipf_s"])
        self.lam = (float(params["requests_per_step"]) * rank_pop
                    / rank_pop.sum())
        self.replica_of_rank = rng(seed, 1).permutation(n_replicas)
        self._common = rng(0, 2)
        self._order = rng(seed, 2)
        self._blocks = []

    def step(self, i: int) -> np.ndarray:
        """int32[n_replicas] arrivals of step ``i`` (0-based)."""
        b, j = divmod(i, BLOCK_STEPS)
        while len(self._blocks) <= b:
            counts = self._common.poisson(
                self.lam[None, :], size=(BLOCK_STEPS, self.lam.size))
            out = np.empty_like(counts, dtype=np.int32)
            out[:, self.replica_of_rank] = counts
            self._blocks.append(out[self._order.permutation(BLOCK_STEPS)])
        return self._blocks[b][j]


def engine_poisson(params: dict, n_replicas: int, seed: int) -> EnginePoisson:
    return EnginePoisson(params, n_replicas, seed)


def capacity_bps(row: dict) -> float:
    r = row["read_ratio"]
    return r * 14e9 + (1.0 - r) * 10e9


def burst_arrivals(rows: list[dict], n_windows: int, window_s: float,
                   g: np.random.Generator,
                   phase_stagger: bool = True) -> np.ndarray:
    """float32[T, n, 2] (read, write) bytes per window. Each SSD alternates
    base-load and burst phases of its row's duty and intensity; phases are
    staggered across SSDs with a random onset, and every window's level
    carries lognormal(0, 0.08) noise (the §2.2 sporadic-burst premise)."""
    n = len(rows)
    out = np.zeros((n_windows, n, 2), np.float32)
    for i, w in enumerate(rows):
        cap = capacity_bps(w) * window_s
        if w["duty"] >= 1.0 - 1e-6:
            on = np.ones(n_windows, bool)
        else:
            period = max(int(n_windows * 0.2), 8)
            burst_len = max(int(period * w["duty"]), 1)
            offset = (i * period) // max(n, 1) if phase_stagger else 0
            offset += int(g.integers(0, max(period // 4, 1)))
            t = (np.arange(n_windows) + offset) % period
            on = t < burst_len
        level = np.where(on, w["intensity"], w["base_load"]).astype(np.float32)
        level = level * g.lognormal(0.0, 0.08, n_windows).astype(np.float32)
        total = level * cap
        out[:, i, 0] = total * w["read_ratio"]
        out[:, i, 1] = total * (1.0 - w["read_ratio"])
    return out


def sim_bursts(params: dict, n_enclosures: int, per_enclosure: int,
               n_windows: int, window_s: float, seed: int, call: int):
    """One simulate call's workload: in every enclosure the first
    `busy_per_enclosure` SSDs run one trace drawn (per enclosure) from
    `traces`, the rest run `idle`; arrivals as `burst_arrivals`. Returns
    (rows per SSD, arrivals)."""
    g = rng(seed, 3, call)
    traces, idle = params["traces"], params["idle"]
    busy = int(params["busy_per_enclosure"])
    rows = []
    for _ in range(n_enclosures):
        t = traces[int(g.integers(len(traces)))]
        rows += [t] * busy + [idle] * (per_enclosure - busy)
    arr = burst_arrivals(rows, n_windows, window_s, g,
                         bool(params.get("phase_stagger", True)))
    return rows, arr


GENERATORS = {"engine_poisson": engine_poisson, "sim_bursts": sim_bursts}


def generator(name: str):
    """The generator a traffic mix names: the entry of `GENERATORS`, or
    else the `generate` function of `generators/<name>.py`."""
    if name in GENERATORS:
        return GENERATORS[name]
    from harness import load_module

    return load_module(GENERATORS_DIR / f"{name}.py",
                       "traffic generator").generate
