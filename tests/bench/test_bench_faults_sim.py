"""A run of the simulator cell, on the CPU at a small size, past the look
for a chip: sound, it comes out correct; with the timed path broken
underneath, `correct` comes out false, once for each fault it can have
(on one chip there is no exchange between chips to leave out)."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import cpu_run  # noqa: E402

SEED = 3_000_000_023


def _state_unchanged(monkeypatch):
    from repro.jbof import sim

    orig = sim._window_step

    def step(state, *a, **kw):
        new, out = orig(state, *a, **kw)
        return state, out

    monkeypatch.setattr(sim, "_window_step", step)


def _half_batch(monkeypatch):
    from repro.jbof import sim

    orig = sim.simulate

    def simulate(plat, wls, arrivals, cfg=None, **kw):
        n = arrivals.shape[1]
        return orig(plat, wls, arrivals.at[:, n // 2:].set(0.0), cfg, **kw)

    monkeypatch.setattr(sim, "simulate", simulate)


def _answer_altered(monkeypatch):
    from repro.jbof import sim

    orig = sim.simulate

    def simulate(*a, **kw):
        res = orig(*a, **kw)
        return res._replace(
            throughput_bps=res.throughput_bps.at[0].multiply(1.1))

    monkeypatch.setattr(sim, "simulate", simulate)


def test_sound_run_is_correct(monkeypatch):
    line = cpu_run.run(monkeypatch, "sim.table2", SEED)
    assert line["correct"], line["checks"]
    assert line["metrics"]["sim_ssd_windows_s"]["value"] > 0


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    line = cpu_run.run(monkeypatch, "sim.table2", SEED)
    assert not line["correct"], line["checks"]
