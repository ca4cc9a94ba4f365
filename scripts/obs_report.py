"""Observability-plane report: run a quick obs-enabled workload on each
substrate, decode the in-scan metric rings + grant-lifecycle event log,
and write the exported artifacts (JSON-lines + a Chrome-trace/perfetto
file that loads in ui.perfetto.dev).

    PYTHONPATH=src python scripts/obs_report.py --out bench_out/obs [--sim]
"""
from __future__ import annotations

import argparse
import pathlib

import jax
import jax.numpy as jnp

from repro.obs import metrics as obs_m
from repro.obs.export import write_report
from repro.serving import engine as E

OBS = obs_m.ObsConfig(enabled=True, ring_depth=64, event_capacity=4096)


def _engine_cfg() -> E.EngineConfig:
    # two shards so the cross-shard exchange runs and assist events land
    # in the log; link metering on so the byte account has traffic
    return E.EngineConfig(
        n_replicas=8, seq_slots=8, shadow_slots=2, pages_per_replica=64,
        page=16, max_pages=16, n_shards=2, link_pages_per_step=2, obs=OBS)


def engine_report(outdir: pathlib.Path, steps: int) -> None:
    cfg = _engine_cfg()
    state = E.init(cfg, jax.random.key(0))
    arrivals = jnp.zeros((cfg.n_replicas,), jnp.int32).at[0].set(4).at[1].set(2)
    arr_t = jnp.broadcast_to(arrivals, (steps, cfg.n_replicas))
    state, stats = E.run_steps(cfg, state, arr_t, k=steps)

    history = E.obs_history(state)
    totals = E.obs_totals(state)
    records, dropped = E.obs_events(state)
    trace = write_report(outdir, history, totals, records,
                         window_us=1000.0, substrate="engine")

    util = history["util"]
    print(f"engine: {steps} steps, R={cfg.n_replicas} S={cfg.n_shards}")
    print(f"  ring windows:   {util.shape[0]} x {util.shape[1]} replicas")
    print(f"  mean util:      {float(util.mean()):.3f}")
    print(f"  redirected:     {float(totals['redirected'].sum()):.0f} seqs")
    print(f"  link redirect:  {float(totals['link_redirect_bytes'].sum()):.0f} B")
    kinds = {}
    for r in records:
        kinds[r["event"]] = kinds.get(r["event"], 0) + 1
    print(f"  events:         {len(records)} ({dropped} dropped) {kinds}")
    print(f"  perfetto trace: {trace}")


def sim_report(outdir: pathlib.Path) -> None:
    from repro.jbof import platforms, sim, workloads as wl

    wls = [wl.micro(False, 4.0, qd=4, random_access=True)] * 4 \
        + [wl.idle()] * 4
    arr = wl.arrivals(wls, 200, seed=7)
    res = sim.simulate(platforms.xbof(), wls, arr,
                       cfg=sim.SimConfig(obs=OBS))
    obs = res.obs
    trace = write_report(outdir, obs["metrics"], obs["totals"],
                         obs["events"], window_us=1000.0,
                         substrate="jbof_sim")
    borrowed = obs["metrics"]["borrowed_seg"]
    print(f"sim: 200 windows, {arr.shape[1]} SSDs (XBOF)")
    print(f"  ring windows:   {borrowed.shape[0]}")
    print(f"  borrowed segs:  {float(borrowed[-1].sum()):.0f} at run end")
    print(f"  served:         {float(obs['totals']['served_bytes'].sum()) / 1e6:.0f} MB")
    print(f"  events:         {len(obs['events'])} "
          f"({obs['events_dropped']} dropped)")
    print(f"  perfetto trace: {trace}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="bench_out/obs",
                    help="directory for jsonl + perfetto artifacts")
    ap.add_argument("--steps", type=int, default=40,
                    help="engine steps for the report run")
    ap.add_argument("--sim", action="store_true",
                    help="also report the JBOF-sim substrate")
    args = ap.parse_args()

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    engine_report(outdir, args.steps)
    if args.sim:
        sim_report(outdir)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
