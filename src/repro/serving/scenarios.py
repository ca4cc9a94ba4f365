"""Shared engine scenarios + invariant drivers (DESIGN.md §8/§13).

One source of truth for the scenarios that benchmarks and tests drive
against the SAME engine:

  * the unified-LINK_BW-account scenario (`link_account_scenario` +
    `drive_link_account`): replica 0 memory-full (the §4.5 spill
    source), replica 1 just past the lend watermark so it keeps its own
    link allowance for §4.4 redirect commands — two debit flows, one
    account type, conservation asserted every step. Driven by
    `benchmarks/fig21_opcost.py`, `tests/test_costs.py`, and
    `tests/test_conservation.py`.

  * the failure/reclaim scenario (`failover_scenario` + `drive_events`):
    borrowers spill KV pages onto a lender, then a `core.events`
    schedule — the SAME typed schedule `jbof.sim` consumes — kills the
    lender (with or without a hot-remove warning). The driver applies
    dead transitions through `engine.fail_replica`, models
    LENDER_RECLAIM as a rising host-pinned fill of the lender's pool
    (what the reclaim predictor watches), and accounts sequences
    end-to-end so `benchmarks/fig23_failover.py` and the conservation
    suite gate zero-loss and bounded-spike from one code path.

Keeping scenario + assertion here means the benchmark and the test suite
cannot silently diverge.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import costs
from repro.core import events as ev_m
from repro.obs import metrics as obs_m
from . import engine as E

# replica 1 sits just past the 0.75 lend watermark (~78% HBM) but below
# the 0.98 borrow gate — it neither pledges its link allowance away nor
# gets its redirects vetoed
LEND_WATERMARK_FILL = 0.78125


def link_account_scenario(
    link_pages: int = 1, page: int = 2, quant: str = "none",
) -> tuple[E.EngineConfig, E.EngineState]:
    """(cfg, state) for the two-flow LINK_BW account scenario. Pools are
    big enough that the redirect source (replica 1) never trips the
    HBM-pressure gate on its own sequences; replica 0 is pre-filled full
    with long-lived page-hungry sequences so decode spills every step.
    ``quant="int8"`` runs the same flows over quantized KV pages — the
    budget and the per-page spill debit both reprice to the stored size."""
    cfg = E.EngineConfig(
        n_replicas=4, seq_slots=4, shadow_slots=4,
        pages_per_replica=32, page=page, kv_heads=2, head_dim=8,
        max_pages=8, link_pages_per_step=link_pages, kv_quant=quant)
    state = E.init(cfg, jax.random.key(0))
    pool = state.pool
    keep = int(cfg.pages_per_replica * LEND_WATERMARK_FILL)
    pool = pool._replace(
        used=pool.used.at[0].set(True).at[1, :keep].set(True),
        seq_active=pool.seq_active.at[0, : cfg.seq_slots].set(True))
    state = state._replace(
        pool=pool, remaining=state.remaining.at[0, : cfg.seq_slots].set(64))
    return cfg, state


class LinkAccountRun(NamedTuple):
    redirect_bytes: float   # cumulative §4.4 command debits, all replicas
    spill_bytes: float      # cumulative §4.5 spill-page debits
    budget_bytes: float     # cumulative published byte budgets
    cmd_saturated: bool     # some step left replica 1 < one command of headroom
    saw_redirect: bool
    saw_spill: bool


def check_link_account(step: int, budget, redirect, spill) -> None:
    """The unified LINK_BW account invariant for one step: per replica,
    redirect-command bytes + spill-page bytes never exceed the byte budget
    (own + borrowed − lent), and neither debit is negative. Raises
    RuntimeError on violation (fails a benchmark run and a test alike)."""
    b, r, s = (np.asarray(x) for x in (budget, redirect, spill))
    if not (r + s <= b + 1e-5).all() or (r < -1e-9).any() \
            or (s < -1e-9).any():
        raise RuntimeError(
            f"LINK_BW account violated at step {step}: "
            f"redirect {r} + spill {s} > budget {b}")


def drive_link_account(
    cfg: E.EngineConfig,
    state: E.EngineState,
    arrivals_fn: Callable[[int], jax.Array],
    steps: int,
) -> LinkAccountRun:
    """Drive ``steps`` engine steps, enforcing the account invariant on
    every one through `check_link_account`."""
    cmd_b = float(costs.REDIRECT_CMD_BYTES)
    red = spill = budget = 0.0
    cmd_saturated = saw_redirect = saw_spill = False
    for i in range(steps):
        state, st = E.step(cfg, state, arrivals_fn(i))
        b = np.asarray(st["link_budget_bytes"])
        r = np.asarray(st["link_redirect_bytes"])
        s = np.asarray(st["link_spill_bytes"])
        check_link_account(i, b, r, s)
        cmd_saturated |= bool((b[1] > 0) and (r[1] > b[1] - cmd_b))
        saw_redirect |= bool(r.sum() > 0)
        saw_spill |= bool(s.sum() > 0)
        red += float(r.sum())
        spill += float(s.sum())
        budget += float(b.sum())
    return LinkAccountRun(red, spill, budget, cmd_saturated,
                          saw_redirect, saw_spill)


def failover_scenario(
    migrate: int = 0, obs: bool = False, events: bool = False,
) -> tuple[E.EngineConfig, E.EngineState]:
    """(cfg, state) for the lender-crash scenario fig23 and the
    conservation suite share. Replicas 0/1 are borrowers whose 16-token
    sequences need 4 pages each — four active slots want 16 pages of a
    12-page pool, so ~4 pages per borrower spill offsite, split between
    the two idle lenders. Replica 2 takes the crash; replica 3 survives
    and is where the predictor-driven drain re-homes 2's pages (the
    borrowers' own pools are full when the warning lands, so pass-A
    home-drain has nowhere to go and the WAL-logged move goes
    lender-to-lender).

    ``migrate`` is the per-step drain allowance (0 = unpredicted run);
    ``obs`` turns the metric rings on (how the driver reports
    ``migrated_pages``); ``events`` reserves obs event-log capacity.
    """
    cfg = E.EngineConfig(
        n_replicas=4, seq_slots=4, shadow_slots=2,
        pages_per_replica=12, page=4, kv_heads=2, head_dim=8,
        max_pages=4, link_pages_per_step=8,
        track_failures=True, migrate_pages_per_step=migrate,
        obs=obs_m.ObsConfig(enabled=True, ring_depth=256,
                            event_capacity=512 if events else 64)
        if obs else obs_m.ObsConfig())
    return cfg, E.init(cfg, jax.random.key(0))


class FailoverRun(NamedTuple):
    """End-to-end accounting of one event-scheduled engine run."""

    completed: int        # sequences admitted AND decoded to completion
    aborted: int          # dead replicas' own sequences (client gone)
    requeued: int         # hosted sequences bounced back to their home
    lost_tokens: int      # KV tokens truncated off crashed lenders
    lost_sequences: int   # sequences neither completed nor aborted — the
                          # zero-loss gate (stuck in-flight at drain end)
    revoked: int          # descriptor rows invalidated by failures
    seq_steps: int        # sum over steps of active sequences — the
                          # latency integral the spike gates compare
    migrated_pages: int   # WAL-committed drain moves (0 unless cfg.obs)
    drained: bool         # system fully emptied within the settle window


def drive_events(
    cfg: E.EngineConfig,
    state: E.EngineState,
    sched: ev_m.EventSchedule,
    arrivals_fn: Callable[[int], np.ndarray],
    steps: int,
    settle: int = 96,
    ramp: int = 4,
) -> FailoverRun:
    """Drive the engine under a `core.events` schedule — the SAME typed
    schedule `jbof.sim` consumes — and account every sequence.

    Host-side, between jitted steps: SSD_FAIL / SSD_HOT_REMOVE dead
    transitions apply through `engine.fail_replica`; ENCLOSURE_DROP maps
    an enclosure to a shard and fails every replica in it; the
    LENDER_RECLAIM stream is modeled as the lender's own load returning —
    a host-pinned fill of its free pages rising to the full pool over
    ``ramp`` steps (owner_seq stays -1, so the pins are invisible to
    sequence accounting) and released when the stream clears. That is
    exactly the utilization signal the reclaim predictor watches, so a
    hot-remove's warning window gives `migrate_pages_per_step` something
    to act on.

    After the scheduled window the driver feeds zero arrivals for up to
    ``settle`` extra steps so requeued and re-decoding sequences can
    finish; a sequence still in flight then counts as lost.
    """
    n = cfg.n_replicas
    nl = E.local_replicas(cfg)
    ev = ev_m.compile(sched, max(steps, 1), n,
                      n_enclosures=max(cfg.n_shards, 1))
    reclaim_s = np.asarray(ev.reclaim)
    # enclosure == shard on the serving side: a fabric drop takes every
    # replica of the shard with it
    dead_s = np.asarray(ev.dead) | np.repeat(np.asarray(ev.drop), nl, axis=1)

    prev_dead = np.zeros((n,), bool)
    pinned = np.zeros((n, cfg.pages_per_replica), bool)
    rcount = np.zeros((n,), np.int64)
    chunk = -(-cfg.pages_per_replica // ramp)

    total_arrivals = 0
    aborted = requeued = lost_tokens = revoked = seq_steps = 0
    active = queued = 0
    drained = False
    for t in range(steps + settle):
        if t < steps:
            for r in np.nonzero(dead_s[t] & ~prev_dead)[0]:
                state, rep = E.fail_replica(cfg, state, int(r))
                aborted += rep.aborted
                requeued += rep.requeued
                lost_tokens += rep.lost_tokens
                revoked += rep.revoked
                pinned[r] = False
                rcount[r] = 0
            prev_dead |= dead_s[t]
            act = reclaim_s[t] & ~prev_dead
        else:
            act = np.zeros((n,), bool)
        if act.any() or pinned.any():
            used = np.array(state.pool.used)
            for r in range(n):
                if act[r]:
                    # the lender's own load ramping back: pin another
                    # chunk of its free pages each reclaim window
                    rcount[r] += 1
                    free = np.nonzero(~used[r])[0][:chunk]
                    used[r, free] = True
                    pinned[r, free] = True
                elif pinned[r].any():
                    used[r] &= ~pinned[r]
                    pinned[r] = False
                    rcount[r] = 0
            state = state._replace(
                pool=state.pool._replace(used=jnp.asarray(used)))
        arr = np.zeros((n,), np.int64)
        if t < steps:
            arr = np.where(prev_dead, 0, np.asarray(arrivals_fn(t)))
            total_arrivals += int(arr.sum())
        state, st = E.step(cfg, state, jnp.asarray(arr, jnp.int32))
        active, queued = int(st["active"]), int(st["queued"])
        seq_steps += active
        if t >= steps and active == 0 and queued == 0:
            drained = True
            break

    in_flight = 0 if drained else active + queued
    migrated = 0
    if cfg.obs.enabled:
        migrated = int(E.obs_totals(state)["migrated_pages"].sum())
    return FailoverRun(
        completed=total_arrivals - aborted - in_flight,
        aborted=aborted,
        requeued=requeued,
        lost_tokens=lost_tokens,
        lost_sequences=in_flight,
        revoked=revoked,
        seq_steps=seq_steps,
        migrated_pages=migrated,
        drained=drained,
    )
