"""Vectorized windowed JBOF simulation (lax.scan over 1 ms windows).

Fluid queueing model: per window and per SSD we compute resource *time*
demands (compute-end clocks, data-end channel time, host clocks, link bytes)
for the queued work, then serve the feasible fraction, carrying backlog.
Harvesting platforms redistribute compute-end capacity, DRAM segments and —
on XBOF+ — data-end channel time (FLASH_BW) and CXL link bytes (LINK_BW)
through the real `repro.core` descriptor machinery — the same code the
serving substrate runs on the TPU mesh. All four rtypes, DRAM included, are
granted exclusively through `ResourceManager.round()` claims: lenders
publish MRC-spare segments as DRAM descriptors, borrowers claim them, and
remote-segment cache hits pay the §4.6 CXL hop + dequeue/unwrap costs with
their lookup bytes metered on the LINK_BW account. Every redirection tax
is priced per-op from `repro.core.costs.OP_COSTS` (dequeue/unwrap + hops
over the borrower's per-command service time, cmd + payload link bytes),
so small-I/O assists pay steeply and large-I/O assists amortize; the
pre-refactor flat constants survive behind `Platform.flat_sync=True`
(DESIGN.md §8).

Latency is estimated analytically per closed-loop I/O depth: a QD-q tester
observes  latency ≈ max(unloaded service latency, q / throughput_rate)
(saturated closed loop ⇒ Little's law on the in-flight window, not on the
fluid backlog).

All per-SSD quantities are arrays of shape [n]; the step is jit-compiled and
scanned, so a 12-SSD x 4000-window run takes milliseconds.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import costs
from repro.core import descriptors as desc
from repro.core import events as ev_m
from repro.core import harvest as hv
from repro.core import manager as mgr
from repro.core import topology as topo
from repro.obs import export as obs_x
from repro.obs import metrics as obs_m
from repro.obs import spans as obs_s
from repro.telemetry import want as tele_want
from repro.telemetry import windows as tele_win
from . import ssd
from .platforms import Platform
from .workloads import Workload

_EPS = 1e-9
_PAGES_PER_SEGMENT = ssd.SEGMENT_BYTES // ssd.PAGE_BYTES

# Observability-plane registry (DESIGN.md §12), sim side: the per-window
# signals the ring captures without any per-step host sync. All ring-only
# (the sim has no stats dict); counters record measured per-window deltas
# so their totals reconcile with the SimState accumulators.
SIM_METRICS = obs_m.MetricSet("jbof-sim")
for _nm in ("miss", "borrowed_seg", "spare_seg", "q_bytes",
            "proc_util", "flash_util", "link_util"):
    SIM_METRICS.gauge(_nm, per="node")
for _nm in ("served_bytes", "cxl_bytes", "log_commits"):
    SIM_METRICS.counter(_nm, per="node")
SIM_METRICS.counter("energy_j", per="scalar")
SIM_METRICS.histogram("latency", bins=16, lo=0.0, hi=4e-3)
del _nm

# Telemetry-plane defaults for trace-driven runs (DESIGN.md §7): segment-
# granular addresses, 1/4 spatial sampling (coverage k/R = 512 distinct
# segments, curve span buckets*bucket_width = 512 segments) and a ~6-window
# estimator memory so the want tracks phase changes.
SIM_TELEMETRY = tele_win.TelemetryConfig(
    k=128, buckets=64, sample_mod=4, sample_thresh=1, bucket_width=8,
    decay=0.85, min_total=4.0)
# Dummy estimator for static runs: the state rides the scan carry either
# way (one pytree structure), but shrunk to a single table entry.
_NO_TELEMETRY = tele_win.TelemetryConfig(k=1, buckets=1)


class WorkloadVec(NamedTuple):
    """Static per-SSD workload parameters as arrays [n]."""

    rb_cmd: jax.Array      # bytes per read command
    wb_cmd: jax.Array      # bytes per write command
    qd: jax.Array          # closed-loop I/O depth
    locality: jax.Array    # mapping-lookup rate per command
    mrc_c0: jax.Array
    mrc_beta: jax.Array
    mrc_cold: jax.Array
    uniform_mrc: jax.Array


def workload_vec(workloads: list[Workload]) -> WorkloadVec:
    f = lambda g: jnp.asarray([g(w) for w in workloads], jnp.float32)
    return WorkloadVec(
        rb_cmd=f(lambda w: max(w.read_kb, 0.1) * 1024.0),
        wb_cmd=f(lambda w: max(w.write_kb, 0.1) * 1024.0),
        qd=f(lambda w: w.qd),
        locality=f(lambda w: min(max(w.locality, 1.0 / 4096.0), 1.0)),
        mrc_c0=f(lambda w: w.mrc_c0),
        mrc_beta=f(lambda w: w.mrc_beta),
        mrc_cold=f(lambda w: w.mrc_cold),
        uniform_mrc=jnp.asarray([w.uniform_mrc for w in workloads], jnp.bool_),
    )


class FabricIn(NamedTuple):
    """Per-enclosure cross-fabric grants, settled one mgmt round earlier.

    The multi-JBOF scan (`simulate(..., n_enclosures>1)`) federates each
    enclosure's post-local (spare, want) residuals through the topology
    plane's fabric level and feeds the settled scalars back into the next
    window's step — a one-round grant delay, exactly like the descriptor
    tables inside one enclosure. Units: lender-seconds for PROCESSOR
    (borrowers net out the fabric-tier per-op tax when converting to
    useful capacity), segments for DRAM."""

    proc_in: jax.Array   # [] lender-seconds granted to this enclosure
    proc_out: jax.Array  # [] lender-seconds drawn from this enclosure
    seg_in: jax.Array    # [] segments granted in across the fabric
    seg_out: jax.Array   # [] segments this enclosure lends out


class FabricOut(NamedTuple):
    """Per-enclosure post-local residual summary — what one enclosure
    publishes upward to the fabric level: spare it could still lend and
    want its local pool could not fill. PROCESSOR in lender-seconds
    (lend-triggered nodes only), DRAM in segments."""

    proc_spare: jax.Array  # []
    proc_want: jax.Array   # []
    seg_spare: jax.Array   # []
    seg_want: jax.Array    # []


def _pool_share(per_node, cap):
    """Distribute a pool-level grant ``cap`` over nodes ∝ ``per_node``
    (clipped at the pool total so nothing is conjured)."""
    pool = jnp.sum(per_node)
    take = jnp.minimum(cap, pool)
    return per_node * take / jnp.maximum(pool, _EPS)


class SimState(NamedTuple):
    q_r: jax.Array           # [n] read backlog bytes
    q_w: jax.Array           # [n] write backlog bytes
    vh_debt: jax.Array       # [n] bytes parked on lenders awaiting copyback
    borrowed_seg: jax.Array  # [n] DRAM segments borrowed (XBOF §4.5)
    borrowed_far: jax.Array  # [n] segments held across the fabric (≫ hops)
    table: desc.IdleResourceTable
    # per-node windowed-SHARDS estimator state (trace-driven runs; a 1-entry
    # dummy otherwise so the carry pytree keeps one structure)
    mrc: object
    # PMU-style measured utilizations from the previous window (the paper
    # polls busy clocks every 10 ms; demand-based estimates are wrong for
    # triggers because a saturated queue makes every resource "look" busy).
    # Lend/borrow triggers use OWN-work utilization (assist work excluded)
    # so harvesting cannot flap its own trigger; borrow GATES use EFFECTIVE
    # utilization (own+remote work over own+granted capacity) so one
    # rtype's successful harvest does not read as "exhausted" and cancel
    # another's — the multi-resource generalization of the §4.4 hysteresis.
    prev_proc_own: jax.Array   # [n] own-work compute-end utilization
    prev_flash: jax.Array      # [n] EFFECTIVE data-end util (PROCESSOR gate)
    prev_flash_own: jax.Array  # [n] own-work data-end util (FLASH_BW trigger)
    prev_link: jax.Array       # [n] EFFECTIVE link util (FLASH_BW gate)
    prev_link_own: jax.Array   # [n] own-work link util (LINK_BW trigger)
    # accumulators
    served_r: jax.Array      # [n] bytes
    served_w: jax.Array      # [n] bytes
    proc_busy: jax.Array     # [n] clock-seconds of compute-end work
    flash_busy: jax.Array    # [n] channel-seconds
    host_busy: jax.Array     # host clock-seconds (scalar)
    flash_written: jax.Array # [n] bytes programmed (DWPD accounting)
    lat_sum: jax.Array       # [n] sum(latency * served commands)
    cmd_count: jax.Array     # [n] served commands
    log_commits: jax.Array   # [n] WAL commits (XBOF)
    energy_j: jax.Array      # scalar total energy
    cxl_bytes: jax.Array     # [n] inter-SSD traffic
    # observability plane state ((MetricsState, EventLog)) when the run
    # passes ObsConfig(enabled=True), else None — an empty pytree, so a
    # disabled run's carry has exactly the pre-obs leaves
    obs: object = None


class SimResult(NamedTuple):
    throughput_bps: jax.Array   # [n]
    read_bps: jax.Array         # [n]
    write_bps: jax.Array        # [n]
    latency_s: jax.Array        # [n] mean per-command latency
    proc_util: jax.Array        # [n]
    flash_util: jax.Array       # [n]
    miss_ratio: jax.Array       # [n] final mapping-table miss ratio
    dwpd: jax.Array             # [n] drive-writes-per-day equivalent
    energy_j: jax.Array
    host_util: jax.Array
    log_commits: jax.Array      # [n]
    cxl_bytes: jax.Array        # [n]
    borrowed_seg: jax.Array     # [n] final DRAM segments held via claims (§4.5)
    borrowed_far: jax.Array | None = None  # [n] final cross-fabric segments
    # Per-window histories: always carries the full-run scan series
    # {"borrowed_seg", "spare_seg"} [T, n]; event-scheduled runs add
    # {"revoked_grants"} [T] (descriptor slots + fabric grants invalidated
    # per window). With obs enabled the ring-sourced tail of every
    # SIM_METRICS metric is exposed through `obs["metrics"]` instead.
    rings: dict | None = None
    # {"metrics": ring histories, "totals", "events", "events_dropped"}
    # when the run had ObsConfig(enabled=True), else None
    obs: dict | None = None


def _miss_ratio(wv: WorkloadVec, cache_frac: jax.Array) -> jax.Array:
    param = jnp.clip(
        wv.mrc_cold + (1.0 - wv.mrc_cold) * (1.0 + cache_frac / wv.mrc_c0) ** (-wv.mrc_beta),
        0.0, 1.0,
    )
    uniform = jnp.clip(1.0 - cache_frac, wv.mrc_cold, 1.0)
    return jnp.where(wv.uniform_mrc, uniform, param)


def static_want_frac(wv: WorkloadVec) -> jax.Array:
    """float32[n] — the §4.5 want fraction from the 33-point parametric MRC
    grid. Workload-static, so it is evaluated ONCE per run (it used to be
    recomputed inside every scanned window) and fed to the step as data;
    trace-driven runs replace it with the online estimate."""
    n = wv.rb_cmd.shape[0]
    grid = jnp.linspace(0.0, 1.0, 33)
    mgrid = jax.vmap(lambda c: _miss_ratio(wv, jnp.full((n,), c)))(grid)  # [33, n]
    return hv.want_fraction(mgrid, wv.locality, grid)


def _policies(plat: Platform) -> tuple[tuple[mgr.ResourcePolicy, ...], int]:
    """Registry-driven per-rtype policies for this platform's round: slots
    [0, n_slots) fragment the proc surplus; XBOF appends a DRAM slot range
    (§4.5 segment lending), XBOF+ appends FLASH_BW and LINK_BW slot ranges —
    every harvested substrate flows through the SAME publish/claim
    machinery. Returns (policies, total_slots)."""
    pols = []
    s0 = 0
    if plat.harvest_proc:
        pols.append(mgr.ResourcePolicy(
            rtype=desc.PROCESSOR, slot0=0, slots=plat.n_slots,
            claim_rounds=plat.claim_rounds, watermark=plat.watermark,
            gate_watermark=plat.data_watermark,
            preserve_claims=True, gate_new_only=True))
        s0 = plat.n_slots
    if plat.harvest_dram:
        # DRAM "utilization" is the MRC-derived segment-need signal (see
        # `_window_step`): >1 iff the node wants segments, so the generic
        # quadrant trigger reads it like any busy resource. Lenders publish
        # their spare-segment count as the descriptor amount; borrowing is
        # gated on link headroom (remote hits ride the CXL fabric).
        pols.append(mgr.ResourcePolicy(
            rtype=desc.DRAM, slot0=s0, slots=plat.dram_slots,
            claim_rounds=plat.claim_rounds, watermark=plat.watermark,
            gate_watermark=plat.link_watermark, min_amount=1.0,
            preserve_claims=True, gate_new_only=True))
        s0 += plat.dram_slots
    if plat.harvest_flash:
        pols.append(mgr.ResourcePolicy(
            rtype=desc.FLASH_BW, slot0=s0, slots=plat.flash_slots,
            claim_rounds=plat.claim_rounds, watermark=plat.watermark,
            gate_watermark=plat.link_watermark,
            preserve_claims=True, gate_new_only=True))
        s0 += plat.flash_slots
    if plat.harvest_link:
        pols.append(mgr.ResourcePolicy(
            rtype=desc.LINK_BW, slot0=s0, slots=plat.link_slots,
            claim_rounds=plat.claim_rounds, watermark=plat.watermark,
            preserve_claims=True, gate_new_only=True))
        s0 += plat.link_slots
    return tuple(pols), s0


def _manager(plat: Platform) -> mgr.ResourceManager:
    """The sim's view of the unified management round: one ResourcePolicy
    per harvested rtype, `claim_rounds` sweeps each."""
    pols, total_slots = _policies(plat)
    return mgr.ResourceManager(mgr.ManagerConfig(
        n_slots=max(total_slots, 1), policies=pols))


def _unloaded_latency(wv: WorkloadVec, read: bool, miss, remote_frac,
                      offsite_frac, plat: Platform,
                      proc_ovh=ssd.SYNC_PROC_OVERHEAD,
                      far_frac=None, offsite_far=None):
    """Fig 14a decomposition: Host + Host-SSD + Processor + DRAM + Flash + Inter-SSD.

    ``proc_ovh``: fractional sync tax on redirected compute — the flat §5.3
    constant under ``flat_sync`` (the per-op model instead charges the fixed
    §4.6 protocol cost once, in the Inter-SSD term, so it passes 0 here).
    Remote-access unit prices come from the §4.6 table (`core.costs`)."""
    io_bytes = wv.rb_cmd if read else wv.wb_cmd
    slices = jnp.maximum(io_bytes / ssd.SLICE_BYTES, 1.0)
    per_slice = ssd.C_READ_SLICE if read else ssd.C_WRITE_SLICE
    proc = (ssd.C_PARSE + slices * per_slice) / ssd.CLOCK_HZ
    proc = proc * (1.0 + proc_ovh * remote_frac)
    if plat.oc:
        proc = proc + ssd.C_HOST_FW / ssd.HOST_CLOCK_HZ
    # mapping-cache hits served from borrowed segments (§4.5) are remote:
    # each pays the per-op §4.6 DRAM price (CXL hop + dequeue/unwrap)
    remote_hit_s = costs.op_overhead_s(
        desc.DRAM, dequeue_s=plat.inter_ssd_op_s, hop_s=plat.cxl_hop_s)
    remote_hits_cmd = wv.locality * (1.0 - miss) * offsite_frac
    dram = ssd.DRAM_LOOKUP_S * slices + remote_hits_cmd * remote_hit_s
    # the fabric tier's extra traversals, on top of the intra price above
    # (remote_frac / offsite_frac already include the far shares)
    far_extra_s = plat.fabric_extra_hops * plat.cxl_hop_s
    if offsite_far is not None:
        far_hits_cmd = wv.locality * (1.0 - miss) * offsite_far
        dram = dram + far_hits_cmd * far_extra_s
    xfer = io_bytes / (ssd.CHANNEL_BUS_BPS / ssd.N_CHANNELS)
    flash_t = ssd.T_READ_AVG if read else 8e-6  # write acks from PLP'd buffer
    lookups = wv.locality  # mapping lookups per command
    flash = flash_t + xfer + miss * lookups * ssd.MAPPING_PAGE_READ_S
    inter = remote_frac * costs.op_overhead_s(
        desc.PROCESSOR, dequeue_s=plat.inter_ssd_op_s, hop_s=plat.cxl_hop_s)
    if far_frac is not None:
        inter = inter + far_frac * far_extra_s
    link = io_bytes / ssd.CXL_BPS_PER_SSD + ssd.T_HOST_SSD_CMD
    host = ssd.T_HOST_STACK + (plat.host_extra_clocks / ssd.HOST_CLOCK_HZ if not plat.oc else 0.0)
    return host + link + proc + dram + flash + inter


@partial(jax.jit, static_argnames=("plat", "window_s", "warmup",
                                   "trace_driven", "tcfg", "obs"))
def _window_step(state: SimState, arr, trace, *, plat: Platform,
                 wv: WorkloadVec, want_frac: jax.Array, window_s: float,
                 step_idx, warmup: int = 0, trace_driven: bool = False,
                 tcfg: tele_win.TelemetryConfig = _NO_TELEMETRY,
                 fabric: FabricIn | None = None,
                 obs: obs_m.ObsConfig = obs_m.ObsConfig(),
                 ev: ev_m.NodeEvents | None = None):
    # ``fabric`` — cross-enclosure grants from the fabric level of the
    # topology plane, or None when this enclosure is the whole world.
    # None keeps the single-enclosure program IDENTICAL to the
    # pre-topology step (every fabric term is a Python-level branch, not a
    # zero-valued op), so pinned single-JBOF baselines cannot drift.
    # ``ev`` — this window's failure/reclaim streams (`core.events`), the
    # same Python-branch discipline: None traces the exact event-free
    # program. A dead node serves nothing, its capacities are zero and
    # its standing descriptors/claims revoke; a reclaiming lender is
    # forced busy so the ordinary §4.3/§4.4 machinery drains its grants.
    n = state.q_r.shape[0]
    cfg = plat.ssd_config

    # -------------------------------------------------- arrivals & backlog
    q_r = state.q_r + arr[:, 0]
    q_w = state.q_w + arr[:, 1]
    if ev is not None:
        # a dead SSD's backlog is lost with the device and it admits
        # nothing new; reclaiming lenders keep serving their own work
        q_r = jnp.where(ev.dead, 0.0, q_r)
        q_w = jnp.where(ev.dead, 0.0, q_w)
    # fluid backlog bound: 3x one-window peak capacity (submission throttling)
    cap_bytes = (ssd.PEAK_READ_BPS + ssd.PEAK_WRITE_BPS) * window_s * 3.0
    q_r = jnp.minimum(q_r, cap_bytes)
    q_w = jnp.minimum(q_w, cap_bytes)

    cmds_r = q_r / wv.rb_cmd
    cmds_w = q_w / wv.wb_cmd
    slices_r = q_r / ssd.SLICE_BYTES
    slices_w = q_w / ssd.SLICE_BYTES

    # ------------------------------------------------------- DRAM / misses
    own_seg = float(cfg.dram_segments)
    seg_eff = own_seg + state.borrowed_seg
    if fabric is not None:
        # segments claimed through the fabric cache mappings like any
        # borrowed segment; only their per-hit price differs (below)
        seg_eff = seg_eff + state.borrowed_far
    cache_frac = jnp.clip(seg_eff / float(ssd.SEGMENTS_FULL), 0.0, 1.0)
    mrc_state = state.mrc
    if trace_driven:
        # telemetry plane (DESIGN.md §7): fold this window's mapping-page
        # references into the per-node windowed-SHARDS estimators at DRAM-
        # segment granularity (caching is segment-granular, so segment
        # reuse distances are the curve that sizes segment counts), and
        # read the miss ratio off the ONLINE curve at the current cache
        # size — phase changes in the trace move it, which the per-run
        # parametric curve cannot do.
        t_mask = trace != tele_win.EMPTY_REF
        seg_addr = jnp.where(t_mask, trace // _PAGES_PER_SEGMENT, trace)
        mrc_state = tele_win.update_window(mrc_state, seg_addr, tcfg,
                                           mask=t_mask)
        miss = jnp.clip(
            tele_win.miss_at_batch(mrc_state, seg_eff, tcfg), 0.0, 1.0)
    else:
        miss = _miss_ratio(wv, cache_frac)
    offsite_frac = jnp.where(seg_eff > 0, state.borrowed_seg / jnp.maximum(seg_eff, 1.0), 0.0)
    offsite_far = jnp.zeros((n,), jnp.float32)
    if fabric is not None:
        offsite_far = jnp.where(
            seg_eff > 0, state.borrowed_far / jnp.maximum(seg_eff, 1.0), 0.0)
        offsite_frac = offsite_frac + offsite_far
    # mapping-table lookups that reach the cache (spatial locality folds
    # same-page lookups together): per command, not per slice
    lookups = (cmds_r + cmds_w) * wv.locality
    miss_lookups = lookups * miss
    hit_lookups = lookups - miss_lookups

    # §4.5 MRC-derived lend/borrow amounts — the DRAM descriptors' inputs.
    # Trigger on the MEASURED lookup miss ratio (spatial locality folds
    # same-page lookups into hits): sequential streams never borrow, random
    # small-I/O workloads borrow until the per-lookup miss is under target.
    # Borrowing targets the MRC-derived want (a stable fixed point); gating
    # on the instantaneous miss ratio would oscillate: the grant itself
    # pushes miss under target, which would then cancel the grant.
    seg_need = jnp.zeros((n,), jnp.float32)
    seg_spare = jnp.zeros((n,), jnp.float32)
    dram_util = jnp.zeros((n,), jnp.float32)
    if plat.harvest_dram:
        min_keep = hv.DRAM_MIN_KEEP_SEGMENTS
        if trace_driven:
            # online want: smallest segment count whose estimated per-
            # lookup miss is under target. The estimator's activity floor
            # replaces the arrival-rate `active` test — a node whose trace
            # went quiet (or shrank to a small set) wants min_keep again
            # and RETURNS its borrowed segments mid-run, which no signal
            # derived from byte demand alone can trigger.
            est = tele_want.want_entries(mrc_state, tcfg, weight=wv.locality)
            want_seg = jnp.clip(est, min_keep, float(ssd.SEGMENTS_FULL))
            seg_need = jnp.maximum(want_seg - own_seg, 0.0)
        else:
            # static parametric grid (`static_want_frac`, hoisted out of
            # the scan body — workload-static, once per run)
            active = lookups > 1.0  # >1 mapping lookup per window
            want_seg = jnp.where(active, want_frac * ssd.SEGMENTS_FULL, min_keep)
            seg_need = jnp.where(active, jnp.maximum(want_seg - own_seg, 0.0), 0.0)
        seg_spare = jnp.maximum(own_seg - jnp.maximum(want_seg, min_keep), 0.0)
        seg_spare_gross = seg_spare
        if fabric is not None:
            # segments already lent across the fabric are occupied by the
            # remote borrowers' mappings — withdraw them from the spare
            # published into the local round so one segment can never be
            # lent through two levels at once
            seg_spare = jnp.maximum(
                seg_spare - _pool_share(seg_spare, fabric.seg_out), 0.0)
        # the DRAM descriptors' "utilization": >watermark iff the node
        # wants segments, ordered by how starved it is — what makes the
        # generic busiest-first claim sweeps serve the §4.5 semantics
        dram_util = jnp.where(
            seg_need > 0, 1.0 + seg_need / float(ssd.SEGMENTS_FULL), 0.0)
        if ev is not None:
            # a reclaiming (or dead) lender's segments are spoken for —
            # zero published spare drains its standing grants at this
            # very window's transfer derivation; dead nodes also stop
            # wanting (their mappings died with them)
            force = ev.dead | ev.reclaim
            seg_spare = jnp.where(force, 0.0, seg_spare)
            seg_spare_gross = jnp.where(force, 0.0, seg_spare_gross)
            seg_need = jnp.where(ev.dead, 0.0, seg_need)
            dram_util = jnp.where(ev.dead, 0.0, dram_util)

    # ------------------------------------------------------ demand (times)
    ppc = (
        cmds_r * ssd.C_PARSE + slices_r * ssd.C_READ_SLICE
        + cmds_w * ssd.C_PARSE + slices_w * ssd.C_WRITE_SLICE
        + miss_lookups * ssd.C_MISS_EXTRA
    )
    # per-op §4.6 pricing inputs: commands this window, their average I/O
    # size and per-command service times — what the cost table turns into
    # I/O-size-dependent overhead fractions and link byte rates
    ops = cmds_r + cmds_w
    io_avg = (q_r + q_w) / jnp.maximum(ops, _EPS)
    proc_op_s = ppc / ssd.CLOCK_HZ / jnp.maximum(ops, _EPS)
    # WAL commits for offsite metadata updates (writes touch the mapping)
    log_ops = slices_w * offsite_frac * (1.0 if plat.harvest_dram else 0.0)
    # §4.5/§4.6 remote-access cost: a mapping-cache hit served from a
    # borrowed segment stalls the compute end for the per-op DRAM price
    # (CXL hop + remote dequeue/unwrap) — the tax the old model only
    # charged on WAL writes, which made borrowed segments read for free
    remote_hit_s = costs.op_overhead_s(
        desc.DRAM, dequeue_s=plat.inter_ssd_op_s, hop_s=plat.cxl_hop_s)
    remote_hits = hit_lookups * offsite_frac
    proc_demand_s = ppc / ssd.CLOCK_HZ + log_ops * ssd.T_LOG_COMMIT \
        + remote_hits * remote_hit_s
    remote_hits_far = jnp.zeros((n,), jnp.float32)
    if fabric is not None:
        # a hit in a segment held across the fabric pays the tier-2 price:
        # the intra-enclosure per-op cost (already charged above, far hits
        # are part of `remote_hits`) PLUS the extra inter-JBOF traversals
        remote_hits_far = hit_lookups * offsite_far
        far_hit_extra_s = (
            costs.tier_overhead_s(
                desc.DRAM, dequeue_s=plat.inter_ssd_op_s,
                hop_s=plat.cxl_hop_s, extra_hops=plat.fabric_extra_hops)
            - remote_hit_s)
        proc_demand_s = proc_demand_s + remote_hits_far * far_hit_extra_s

    pages_r = q_r / ssd.PAGE_BYTES
    small_w = wv.wb_cmd < ssd.PAGE_BYTES
    amp = jnp.where(small_w, ssd.SLC_AMP_SMALL_WRITE, 1.0)
    pages_w = q_w / ssd.PAGE_BYTES * amp
    # WAL log-page flush-backs: every 512 commits flushes one 2 MB segment
    log_flush_pages = log_ops / 512.0 * (ssd.SEGMENT_BYTES / ssd.PAGE_BYTES)
    flash_time = (
        pages_r / ssd.F_READ_PAGES
        + pages_w / ssd.F_PROG_PAGES
        + miss_lookups / ssd.F_READ_PAGES          # mapping-page fetches
        + log_flush_pages / ssd.F_PROG_PAGES
    )

    host_clocks = (cmds_r + cmds_w) * (ssd.C_HOST_DRIVER + plat.host_extra_clocks)
    if plat.oc:  # firmware runs on the host pool, with kernel-stack inefficiency
        host_clocks = host_clocks + ppc * ssd.OC_HOST_INEFF
    # remote-lookup bytes ride the LINK_BW account: DRAM borrowing competes
    # with I/O data and flash/link assist traffic for the port
    # the mapping line is the payload of the lookup — it compresses at the
    # platform's payload ratio (int8-KV analogue); a compressed line still
    # pays full descriptor overheads upstream in overhead_frac
    lookup_bytes = costs.op_link_bytes(
        desc.DRAM,
        cmd_bytes=plat.remote_lookup_bytes * plat.payload_comp_ratio)
    link_time = (q_r + q_w
                 + remote_hits * lookup_bytes) / ssd.CXL_BPS_PER_SSD
    far_lookup_extra_b = 0.0
    if fabric is not None:
        # fabric-tier lookups re-cross the port once per extra hop
        far_lookup_extra_b = (
            costs.tier_link_bytes(
                desc.DRAM,
                cmd_bytes=plat.remote_lookup_bytes * plat.payload_comp_ratio,
                extra_hops=plat.fabric_extra_hops)
            - lookup_bytes)
        link_time = link_time + (
            remote_hits_far * far_lookup_extra_b / ssd.CXL_BPS_PER_SSD)

    # -------------------------------------------------------- capacities
    proc_cap_s = (0.0 if plat.oc else cfg.proc_clocks_per_s / ssd.CLOCK_HZ) * window_s
    proc_cap_s = jnp.full((n,), proc_cap_s, jnp.float32)
    flash_cap_s = jnp.full((n,), window_s, jnp.float32)
    if ev is not None:
        proc_cap_s = jnp.where(ev.dead, 0.0, proc_cap_s)
        flash_cap_s = jnp.where(ev.dead, 0.0, flash_cap_s)

    # trigger utilizations: measured (previous window), per the paper's PMU
    # polling. Lender triggers use OWN-work utilization so that assisting a
    # borrower does not flap the lend decision.
    proc_util_est = state.prev_proc_own
    flash_util_est = state.prev_flash
    link_est = state.prev_link
    flash_own_est = state.prev_flash_own
    link_own_est = state.prev_link_own
    if ev is not None:
        # forced-busy lenders: a reclaiming node reads saturated on every
        # lend trigger (its resources are spoken for); a dead node reads
        # saturated on both trigger AND gate, so it neither lends nor
        # borrows through the round
        force = ev.dead | ev.reclaim
        proc_util_est = jnp.where(force, 1.0, proc_util_est)
        flash_own_est = jnp.where(force, 1.0, flash_own_est)
        link_own_est = jnp.where(force, 1.0, link_own_est)
        flash_util_est = jnp.where(ev.dead, 1.0, flash_util_est)
        link_est = jnp.where(ev.dead, 1.0, link_est)

    # ---------------------------------- management round (§4.3, all rtypes)
    assist_in = jnp.zeros((n,), jnp.float32)
    used_from = jnp.zeros((n, n), jnp.float32)
    remote_frac = jnp.zeros((n,), jnp.float32)
    table = state.table
    revoked = jnp.int32(0)
    if ev is not None:
        # failure-forced §4.3 descriptor invalidation: a dead node's
        # published slots go invalid and its held claims release NOW —
        # not at the next mgmt round — so every standing grant of a
        # failed lender drops at this window's transfer derivation
        table, revoked = mgr.revoke_nodes(table, ev.dead)
    any_harvest = (plat.harvest_proc or plat.harvest_dram
                   or plat.harvest_flash or plat.harvest_link)
    if any_harvest:
        manager = _manager(plat)
        do_mgmt = (step_idx % plat.mgmt_interval) == 0
        inputs = {}
        if plat.harvest_proc:
            inputs[desc.PROCESSOR] = mgr.RoundInputs(
                util=proc_util_est, gate_util=flash_util_est)
        if plat.harvest_dram:
            inputs[desc.DRAM] = mgr.RoundInputs(
                util=dram_util, gate_util=link_est, amount=seg_spare)
        if plat.harvest_flash:
            inputs[desc.FLASH_BW] = mgr.RoundInputs(
                util=flash_own_est, gate_util=link_est,
                amount=jnp.maximum(1.0 - flash_own_est, 0.0) * window_s)
        if plat.harvest_link:
            inputs[desc.LINK_BW] = mgr.RoundInputs(
                util=link_own_est,
                amount=jnp.maximum(1.0 - link_own_est, 0.0) * window_s)
        new_table = manager.round(table, inputs)
        table = jax.tree.map(lambda a, b: jnp.where(do_mgmt, b, a), table, new_table)

    # ------------------------------------------ processor harvesting (§4.4)
    # The redirection tax: flat §5.3 constant under `flat_sync`, else the
    # per-op §4.6 price (2 dequeue/unwrap + 1 hop per command) over the
    # borrower's per-command compute time — 4 KB commands pay a far
    # steeper fractional tax than 256 KB commands (DESIGN.md §8).
    if plat.flat_sync:
        proc_ovh = ssd.SYNC_PROC_OVERHEAD
    else:
        proc_ovh = costs.overhead_frac(
            desc.PROCESSOR, proc_op_s,
            dequeue_s=plat.inter_ssd_op_s, hop_s=plat.cxl_hop_s)
    far_in = jnp.zeros((n,), jnp.float32)
    far_out = jnp.zeros((n,), jnp.float32)
    far_frac = jnp.zeros((n,), jnp.float32)
    proc_resid_spare = jnp.float32(0.0)
    proc_resid_want = jnp.float32(0.0)
    if plat.harvest_proc:
        M = manager.assist_matrix(table, desc.PROCESSOR)  # [lender, borrower]
        surplus = jnp.maximum(proc_cap_s - proc_demand_s, 0.0)
        deficit = jnp.maximum(proc_demand_s - proc_cap_s, 0.0)
        assist_in, used_from = mgr.fluid_transfer(
            M, surplus, deficit, proc_ovh)
        remote_frac = jnp.where(
            proc_demand_s > 0, assist_in / jnp.maximum(proc_demand_s, _EPS), 0.0
        )
        if not plat.flat_sync:
            # §4.4 redirection command descriptors ride the one LINK_BW
            # account alongside I/O data, lookup bytes and assist payloads
            red_ops = assist_in / jnp.maximum(proc_op_s, _EPS)
            link_time = link_time + (
                red_ops * costs.op_link_bytes(desc.PROCESSOR)
                / ssd.CXL_BPS_PER_SSD)
        if fabric is not None:
            # ---- fabric level: grants settled one mgmt round ago spill in.
            # Lender-seconds drawn from this enclosure come from lend-
            # triggered nodes' undonated surplus; lender-seconds granted in
            # distribute over the residual (locally-unmet) deficits, net of
            # the tier-2 per-op tax — a far-redirected command pays extra
            # inter-JBOF traversals per op, so the same donated second buys
            # strictly less useful work than an enclosure-local one.
            per_op_far = costs.tier_overhead_s(
                desc.PROCESSOR, dequeue_s=plat.inter_ssd_op_s,
                hop_s=plat.cxl_hop_s, extra_hops=plat.fabric_extra_hops)
            ovh_far = jnp.clip(
                per_op_far / jnp.maximum(proc_op_s, _EPS), 0.0, 1e3)
            out_rem = jnp.where(
                state.prev_proc_own <= plat.watermark,
                jnp.maximum(surplus - jnp.sum(used_from, axis=1), 0.0), 0.0)
            far_out = _pool_share(out_rem, fabric.proc_out)
            resid_def = jnp.maximum(deficit - assist_in, 0.0)
            far_gross = _pool_share(resid_def * (1.0 + ovh_far),
                                    fabric.proc_in)
            far_in = far_gross / (1.0 + ovh_far)
            far_frac = jnp.where(
                proc_demand_s > 0,
                far_in / jnp.maximum(proc_demand_s, _EPS), 0.0)
            remote_frac = remote_frac + far_frac
            if not plat.flat_sync:
                red_far = far_in / jnp.maximum(proc_op_s, _EPS)
                link_time = link_time + (
                    red_far * costs.tier_link_bytes(
                        desc.PROCESSOR, extra_hops=plat.fabric_extra_hops)
                    / ssd.CXL_BPS_PER_SSD)
            # published residuals are GROSS of the currently-held fabric
            # grants: each mgmt round re-settles the complete assignment
            # (grants replace, never accumulate — far_in/far_out above are
            # full re-distributions of the standing grant). Publishing net
            # of held grants would zero the want one round after a grant
            # and flap the settlement at the mgmt period.
            proc_resid_spare = jnp.sum(out_rem)
            proc_resid_want = jnp.sum(resid_def)

    # --------------------------------------------- DRAM harvesting (§4.5)
    # Borrowed segments come through the SAME publish/claim round as every
    # other rtype: idle nodes publish their MRC-spare segments as DRAM
    # descriptors, needy nodes claim them in the busiest-first sweeps, and
    # the per-rtype assist matrix turns pledges into granted segments —
    # capped at each borrower's need, conserving each lender's published
    # spare. No omniscient pool / total-need formula anywhere.
    borrowed_seg = state.borrowed_seg
    borrowed_far = state.borrowed_far
    seg_resid_spare = jnp.float32(0.0)
    seg_resid_want = jnp.float32(0.0)
    if plat.harvest_dram:
        Md = manager.assist_matrix(table, desc.DRAM)  # [lender, borrower]
        borrowed_seg, seg_lent = mgr.fluid_transfer(Md, seg_spare, seg_need)
        if fabric is not None:
            # segments granted across the fabric cover what the local round
            # could not: distribute over the residual needs. seg_spare is
            # already net of this enclosure's own fabric lends (above), so
            # the residual spare published up is genuinely uncommitted.
            resid_need = jnp.maximum(seg_need - borrowed_seg, 0.0)
            borrowed_far = _pool_share(resid_need, fabric.seg_in)
            # gross residuals, as for PROCESSOR above: the spare offered
            # upward includes segments currently on loan through the
            # fabric (gross spare minus local lends), and the want
            # includes segments currently held — renewal, not delta
            seg_resid_spare = jnp.sum(jnp.maximum(
                seg_spare_gross - jnp.sum(seg_lent, axis=1), 0.0))
            seg_resid_want = jnp.sum(resid_need)

    # ------------------------------------------------ VH write redirection
    vh_debt = state.vh_debt
    vh_extra_flash = jnp.zeros((n,), jnp.float32)
    vh_redirect_bytes = jnp.zeros((n,), jnp.float32)
    drain_bytes = jnp.zeros((n,), jnp.float32)
    if plat.vh:
        flash_over = jnp.maximum(flash_time - flash_cap_s, 0.0)
        w_share = (pages_w / ssd.F_PROG_PAGES) / jnp.maximum(flash_time, _EPS)
        overflow_w_time = flash_over * w_share
        overflow_bytes = overflow_w_time * ssd.F_PROG_PAGES * ssd.PAGE_BYTES
        lender_spare_t = jnp.maximum(flash_cap_s - flash_time, 0.0) * 0.9
        pool_t = jnp.sum(lender_spare_t)
        frac = jnp.minimum(pool_t / jnp.maximum(jnp.sum(overflow_w_time), _EPS), 1.0)
        granted_t = overflow_w_time * frac
        vh_redirect_bytes = jnp.where(overflow_w_time > 0, overflow_bytes * frac, 0.0)
        absorb = jnp.where(
            pool_t > 0, lender_spare_t / jnp.maximum(pool_t, _EPS), 0.0
        ) * jnp.sum(granted_t)
        vh_extra_flash = absorb
        flash_time = flash_time - granted_t
        if plat.vh_copyback:
            vh_debt = vh_debt + vh_redirect_bytes
            # the hypervisor must reclaim lenders: once debt exists it drains
            # continuously (deadline-bound), reserving borrower program slots
            # — this contention is exactly what "sweeps out" VH's burst gains
            # (§5.2). Reserve up to 30% of the borrower backbone for drain.
            reserve_t = jnp.minimum(
                vh_debt / ssd.PAGE_BYTES / ssd.F_PROG_PAGES, flash_cap_s * 0.3
            )
            drain_bytes = reserve_t * ssd.F_PROG_PAGES * ssd.PAGE_BYTES
            drain_bytes = jnp.minimum(drain_bytes, vh_debt)
            flash_time = flash_time + drain_bytes / ssd.PAGE_BYTES / ssd.F_PROG_PAGES
            vh_extra_flash = vh_extra_flash + drain_bytes / ssd.PAGE_BYTES / ssd.F_READ_PAGES
            vh_debt = vh_debt - drain_bytes

    flash_time_total = flash_time + vh_extra_flash

    # ------------------------------- data-end (backbone) harvesting (§3/§4)
    # Idle SSDs' channel time redistributes through the SAME publish/claim
    # round as processor clocks: the FLASH_BW assist matrix turns published
    # surplus into fluid capacity transfers. Redirected backbone work ships
    # its data across the fabric, so it adds link demand on both ends.
    flash_assist_in = jnp.zeros((n,), jnp.float32)
    flash_used_from = jnp.zeros((n, n), jnp.float32)
    flash_cap_eff = flash_cap_s
    # per-borrower fabric byte rate of redirected backbone work: flat model
    # ships a program-rate worth of data per donated channel-second; the
    # per-op model prices cmd + payload bytes per op at the borrower's I/O
    # size (4 KB ops move far fewer bytes per channel-second than 256 KB)
    flash_rate = jnp.full((n,), ssd.FLASH_ASSIST_BPS, jnp.float32)
    if plat.harvest_flash:
        Mf = manager.assist_matrix(table, desc.FLASH_BW)
        f_surplus = jnp.maximum(flash_cap_s - flash_time_total, 0.0)
        f_deficit = jnp.maximum(flash_time_total - flash_cap_s, 0.0)
        if plat.flat_sync:
            flash_ovh = ssd.SYNC_FLASH_OVERHEAD
        else:
            flash_op_s = flash_time_total / jnp.maximum(ops, _EPS)
            flash_ovh = costs.overhead_frac(
                desc.FLASH_BW, flash_op_s,
                dequeue_s=plat.inter_ssd_op_s, hop_s=plat.cxl_hop_s)
            flash_rate = costs.assist_link_bps(
                desc.FLASH_BW, io_avg, flash_op_s,
                payload_ratio=plat.payload_comp_ratio)
        flash_assist_in, flash_used_from = mgr.fluid_transfer(
            Mf, f_surplus, f_deficit, flash_ovh)
        f_out = jnp.sum(flash_used_from, axis=1)
        flash_cap_eff = flash_cap_s + flash_assist_in - f_out
        # both endpoints' ports carry the redirected payload; each lender's
        # outbound share is priced at its borrowers' byte rates
        link_time = link_time + (
            flash_assist_in * flash_rate + flash_used_from @ flash_rate
        ) / ssd.CXL_BPS_PER_SSD

    # ------------------------------------- CXL link harvesting (pooled BW)
    # LINK_BW descriptors pool idle ports: a node whose link saturates (own
    # I/O + assist traffic) draws claimed peers' spare link-seconds — this is
    # also what caps inter-SSD assist traffic at published idle capacity.
    link_assist_in = jnp.zeros((n,), jnp.float32)
    link_used_from = jnp.zeros((n, n), jnp.float32)
    link_cap_eff = jnp.full((n,), window_s, jnp.float32)
    if plat.harvest_link:
        Ml = manager.assist_matrix(table, desc.LINK_BW)
        l_surplus = jnp.maximum(window_s - link_time, 0.0)
        l_deficit = jnp.maximum(link_time - window_s, 0.0)
        if plat.flat_sync:
            link_ovh = ssd.SYNC_LINK_OVERHEAD
        else:
            # multipath detour tax per transfer, fractional in transfer size
            link_op_s = link_time / jnp.maximum(ops, _EPS)
            link_ovh = costs.overhead_frac(
                desc.LINK_BW, link_op_s,
                dequeue_s=plat.inter_ssd_op_s, hop_s=plat.cxl_hop_s)
        link_assist_in, link_used_from = mgr.fluid_transfer(
            Ml, l_surplus, l_deficit, link_ovh)
        link_cap_eff = link_cap_eff + link_assist_in - jnp.sum(link_used_from, axis=1)

    # ------------------------------------------------------- joint service
    proc_cap_eff = proc_cap_s + assist_in - jnp.sum(used_from, axis=1)
    if fabric is not None:
        # fabric grants arrive net of the tier-2 tax; far_out is capped at
        # lend-triggered undonated surplus, so donating across the fabric
        # can never starve the lender's own service
        proc_cap_eff = proc_cap_eff + far_in - far_out
    s_proc = jnp.where(
        plat.oc,
        jnp.full((n,), jnp.inf),
        proc_cap_eff / jnp.maximum(proc_demand_s, _EPS),
    )
    s_flash = flash_cap_eff / jnp.maximum(flash_time_total, _EPS)
    s_link = link_cap_eff / jnp.maximum(link_time, _EPS)
    host_demand = jnp.sum(host_clocks) / ssd.HOST_CLOCKS_PER_S
    s_host = jnp.where(host_demand > 0, window_s / jnp.maximum(host_demand, _EPS), jnp.inf)
    scale = jnp.clip(
        jnp.minimum(jnp.minimum(s_proc, s_flash), jnp.minimum(s_link, s_host)),
        0.0, 1.0,
    )

    served_r = q_r * scale
    served_w = q_w * scale
    q_r = q_r - served_r
    q_w = q_w - served_w

    # ------------------------------------------------------ accounting
    # per-resource busy-time attribution: own capacity runs first, the
    # overflow ran on lenders, donated time charged by actual usage
    own_done, remote_done, out_done = mgr.busy_split(
        proc_demand_s * scale, proc_cap_s, assist_in, used_from)
    proc_busy = own_done + out_done
    f_own_done, f_remote_done, f_out_done = mgr.busy_split(
        flash_time_total * scale, flash_cap_s, flash_assist_in,
        flash_used_from)
    flash_busy = f_own_done + f_out_done
    l_own_done, l_remote_done, l_out_done = mgr.busy_split(
        link_time * scale, jnp.full((n,), window_s, jnp.float32),
        link_assist_in, link_used_from)
    link_busy = l_own_done + l_out_done

    srv_cmds = served_r / wv.rb_cmd + served_w / wv.wb_cmd
    # per-op mode charges the fixed §4.6 cost once (Inter-SSD term); the
    # flat model's proportional sync multiplier applies only as fallback
    lat_proc_ovh = ssd.SYNC_PROC_OVERHEAD if plat.flat_sync else 0.0
    far_lat = {} if fabric is None else dict(
        far_frac=far_frac, offsite_far=offsite_far)
    base_lat_r = _unloaded_latency(wv, True, miss, remote_frac, offsite_frac,
                                   plat, proc_ovh=lat_proc_ovh, **far_lat)
    base_lat_w = _unloaded_latency(wv, False, miss, remote_frac, offsite_frac,
                                   plat, proc_ovh=lat_proc_ovh, **far_lat)
    # closed-loop QD latency: lat = max(base, qd / per-cmd service rate)
    rate_cmds = jnp.maximum(srv_cmds / window_s, _EPS)
    lat_r = jnp.maximum(base_lat_r, wv.qd / rate_cmds)
    lat_w = jnp.maximum(base_lat_w, wv.qd / rate_cmds)
    lat = jnp.where(
        srv_cmds > 0,
        (served_r / wv.rb_cmd * lat_r + served_w / wv.wb_cmd * lat_w)
        / jnp.maximum(srv_cmds, _EPS),
        0.0,
    )

    flash_written = served_w * amp + drain_bytes + vh_redirect_bytes \
        + log_flush_pages * scale * ssd.PAGE_BYTES

    # energy (coarse, §5.3 parameters)
    e_flash = (
        (served_r / ssd.PAGE_BYTES) * ssd.T_READ_AVG
        + (flash_written / ssd.PAGE_BYTES) * ssd.T_PROG_AVG
    ) * ssd.FLASH_V * ssd.I_READ
    e_proc = proc_busy * ssd.SSD_PROC_W_FULL * (cfg.cores / ssd.CONV_CORES if cfg.cores else 1.0)
    e_dram = (served_r + served_w) * 8 * ssd.E_DRAM_PJ_PER_BIT * 1e-12
    if plat.flat_sync:
        # pre-refactor accounting: 64 B per redirected slice, program-rate
        # bytes per donated channel-second
        proc_cmd_bytes = remote_done * ssd.CLOCK_HZ \
            / jnp.maximum(ssd.C_READ_SLICE, 1.0) * 64.0
    else:
        # per-op §4.6 accounting: command descriptors per redirected
        # command, payload-rate bytes per donated channel-second
        proc_cmd_bytes = remote_done / jnp.maximum(proc_op_s, _EPS) \
            * costs.op_link_bytes(desc.PROCESSOR)
    cxl_traffic = proc_cmd_bytes \
        + log_ops * scale * 64.0 + vh_redirect_bytes + drain_bytes \
        + f_remote_done * flash_rate \
        + remote_hits * scale * lookup_bytes
    if fabric is not None:
        # inter-JBOF traffic: far-redirected command descriptors at the
        # tier-2 byte price, plus the fabric re-crossings of far lookups
        cxl_traffic = cxl_traffic + scale * (
            far_in / jnp.maximum(proc_op_s, _EPS)
            * costs.tier_link_bytes(
                desc.PROCESSOR, extra_hops=plat.fabric_extra_hops)
            + remote_hits_far * far_lookup_extra_b)
    e_cxl = cxl_traffic * 8 * ssd.E_CXL_PJ_PER_BIT * 1e-12
    e_idle = (window_s * n) * ssd.FLASH_V * ssd.I_BUSIDLE
    energy = jnp.sum(e_flash + e_proc + e_dram + e_cxl) + e_idle

    measure = (step_idx >= warmup).astype(jnp.float32)
    proc_own_util = jnp.where(
        proc_cap_s > 0, own_done / jnp.maximum(proc_cap_s, _EPS), 0.0)
    flash_eff_util = (flash_busy + f_remote_done) \
        / jnp.maximum(flash_cap_s + flash_assist_in, _EPS)
    link_eff_util = (link_busy + l_remote_done) / (window_s + link_assist_in)

    # ------------------------------------------- observability (§12, opt-in)
    # Python-gated on the static flag: a disabled run traces the exact
    # pre-obs program (the bitwise pin in tests/test_obs.py relies on it).
    obs_state = state.obs
    if obs.enabled:
        with obs_x.scope("obs_record"):
            ms, elog = state.obs
            ms = SIM_METRICS.record(ms, {
                "miss": miss,
                "borrowed_seg": borrowed_seg,
                "spare_seg": seg_spare,
                "q_bytes": q_r + q_w,
                "proc_util": proc_own_util,
                "flash_util": flash_eff_util,
                "link_util": link_eff_util,
                "served_bytes": measure * (served_r + served_w),
                "cxl_bytes": measure * cxl_traffic,
                "log_commits": measure * log_ops * scale,
                "energy_j": measure * energy,
                "latency": lat,
            })
            if any_harvest:
                # grant lifecycle from the table diff — all zeros (no rows
                # appended) on the windows the mgmt gate held the table
                rows, emask = obs_s.table_event_rows(
                    state.table, table, step_idx)
                elog = obs_s.append(elog, rows, emask)
            obs_state = (ms, elog)

    new_state = SimState(
        q_r=q_r, q_w=q_w, vh_debt=vh_debt, borrowed_seg=borrowed_seg,
        borrowed_far=borrowed_far, table=table,
        mrc=mrc_state,
        prev_proc_own=proc_own_util,
        prev_flash=flash_eff_util,
        prev_flash_own=f_own_done / jnp.maximum(flash_cap_s, _EPS),
        prev_link=link_eff_util,
        prev_link_own=l_own_done / window_s,
        obs=obs_state,
        served_r=state.served_r + measure * served_r,
        served_w=state.served_w + measure * served_w,
        proc_busy=state.proc_busy + measure * proc_busy,
        flash_busy=state.flash_busy + measure * flash_busy,
        host_busy=state.host_busy + measure * host_demand * scale.mean(),
        flash_written=state.flash_written + measure * flash_written,
        lat_sum=state.lat_sum + measure * lat * srv_cmds,
        cmd_count=state.cmd_count + measure * srv_cmds,
        log_commits=state.log_commits + measure * log_ops * scale,
        energy_j=state.energy_j + measure * energy,
        cxl_bytes=state.cxl_bytes + measure * cxl_traffic,
    )
    if fabric is not None:
        fout = FabricOut(
            proc_spare=proc_resid_spare, proc_want=proc_resid_want,
            seg_spare=seg_resid_spare, seg_want=seg_resid_want)
        if ev is not None:
            return new_state, (miss, borrowed_seg, seg_spare, fout, revoked)
        return new_state, (miss, borrowed_seg, seg_spare, fout)
    if ev is not None:
        return new_state, (miss, borrowed_seg, seg_spare, revoked)
    return new_state, (miss, borrowed_seg, seg_spare)


def _init_state(plat: Platform, n: int,
                tcfg: tele_win.TelemetryConfig,
                obs: obs_m.ObsConfig = obs_m.ObsConfig()) -> SimState:
    obs_state = None
    if obs.enabled:
        obs_state = (SIM_METRICS.init(n, obs),
                     obs_s.make_log(obs.event_capacity))
    return SimState(
        obs=obs_state,
        q_r=jnp.zeros((n,), jnp.float32),
        q_w=jnp.zeros((n,), jnp.float32),
        vh_debt=jnp.zeros((n,), jnp.float32),
        borrowed_seg=jnp.zeros((n,), jnp.float32),
        borrowed_far=jnp.zeros((n,), jnp.float32),
        table=_manager(plat).init_table(n),
        mrc=tele_win.init_batch(n, tcfg),
        prev_proc_own=jnp.zeros((n,), jnp.float32),
        prev_flash=jnp.zeros((n,), jnp.float32),
        prev_flash_own=jnp.zeros((n,), jnp.float32),
        prev_link=jnp.zeros((n,), jnp.float32),
        prev_link_own=jnp.zeros((n,), jnp.float32),
        served_r=jnp.zeros((n,), jnp.float32),
        served_w=jnp.zeros((n,), jnp.float32),
        proc_busy=jnp.zeros((n,), jnp.float32),
        flash_busy=jnp.zeros((n,), jnp.float32),
        host_busy=jnp.float32(0.0),
        flash_written=jnp.zeros((n,), jnp.float32),
        lat_sum=jnp.zeros((n,), jnp.float32),
        cmd_count=jnp.zeros((n,), jnp.float32),
        log_commits=jnp.zeros((n,), jnp.float32),
        energy_j=jnp.float32(0.0),
        cxl_bytes=jnp.zeros((n,), jnp.float32),
    )


def _enclosure_state(plat: Platform, e: int, nl: int,
                     tcfg: tele_win.TelemetryConfig,
                     obs: obs_m.ObsConfig) -> SimState:
    """`e` copies of one enclosure's `_init_state` on a leading axis: one
    broadcast a leaf, so the host's dispatches do not grow with `e`."""
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (e,) + a.shape),
                        _init_state(plat, nl, tcfg, obs))


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """One frozen bundle for every `simulate` run knob.

    `simulate()` had accreted eight keyword arguments by PR 9; they fold
    here so call sites read as *one* configuration object and new knobs
    (like ``events``) stop widening a positional-adjacent signature.
    Legacy keyword calls still work for one release through the shim in
    `simulate` (with a DeprecationWarning).
    """

    window_s: float = 1e-3
    warmup: int = 50
    traces: jax.Array | None = None
    telemetry: tele_win.TelemetryConfig = SIM_TELEMETRY
    n_enclosures: int = 1
    fabric_federation: bool = True
    obs: obs_m.ObsConfig = obs_m.ObsConfig()
    # failure/reclaim schedule (`core.events.schedule(...)`); None (or an
    # empty schedule) traces the exact event-free program
    events: ev_m.EventSchedule | None = None


_SIM_CFG_FIELDS = frozenset(f.name for f in dataclasses.fields(SimConfig))


def simulate(
    plat: Platform,
    workloads: list[Workload],
    arrivals: jax.Array,
    cfg: SimConfig | None = None,
    **legacy,
) -> SimResult:
    """Run the platform over the arrival matrix; return per-SSD metrics.

    Run knobs ride one frozen `SimConfig`; passing them as bare keyword
    arguments (the pre-PR-10 signature) still works for one release but
    warns. The knob semantics below are unchanged.

    The first ``warmup`` windows are simulated but excluded from the
    accumulators (descriptor claims need one management interval to ramp).

    ``traces`` (uint32[T, n, A] mapping-page references, EMPTY_REF-padded —
    see `repro.telemetry.traces`) switches a DRAM-harvesting platform to
    trace-driven mode: each window folds its per-node trace slice into a
    windowed-SHARDS estimator (``telemetry`` knobs) and `seg_need` /
    `seg_spare` derive from the ONLINE curve instead of the static
    parametric grid, so bursty nodes return borrowed segments mid-run
    (`SimResult.rings["borrowed_seg"]` is the proof). Ignored on
    platforms without DRAM harvesting.

    ``obs`` (`repro.obs.metrics.ObsConfig`) switches on the observability
    plane: every `SIM_METRICS` metric records into in-scan ring buffers,
    grant-lifecycle events (publish/claim/release/withdraw plus fabric
    grants) append to a bounded device-side log, and the decoded feed
    comes back in `SimResult.obs`. Disabled (the default) is
    bitwise-identical to a build without the plane.

    ``n_enclosures`` > 1 scales out to a multi-JBOF fabric: the SSDs
    split into that many enclosures (contiguous ``n // n_enclosures``
    blocks), each running the full descriptor machinery privately in a
    vmapped step, while per-enclosure (spare, want) residual summaries
    federate through the topology plane's fabric level
    (`core.topology.hierarchical_exchange`) once per management interval
    — claims settle inside the enclosure first and spill to the fabric
    only when the local pool is dry, every cross-enclosure grant taxed at
    `Platform.fabric_extra_hops` extra traversals per op. Grants apply
    one window later (the federation round trip). With 1 enclosure the
    pre-topology single-JBOF program runs unchanged. PROCESSOR clocks and
    DRAM segments federate; data-end channel time and link bytes stay
    enclosure-local (shipping payloads across JBOFs is priced out by
    construction). ``fabric_federation=False`` keeps the enclosures
    isolated — the scale-out baseline fig22_fabric compares against.
    `SimResult.host_util` / `energy_j` stay per-enclosure aggregates
    ([E] and summed respectively).

    ``events`` (`core.events.EventSchedule`) drives the failure/reclaim
    plane: lender reclaims force nodes fully busy (the ordinary §4.3
    machinery drains their grants), SSD failures kill nodes outright
    (standing grants revoke via `manager.revoke_nodes` inside the next
    management round, within one interval), and enclosure drops
    invalidate exactly the dropped block's cross-level fabric grants
    (`topology.invalidate_block_grants`). Scheduled runs add a
    `rings["revoked_grants"]` [T] series counting descriptor rows plus
    fabric-grant units invalidated per window.
    """
    if legacy:
        unknown = sorted(set(legacy) - _SIM_CFG_FIELDS)
        if unknown:
            raise TypeError(
                f"simulate() got unexpected keyword arguments: {unknown}")
        warnings.warn(
            f"passing simulate() run knobs as keyword arguments "
            f"({sorted(legacy)}) is deprecated; fold them into "
            "cfg=SimConfig(...)",
            DeprecationWarning, stacklevel=2)
        cfg = dataclasses.replace(cfg or SimConfig(), **legacy)
    elif cfg is None:
        cfg = SimConfig()
    with obs_x.annotate("xbof.sim.prepare"):
        window_s, warmup, traces = cfg.window_s, cfg.warmup, cfg.traces
        telemetry, n_enclosures = cfg.telemetry, cfg.n_enclosures
        fabric_federation, obs = cfg.fabric_federation, cfg.obs

        n = arrivals.shape[1]
        wv = workload_vec(workloads)
        trace_driven = traces is not None and plat.harvest_dram
        tcfg = telemetry if trace_driven else _NO_TELEMETRY
        want_frac = (static_want_frac(wv)
                     if plat.harvest_dram and not trace_driven
                     else jnp.zeros((n,), jnp.float32))
        warmup = min(warmup, max(arrivals.shape[0] - 1, 0))
        traces_x = (traces if trace_driven
                    else jnp.zeros((arrivals.shape[0], n, 1), jnp.uint32))
        ev_arr = (ev_m.compile(cfg.events, arrivals.shape[0], n, n_enclosures)
                  if cfg.events else None)
        use_ev = ev_arr is not None

        if n_enclosures <= 1:
            step = partial(_window_step, plat=plat, wv=wv, want_frac=want_frac,
                           window_s=window_s, warmup=warmup,
                           trace_driven=trace_driven, tcfg=tcfg, obs=obs)

            def body(carry, x):
                state, i = carry
                if use_ev:
                    arr, trc, ne = x
                    state, out = step(state, arr, trc, step_idx=i, ev=ne)
                else:
                    arr, trc = x
                    state, out = step(state, arr, trc, step_idx=i)
                return (state, i + 1), out

            xs = ((arrivals, traces_x, ev_m.node_view(ev_arr)) if use_ev
                  else (arrivals, traces_x))
            carry0 = (_init_state(plat, n, tcfg, obs), jnp.int32(0))
        else:
            e = n_enclosures
            if n % e:
                raise ValueError(
                    f"n_enclosures={e} must divide the {n} SSDs evenly")
            nl = n // e
            st0 = _enclosure_state(plat, e, nl, tcfg, obs)
            wv_e = jax.tree.map(lambda a: a.reshape(e, nl), wv)
            wf_e = want_frac.reshape(e, nl)
            xg0 = FabricIn(*(jnp.zeros((e,), jnp.float32) for _ in range(4)))
            ftopo = topo.flat(e)
            arr_e = arrivals.reshape(arrivals.shape[0], e, nl, -1)
            trc_e = traces_x.reshape(traces_x.shape[0], e, nl, -1)
            # fabric-tier grant events ride their own single-lane log in the
            # outer carry (the vmapped per-enclosure logs only see level 0)
            use_flog = obs.enabled and fabric_federation
            price_p = float(costs.tier_link_bytes(
                desc.PROCESSOR, extra_hops=plat.fabric_extra_hops))
            price_s = float(costs.tier_link_bytes(
                desc.DRAM,
                cmd_bytes=plat.remote_lookup_bytes * plat.payload_comp_ratio,
                extra_hops=plat.fabric_extra_hops))

            if use_ev:
                ne_e = jax.tree.map(
                    lambda a: a.reshape(a.shape[0], e, nl),
                    ev_m.node_view(ev_arr))

            def body(carry, x):
                if use_flog:
                    state, i, xg, flog = carry
                else:
                    state, i, xg = carry
                if use_ev:
                    arr, trc, ne, dr = x
                    # an enclosure dropping off the fabric invalidates its
                    # standing inbound/outbound fabric grants; zeroing the
                    # CARRY makes the tally tick exactly at the transition
                    rev_fab = sum(
                        jnp.sum(jnp.where(dr, a, 0.0)) for a in xg)
                    xg = FabricIn(*(jnp.where(dr, 0.0, a) for a in xg))
                else:
                    arr, trc = x

                def one(s, a, t, w, wf, fab, ne1=None):
                    return _window_step(
                        s, a, t, plat=plat, wv=w, want_frac=wf,
                        window_s=window_s, step_idx=i, warmup=warmup,
                        trace_driven=trace_driven, tcfg=tcfg, fabric=fab,
                        obs=obs, ev=ne1)

                if use_ev:
                    state, (miss, bseg, sspare, fout, rev) = jax.vmap(one)(
                        state, arr, trc, wv_e, wf_e, xg, ne)
                    rev_node = jnp.sum(rev).astype(jnp.float32)
                    # a dropped enclosure neither publishes upward nor draws
                    # back from the fabric
                    fout = FabricOut(*(
                        jnp.where(dr, 0.0, a) for a in fout))
                else:
                    state, (miss, bseg, sspare, fout) = jax.vmap(one)(
                        state, arr, trc, wv_e, wf_e, xg)
                if fabric_federation:
                    # fabric level of the topology plane: settle the
                    # enclosures' residuals with the SAME exchange the engine
                    # and the intra-enclosure rounds run; grants hold for one
                    # management interval, like the local descriptor tables
                    gp, rp = topo.hierarchical_exchange(
                        fout.proc_spare, fout.proc_want, ftopo)
                    gs, rs = topo.hierarchical_exchange(
                        fout.seg_spare, fout.seg_want, ftopo)
                    if use_ev:
                        # exactly the dropped block's cross-level grants die;
                        # grants between surviving enclosures are untouched
                        gp, rel_p = topo.invalidate_block_grants(gp, dr)
                        gs, rel_s = topo.invalidate_block_grants(gs, dr)
                        rp = jnp.where(dr[None, :], 0.0, rp)
                        rs = jnp.where(dr[None, :], 0.0, rs)
                        rev_fab = rev_fab + rel_p + rel_s
                    xg_new = FabricIn(
                        proc_in=jnp.sum(rp, axis=0),
                        proc_out=jnp.sum(gp, axis=(0, 2)),
                        seg_in=jnp.sum(rs, axis=0),
                        seg_out=jnp.sum(gs, axis=(0, 2)))
                    do = (i % plat.mgmt_interval) == 0
                    xg = jax.tree.map(
                        lambda a, b: jnp.where(do, b, a), xg, xg_new)
                    if use_flog:
                        # log only the grants that actually apply (mgmt gate);
                        # lender/borrower columns carry ENCLOSURE ids
                        for grants, rt, pr in (
                                (gp[0], desc.PROCESSOR, price_p),
                                (gs[0], desc.DRAM, price_s)):
                            rows, gmask = obs_s.grant_event_rows(
                                grants, rtype=rt, level=2, t=i,
                                code=obs_s.FABRIC_GRANT, price=pr)
                            flog = obs_s.append(flog, rows, gmask & do)
                out = (miss, bseg, sspare)
                if use_ev:
                    out = out + (rev_node + rev_fab,)
                if use_flog:
                    return (state, i + 1, xg, flog), out
                return (state, i + 1, xg), out

            carry0 = ((st0, jnp.int32(0), xg0,
                       obs_s.make_log(obs.event_capacity)) if use_flog
                      else (st0, jnp.int32(0), xg0))
            xs = ((arr_e, trc_e, ne_e, ev_arr.drop) if use_ev
                  else (arr_e, trc_e))

    with obs_x.annotate("xbof.sim.scan"):
        carry1, aux = jax.lax.scan(body, carry0, xs)

    with obs_x.annotate("xbof.sim.finish"):
        st, revoked_hist = carry1[0], None
        if n_enclosures <= 1:
            if use_ev:
                miss_hist, borrowed_hist, spare_hist, rev = aux
                revoked_hist = rev.astype(jnp.float32)
            else:
                miss_hist, borrowed_hist, spare_hist = aux
            energy = st.energy_j
            host_busy = st.host_busy
            obs_ms_el = st.obs
            fabric_log = None
        else:
            if use_ev:
                miss_hist, borrowed_hist, spare_hist, revoked_hist = aux
            else:
                miss_hist, borrowed_hist, spare_hist = aux
            fabric_log = carry1[3] if use_flog else None
            miss_hist = miss_hist.reshape(miss_hist.shape[0], n)
            borrowed_hist = borrowed_hist.reshape(borrowed_hist.shape[0], n)
            spare_hist = spare_hist.reshape(spare_hist.shape[0], n)
            energy = jnp.sum(st.energy_j)
            host_busy = st.host_busy  # [E] — one host DPU per enclosure
            fl = lambda a: a.reshape(n)
            st = st._replace(
                served_r=fl(st.served_r), served_w=fl(st.served_w),
                proc_busy=fl(st.proc_busy), flash_busy=fl(st.flash_busy),
                flash_written=fl(st.flash_written), lat_sum=fl(st.lat_sum),
                cmd_count=fl(st.cmd_count), log_commits=fl(st.log_commits),
                cxl_bytes=fl(st.cxl_bytes), borrowed_seg=fl(st.borrowed_seg),
                borrowed_far=fl(st.borrowed_far))
            # collapse the vmapped [E, local, ...] obs leaves to the canonical
            # layout: node lanes -> [n], scalar lanes -> [E], one log lane per
            # enclosure (decode offsets the local node ids by lane * nl)
            obs_ms_el = obs_m.merge_lead(st.obs) if obs.enabled else None

        t_total = (arrivals.shape[0] - warmup) * window_s
        total = st.served_r + st.served_w
        day_s = 86400.0
        proc_cap_rate = plat.ssd_config.proc_clocks_per_s / ssd.CLOCK_HZ
        rings = {"borrowed_seg": borrowed_hist, "spare_seg": spare_hist}
        if revoked_hist is not None:
            rings["revoked_grants"] = revoked_hist
        obs_out = None
        if obs.enabled:
            ms, elog = obs_ms_el
            id_stride = n // n_enclosures if n_enclosures > 1 else 0
            records, dropped = obs_s.decode(elog, id_stride=id_stride)
            if fabric_log is not None:
                frecs, fdrop = obs_s.decode(fabric_log)
                records = sorted(records + frecs,
                                 key=lambda r: (r["t"], r["lane"]))
                dropped += fdrop
            obs_out = {
                "metrics": SIM_METRICS.history(ms),
                "totals": SIM_METRICS.totals(ms),
                "events": records,
                "events_dropped": dropped,
            }
        return SimResult(
            throughput_bps=total / t_total,
            read_bps=st.served_r / t_total,
            write_bps=st.served_w / t_total,
            latency_s=st.lat_sum / jnp.maximum(st.cmd_count, 1.0),
            proc_util=(st.proc_busy / (proc_cap_rate * t_total)) if plat.cores
            else jnp.zeros_like(total),
            flash_util=st.flash_busy / t_total,
            miss_ratio=miss_hist[-1],
            dwpd=(st.flash_written / t_total) * day_s
            / (ssd.SSD_CAPACITY_TB * 1e12),
            energy_j=energy,
            host_util=host_busy / t_total,
            log_commits=st.log_commits,
            cxl_bytes=st.cxl_bytes,
            borrowed_seg=st.borrowed_seg,
            borrowed_far=st.borrowed_far,
            rings=rings,
            obs=obs_out,
        )
