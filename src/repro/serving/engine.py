"""Continuous-batching serving engine with XBOF inter-replica harvesting.

The runtime loop maps the paper one-to-one onto DP serving replicas:

  paper                         | engine
  ------------------------------+------------------------------------------
  idle-resource descriptors     | per-replica rows in core.descriptors table
  processor harvesting (§4.4)   | decode-slot redirection: overloaded
                                |   replicas send admitted requests to idle
                                |   replicas' SHADOW slots via the §4.4
                                |   load-balance split
  DRAM harvesting (§4.5)        | kv_pool peer-page spill + WAL; with
                                |   trace_driven, the page-access stream
                                |   feeds the telemetry plane's windowed
                                |   SHARDS and the online want reserves
                                |   lendable pages (DESIGN.md §7)
  link-bandwidth harvesting     | LINK_BW descriptors fund ONE byte account
                                |   per replica (§4.6 cost table): lender-
                                |   spill pages AND §4.4 redirect commands
                                |   debit it, commands first (DESIGN.md §8)
  10 ms descriptor poll         | every engine step
  WRR shadow-queue weights      | shadow slots admit at low priority
  CXL pool locality tiers       | the shard axis: full descriptor machinery
                                |   within a shard, one aggregate summary
                                |   across shards (DESIGN.md §9)

The management round is HIERARCHICAL (DESIGN.md §9/§11): with `n_shards >
1` the replicas split into shards of `n_replicas / n_shards`, each shard
runs the full `core.manager.ResourceManager` round over its own pool,
descriptor table, and telemetry state, and shards exchange only one
aggregate spare/want summary per rtype, settled level by level through
`core.topology.hierarchical_exchange` (flat = the PR 6 exchange;
`shards_per_enclosure` groups shards into enclosures with a pricier
fabric tier above them). Every cross-level assist pays its tier's
extra-hop price (`core.costs.tier_link_bytes`), so nearer lenders always
win — per-step cost scales with the shard size, not global `n_replicas`.

Decentralized: routing is a pure function of the replicated descriptor
table — every replica in a shard computes identical local decisions, and
every shard computes the identical exchange matrix from the all-gathered
summaries (DESIGN.md §3 at both levels). The engine is functional:
step(state, arrivals) -> (state', stats).

The model here is a single paged-attention decode layer (the runtime's unit
of work); the full zoo runs through launch/serve.py's lowered serve_step.
The decode hot path is batched AND shard-local: one `kv_pool.append_tokens`
grows every active sequence of the shard and one
`kernels.ops.paged_attention` call (Pallas on TPU, interpret/oracle
fallback elsewhere) attends over the shard's flattened (replica, slot)
batch — no per-slot Python loops anywhere, no cross-shard tensor traffic
outside the aggregate exchange. `step` executes the hierarchy under `vmap`
on one device; `make_sharded_step` executes the same shard-local function
under `shard_map` on a real mesh — both compute identical values.
"""
from __future__ import annotations

import functools
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import costs
from repro.core import descriptors as desc
from repro.core import loadbalance as lb
from repro.core import manager as mgr
from repro.core import topology as topo
from repro.kernels import ops as kops
from repro.obs import export as obs_x
from repro.obs import metrics as obs_m
from repro.obs import spans as obs_s
from repro.telemetry import reclaim as tele_reclaim
from repro.telemetry import want as tele_want
from repro.telemetry import windows as tele_win
from . import kv_pool as kvp

WATERMARK = 0.75
DRAM_MIN_PAGES = 4.0  # publish/consume threshold for lendable KV pages

# mesh axis of the replica-shard dimension; launch.mesh.make_serving_mesh
# builds the matching 1-D device mesh
SHARD_AXIS = "shards"

_NO_TELEMETRY = tele_win.TelemetryConfig(k=1, buckets=1)


def _telemetry(cfg: "EngineConfig") -> tele_win.TelemetryConfig:
    """Telemetry plane (DESIGN.md §7), engine side: the kv_pool page-access
    stream (every physical page the decode batch attends over) feeds the
    SAME windowed-SHARDS estimator the JBOF sim runs, at page granularity
    and full sample rate (page ids are small ints). The derived per-replica
    want backs a lendable-page reserve on the DRAM descriptor — per-rtype
    telemetry parity between substrates.

    Coverage is derived from the pool geometry, never hardcoded: the table
    holds every local page (k = pages_per_replica) and the curve spans the
    pool (buckets * bucket_width >= pages_per_replica), so the reserve
    cannot silently saturate below the pool size on large configurations —
    the same bug class as a hardcoded descriptor slot index."""
    return tele_win.TelemetryConfig(
        k=cfg.pages_per_replica, buckets=16,
        bucket_width=max(-(-cfg.pages_per_replica // 16), 1),
        sample_mod=1, sample_thresh=1, decay=0.9, min_total=2.0)


class EngineConfig(NamedTuple):
    n_replicas: int = 4
    seq_slots: int = 8          # decode slots per replica (normal queue)
    shadow_slots: int = 2       # slots reserved for redirected work (§4.4)
    pages_per_replica: int = 64
    page: int = 16
    kv_heads: int = 2
    head_dim: int = 32
    n_heads: int = 4
    max_pages: int = 16
    shadow_weight: float = 1.0  # WRR weights
    normal_weight: float = 4.0
    # LINK_BW metering: per-step link allowance per replica, expressed in
    # KV-page transfers but kept as ONE byte account (§4.6 cost table):
    # lender-spill page moves AND §4.4 shadow-slot redirection commands
    # (`costs.REDIRECT_CMD_BYTES` each) debit the same budget, commands
    # first — so per step Σ(spill bytes + redirect bytes) ≤ budget.
    # Replicas under HBM pressure borrow idle peers' budgets through the
    # same management round (LINK_BW rtype); 0 disables metering (spill
    # unmetered, redirects unmetered, no LINK_BW descriptors).
    link_pages_per_step: int = 0
    # Telemetry-driven DRAM publishing: derive each replica's near-future
    # page want from its kv_pool page-access stream (windowed SHARDS) and
    # reserve that headroom out of the lendable amount, instead of lending
    # every currently-free page. Off by default (amount = free pages).
    trace_driven: bool = False
    # Hierarchical round (DESIGN.md §9): replicas split into n_shards
    # shards of n_replicas/n_shards; descriptors, routing, pool, and
    # telemetry are all shard-local, and shards exchange one aggregate
    # spare/want summary per rtype. cross_shard=False keeps the shards
    # fully independent (no exchange) — the parity-test configuration.
    n_shards: int = 1
    cross_shard: bool = True
    # Topology plane (DESIGN.md §11): group the shards into enclosures of
    # this many shards each. 0 (or n_shards) keeps the flat PR 6 exchange
    # — ONE level over all shards at the enclosure tier. A proper divisor
    # deepens the tree: leftovers settle shard↔shard within each enclosure
    # first (tier-1 hop price), and only the residual crosses enclosures
    # at the fabric tier (tier-2 price, intra ≪ cross) — same
    # `topology.hierarchical_exchange` code path either way.
    shards_per_enclosure: int = 0
    # KV page storage: "none" keeps full-precision fp32 pages (bitwise the
    # pre-quant engine); "int8" stores int8 codes + per-page fp32 scale
    # planes (kv_pool rescale-on-write), shrinking page_nbytes ~4x — the
    # LINK_BW spill debit, the lendable-page byte price, and the paged-
    # attention HBM traffic all reprice automatically. Attention math stays
    # fp32 (fused dequant in the kernel); "quant_err_norm" in the step
    # stats tracks the write-side quantization error.
    kv_quant: str = "none"
    # Observability plane (DESIGN.md §12): metric rings + grant-lifecycle
    # event log riding the scan carry. Off by default — enabled=False is
    # bitwise-identical to an engine without the plane (state carries an
    # empty pytree, every record site is Python-gated).
    obs: obs_m.ObsConfig = obs_m.ObsConfig()
    # Failure plane (DESIGN.md §13): carry a per-replica dead mask and
    # honor it every step (arrivals, publishing, claiming, hosting all
    # masked for dead replicas). Off by default — state.dead stays None
    # and the step traces the exact pre-failure-plane program.
    track_failures: bool = False
    # WAL-backed live migration (DESIGN.md §13): per-step page allowance
    # for draining offsite KV pages off lenders the reclaim predictor
    # flags as risky (`kv_pool.drain_offsite`). The drain rides the SAME
    # unified LINK_BW byte account as spill/redirect traffic when
    # metering is on. 0 disables (state.reclaim stays None).
    migrate_pages_per_step: int = 0
    # predictor knobs (telemetry/reclaim.py) — hashable NamedTuple, so
    # the config stays a valid static jit argument
    reclaim: tele_reclaim.ReclaimConfig = tele_reclaim.ReclaimConfig()


class EngineState(NamedTuple):
    pool: kvp.PagedPool
    table: desc.IdleResourceTable
    home_of: jax.Array      # [R, S_total] int32 — original replica of the seq
    remaining: jax.Array    # [R, S_total] int32 — tokens left to decode
    queue: jax.Array        # [R] int32 — backlog of unadmitted requests
    step_count: jax.Array
    # per-replica windowed-SHARDS state over the kv_pool page-access stream
    # (1-entry dummy unless cfg.trace_driven)
    mrc: object
    # params of the demo decode layer (shared across replicas, like
    # homogeneous SSD firmware)
    wq: jax.Array
    wk: jax.Array
    wv: jax.Array
    wo: jax.Array
    # observability plane state (EngineObs) when cfg.obs.enabled, else
    # None — an EMPTY pytree, so a disabled engine's state has exactly the
    # pre-obs leaves (the digest-pinned parity suites stay bitwise)
    obs: object = None
    # failure plane: bool[R] dead-replica mask when cfg.track_failures,
    # else None (empty pytree — same digest discipline as obs)
    dead: object = None
    # reclaim predictor carry (telemetry.reclaim.ReclaimState) when
    # cfg.migrate_pages_per_step > 0, else None
    reclaim: object = None


class EngineObs(NamedTuple):
    """Metric rings + grant-lifecycle event log (DESIGN.md §12). Node
    metrics lead with the replica axis, scalar metrics/event lanes with
    the shard axis, so the whole thing shards like any other state field."""

    metrics: obs_m.MetricsState
    events: obs_s.EventLog


# Fields with a leading replica axis — everything a shard owns privately.
# step_count and the decode-layer weights are replicated across shards.
SHARDED_FIELDS = ("pool", "table", "home_of", "remaining", "queue", "mrc",
                  "obs", "dead", "reclaim")

_STATE_AXES = None  # filled in below (needs EngineState defined)


def total_slots(cfg: EngineConfig) -> int:
    return cfg.seq_slots + cfg.shadow_slots


def shard_topology(cfg: EngineConfig) -> topo.Topology:
    """The exchange tree above the shard-local rounds. Flat (the PR 6
    two-level round) unless ``shards_per_enclosure`` is a proper divisor
    of n_shards, in which case the shards settle within enclosures first
    and spill to the fabric tier only when the enclosure pool is dry."""
    spe = cfg.shards_per_enclosure
    if spe and 1 < spe < cfg.n_shards:
        return topo.two_level(spe, cfg.n_shards // spe)
    return topo.flat(cfg.n_shards)


def local_replicas(cfg: EngineConfig) -> int:
    return cfg.n_replicas // cfg.n_shards


def init(cfg: EngineConfig, key) -> EngineState:
    if cfg.n_shards < 1 or cfg.n_replicas % cfg.n_shards != 0:
        raise ValueError(
            f"n_shards={cfg.n_shards} must evenly divide "
            f"n_replicas={cfg.n_replicas}")
    if cfg.shards_per_enclosure:
        if cfg.n_shards % cfg.shards_per_enclosure != 0:
            raise ValueError(
                f"shards_per_enclosure={cfg.shards_per_enclosure} must "
                f"evenly divide n_shards={cfg.n_shards}")
    shard_topology(cfg).validate(cfg.n_shards)
    st = total_slots(cfg)
    d = cfg.n_heads * cfg.head_dim
    ks = jax.random.split(key, 4)
    pool = kvp.make_pool(cfg.n_replicas, cfg.pages_per_replica, cfg.page,
                         cfg.kv_heads, cfg.head_dim, st, cfg.max_pages,
                         dtype=jnp.float32, quant=cfg.kv_quant)
    if cfg.n_shards > 1:
        # the WAL cost counters are scalars per pool; hierarchical state
        # carries one per shard (summed for the reported stat) so each
        # shard's commits stay shard-local
        pool = pool._replace(logs=pool.logs._replace(
            flushes=jnp.zeros((cfg.n_shards,), jnp.int32),
            commits=jnp.zeros((cfg.n_shards,), jnp.int32)))
    obs_state = None
    if cfg.obs.enabled:
        obs_state = EngineObs(
            metrics=ENGINE_METRICS.init(cfg.n_replicas, cfg.obs,
                                        lead=cfg.n_shards),
            events=obs_s.make_log(cfg.obs.event_capacity,
                                  lead=cfg.n_shards))
    sc = lambda k, sh: jax.random.normal(k, sh, jnp.float32) * (sh[0] ** -0.5)
    return EngineState(
        pool=pool,
        table=_manager(cfg).init_table(cfg.n_replicas),
        home_of=jnp.full((cfg.n_replicas, st), -1, jnp.int32),
        remaining=jnp.zeros((cfg.n_replicas, st), jnp.int32),
        queue=jnp.zeros((cfg.n_replicas,), jnp.int32),
        step_count=jnp.zeros((), jnp.int32),
        mrc=tele_win.init_batch(
            cfg.n_replicas,
            _telemetry(cfg) if cfg.trace_driven else _NO_TELEMETRY),
        wq=sc(ks[0], (d, d)), wk=sc(ks[1], (d, cfg.kv_heads * cfg.head_dim)),
        wv=sc(ks[2], (d, cfg.kv_heads * cfg.head_dim)), wo=sc(ks[3], (d, d)),
        obs=obs_state,
        dead=(jnp.zeros((cfg.n_replicas,), bool)
              if cfg.track_failures else None),
        reclaim=(tele_reclaim.init(cfg.n_replicas)
                 if cfg.migrate_pages_per_step > 0 else None),
    )


def utilization(cfg: EngineConfig, state: EngineState) -> jax.Array:
    """Processor-descriptor utilization = normal-slot occupancy (+queue)."""
    occ = jnp.sum(state.pool.seq_active[:, : cfg.seq_slots], axis=1)
    util = (occ + jnp.minimum(state.queue, 4)) / cfg.seq_slots
    return jnp.clip(util.astype(jnp.float32), 0.0, 1.5)


def hbm_pressure(cfg: EngineConfig, state: EngineState) -> jax.Array:
    return 1.0 - kvp.free_pages(state.pool) / cfg.pages_per_replica


class FailureReport(NamedTuple):
    """What one `fail_replica` call cost, for the scenario driver."""

    lost_tokens: int   # KV tokens truncated off borrowers' tails (they
                       # re-decode — latency spike, never sequence loss)
    requeued: int      # shadow sequences re-queued at their home replica
    aborted: int       # the dead replica's OWN sequences (client gone)
    revoked: int       # standing descriptor rows invalidated


def fail_replica(cfg: EngineConfig, state: EngineState, failed: int,
                 ) -> tuple[EngineState, FailureReport]:
    """Kill one replica: the §4.5 recovery story, serving side.

    Four transitions, in crash-consistent order: (1) sequences HOSTED on
    the dead replica (shadow slots serving other homes) release their
    pages and re-queue at their true home — the dead replica's own
    sequences abort (their client died with it); (2) borrowers whose
    offsite KV pages lived in the dead pool WAL-truncate to the last
    fully-surviving prefix (`kv_pool.lender_failure`) and the truncated
    tail is added back to ``remaining`` — the engine re-decodes it, so a
    lender crash costs latency, never sequences; (3) every standing
    descriptor grant the dead replica lends or borrows invalidates
    (`manager.revoke_nodes`, per shard-local table); (4) the dead mask
    raises, and `cfg.track_failures` keeps the replica inert from the
    next step on.

    Host-side (called between steps by scenario drivers, not inside the
    jitted step). Requires ``cfg.track_failures=True``.
    """
    if state.dead is None:
        raise ValueError(
            "fail_replica needs cfg.track_failures=True (state.dead is "
            "None — the step would keep scheduling onto the dead replica)")
    failed = int(failed)
    r, st = cfg.n_replicas, total_slots(cfg)
    pool = state.pool

    # (1) hosted sequences: requeue at home, abort the replica's own
    hosted = pool.seq_active[failed]
    homes = state.home_of[failed]
    own = homes == failed
    requeue = jnp.zeros((r,), jnp.int32).at[jnp.clip(homes, 0, r - 1)].add(
        (hosted & ~own).astype(jnp.int32))
    aborted = int(jnp.sum(hosted & own))
    pool = kvp.release_sequences(
        pool, jnp.zeros((r, st), bool).at[failed].set(hosted))
    remaining = state.remaining.at[failed].set(0)
    home_of = state.home_of.at[failed].set(-1)
    queue = (state.queue + requeue).at[failed].set(0)

    # (2) offsite pages in the dead pool: WAL replay -> truncate -> the
    # lost tail re-decodes (remaining grows back by what was cut)
    len_before = pool.seq_len
    pool = kvp.lender_failure(pool, failed)
    lost = jnp.where(pool.seq_active, len_before - pool.seq_len, 0)
    remaining = remaining + lost

    # (3) standing grants revoke, per shard-local table (borrower ids are
    # shard-local under the hierarchy)
    dead = state.dead.at[failed].set(True)
    nsh, nl = cfg.n_shards, local_replicas(cfg)
    tbl = jax.tree.map(
        lambda a: a.reshape(nsh, nl, *a.shape[1:]), state.table)
    tbl, revoked = jax.vmap(mgr.revoke_nodes)(tbl, dead.reshape(nsh, nl))
    table = jax.tree.map(
        lambda a: a.reshape(nsh * nl, *a.shape[2:]), tbl)

    state = state._replace(pool=pool, table=table, home_of=home_of,
                           remaining=remaining, queue=queue, dead=dead)
    return state, FailureReport(
        lost_tokens=int(jnp.sum(lost)),
        requeued=int(jnp.sum(requeue)),
        aborted=aborted,
        revoked=int(jnp.sum(revoked)),
    )


@functools.lru_cache(maxsize=None)
def _manager(cfg: EngineConfig) -> mgr.ResourceManager:
    """The engine's view of the unified management round: one PROCESSOR
    descriptor in slot 0, one DRAM descriptor (lendable pages) in slot 1,
    optionally one LINK_BW descriptor (spill page budget) in slot 2; a
    single busiest-first claim sweep per step. Cached per config so the
    jitted step traces one shared instance instead of rebuilding it at
    every call site."""
    pols = [
        mgr.ResourcePolicy(
            rtype=desc.PROCESSOR, slot0=0, slots=1, claim_rounds=1,
            watermark=WATERMARK, gate_watermark=0.98),
        mgr.ResourcePolicy(
            rtype=desc.DRAM, slot0=1, slots=1, claim_rounds=0,
            min_amount=DRAM_MIN_PAGES, amount_gated=True),
    ]
    n_slots = 2
    if cfg.link_pages_per_step > 0:
        pols.append(mgr.ResourcePolicy(
            rtype=desc.LINK_BW, slot0=2, slots=1, claim_rounds=1,
            watermark=WATERMARK))
        n_slots = 3
    return mgr.ResourceManager(mgr.ManagerConfig(
        n_slots=n_slots, policies=tuple(pols)))


def _route(cfg: EngineConfig, state: EngineState, arrivals: jax.Array):
    """§4.4 transparent redirection: split each replica's (queue + arrivals)
    between itself and its claimed lender using the load-balance formula.
    Operates on whatever replica count the state carries — the full engine
    in single-shard mode, one shard's slice under the hierarchy."""
    util = utilization(cfg, state)
    n = state.queue.shape[0]
    demand = state.queue + arrivals
    assist = _manager(cfg).assist_matrix(
        state.table, desc.PROCESSOR)  # [lender, borrower]

    def split_one(i):
        lender_mask = assist[:, i] > 0
        n_kept, n_sent = lb.split_commands(
            demand[i], util[i], util, lender_mask,
            w_borrow_sq=cfg.normal_weight, w_shadow_sq=cfg.shadow_weight,
            sum_w_borrow=cfg.normal_weight * cfg.seq_slots,
            sum_w_lend=cfg.normal_weight * cfg.seq_slots,
        )
        return n_kept, n_sent

    kept, sent = jax.vmap(split_one)(jnp.arange(n))     # [n], [n, n]
    return kept, sent


def _admit(cfg: EngineConfig, state: EngineState, kept, sent, home_base=0,
           imported=None, import_src=None, import_home=None):
    """Prefix-sum admission, every replica in parallel: the first `kept[r]`
    free normal slots take local work, the first `sum(sent[:, r])` free
    shadow slots take redirected work. Each shadow admission is attributed
    to its TRUE borrower — the j-th redirected request at lender r belongs
    to the borrower whose cumulative `sent[:, r]` count covers j — not to
    the dominant borrower (which mis-homed sequences whenever two borrowers
    redirected to the same lender in one step).

    `home_of` records GLOBAL replica ids: ``home_base`` is the global id of
    this shard's replica 0 (0 in single-shard mode). Cross-shard imports
    (``imported`` int32[n] per host replica) admit to shadow slots AFTER
    the shard-local redirects; their home is attributed at shard
    granularity — ``import_home[src]`` for the source shard found through
    the per-source counts ``import_src`` (int32[n_shards], the exchange
    matrix row) — because the aggregate exchange summary deliberately hides
    per-replica provenance (DESIGN.md §9)."""
    pool = state.pool
    st = total_slots(cfg)
    n = state.queue.shape[0]
    free = ~pool.seq_active                             # [R, St]
    is_shadow = jnp.arange(st)[None, :] >= cfg.seq_slots

    normal_free = free & ~is_shadow
    shadow_free = free & is_shadow
    nrank = jnp.cumsum(normal_free, axis=1) - normal_free
    srank = jnp.cumsum(shadow_free, axis=1) - shadow_free
    n_remote = jnp.sum(sent, axis=0)                    # [R] redirected here
    admit_local = normal_free & (nrank < kept[:, None])
    admit_remote = shadow_free & (srank < n_remote[:, None])
    admit = admit_local | admit_remote

    cum = jnp.cumsum(sent, axis=0)                      # [B, R] per lender
    from_rep = jax.vmap(
        lambda c, j: jnp.clip(
            jnp.searchsorted(c, j, side="right"), 0, n - 1),
        in_axes=(1, 0),
    )(cum, srank)                                       # [R, St]
    home = jnp.where(is_shadow, home_base + from_rep,
                     home_base + jnp.arange(n)[:, None])

    n_imported = jnp.zeros((n,), jnp.int32)
    if imported is not None:
        # cross-shard arrivals rank behind the local redirects in the
        # shadow-slot order (local work keeps §4.4 priority)
        admit_import = shadow_free & (srank >= n_remote[:, None]) & (
            srank < (n_remote + imported)[:, None])
        admit = admit | admit_import
        ioff = jnp.cumsum(imported) - imported          # [n] exclusive
        j = srank - n_remote[:, None] + ioff[:, None]   # import arrival rank
        scum = jnp.cumsum(import_src)
        src = jnp.clip(jnp.searchsorted(scum, j, side="right"),
                       0, import_src.shape[0] - 1)
        home = jnp.where(admit_import, import_home[src], home)
        n_imported = imported - jnp.sum(admit_import, axis=1)

    pool = pool._replace(seq_active=pool.seq_active | admit)
    home_of = jnp.where(admit, home, state.home_of)
    remaining = jnp.where(admit, 16, state.remaining)   # 16-token requests
    leftover = (kept - jnp.sum(admit_local, axis=1)
                + n_remote - jnp.sum(admit_remote, axis=1)
                + n_imported)
    return state._replace(pool=pool, home_of=home_of, remaining=remaining,
                          queue=leftover.astype(jnp.int32))


def _decode_all(cfg: EngineConfig, state: EngineState, dram_lenders,
                spill_budget=None, key=None):
    """One decode token for every active slot, batched (borrower metadata
    stays authoritative — shadow slots run with home's pages): a single
    `kv_pool.append_tokens` grows every sequence at once and one paged
    attention over the flattened (replica, slot) batch does the compute.
    ``key`` varies per step (step_count folded in by the caller) so
    attn_norm actually measures a fresh activation batch every step."""
    pool = state.pool
    d = cfg.n_heads * cfg.head_dim
    st = total_slots(cfg)
    r = state.queue.shape[0]

    if key is None:
        key = jax.random.key(7)
    x = jax.random.normal(key, (r, st, d)) * 0.1
    q = (x @ state.wq).reshape(r * st, cfg.n_heads, cfg.head_dim)
    k_t = (x @ state.wk).reshape(r, st, cfg.kv_heads, cfg.head_dim)
    v_t = (x @ state.wv).reshape(r, st, cfg.kv_heads, cfg.head_dim)

    active = pool.seq_active
    length_before = pool.seq_len
    # append returns the offsite page grants of this step per home replica
    # (the LINK_BW debit for spill traffic) — no before/after offsite scan
    with obs_x.scope("kv_append"):
        pool, spill_pages = kvp.append_tokens(pool, k_t, v_t, active,
                                              dram_lenders,
                                              spill_budget=spill_budget)

    p = cfg.pages_per_replica
    k_flat = pool.k.reshape(r * p, cfg.page, cfg.kv_heads, cfg.head_dim)
    v_flat = pool.v.reshape(r * p, cfg.page, cfg.kv_heads, cfg.head_dim)
    scales = {}
    if kvp.quantized(pool):
        # int8 pool: hand the code planes + per-page scales to the fused
        # dequant kernel path (scale-up happens in VMEM before the dot)
        scales = dict(k_scale=pool.k_scale.reshape(-1),
                      v_scale=pool.v_scale.reshape(-1))
    out = kops.paged_attention(
        q, k_flat, v_flat,
        pool.page_table.reshape(r * st, cfg.max_pages),
        pool.seq_len.reshape(r * st),
        **scales,
    )
    out = jnp.where(active.reshape(-1)[:, None, None], out, 0.0)
    attn_norm = jnp.sum(out.astype(jnp.float32) ** 2)

    quant_err = jnp.zeros((), jnp.float32)
    if cfg.kv_quant != "none":
        # write-side quantization error: read this step's token rows back
        # through the dequant path and compare against what decode produced
        wrote = pool.seq_len > length_before            # [R, St]
        lp = jnp.clip((pool.seq_len - 1) // cfg.page, 0, cfg.max_pages - 1)
        phys = jnp.take_along_axis(
            pool.page_table, lp[..., None], axis=2)[..., 0]
        safe = jnp.clip(phys, 0, r * p - 1).reshape(-1)
        slot = jnp.clip((pool.seq_len - 1) % cfg.page, 0,
                        cfg.page - 1).reshape(-1)
        ks = pool.k_scale.reshape(-1)[safe][:, None, None]
        vs = pool.v_scale.reshape(-1)[safe][:, None, None]
        kr = k_flat[safe, slot].astype(jnp.float32) * ks
        vr = v_flat[safe, slot].astype(jnp.float32) * vs
        m = (wrote & (phys >= 0)).reshape(-1)[:, None, None]
        kt = k_t.reshape(r * st, cfg.kv_heads, cfg.head_dim)
        vt = v_t.reshape(r * st, cfg.kv_heads, cfg.head_dim)
        quant_err = (jnp.sum(jnp.where(m, (kr - kt) ** 2, 0.0))
                     + jnp.sum(jnp.where(m, (vr - vt) ** 2, 0.0)))

    remaining = jnp.where(pool.seq_active, state.remaining - 1,
                          state.remaining)
    done = pool.seq_active & (remaining <= 0)
    pool = kvp.release_sequences(pool, done)
    # post-release offsite footprint — the one offsite scan of the step
    offsite_after = kvp.offsite_pages(pool)
    return (state._replace(pool=pool, remaining=jnp.maximum(remaining, 0)),
            jnp.sum(pool.seq_active, axis=1), attn_norm, spill_pages,
            offsite_after, quant_err)


def _pall(x, axis):
    """psum across shards when running under a shard axis; identity in
    single-shard mode."""
    return x if axis is None else jax.lax.psum(x, axis)


# The engine's metric registry (DESIGN.md §12): ONE declaration per
# signal carries both its ring/obs kind and its stats-dict reduction, so
# the classification that used to live in three hand-maintained name sets
# cannot drift from the record sites. `reduce` drives `_finish_stats` /
# the shard_map out specs: "concat" = per-replica arrays concatenate
# across shards, "sum" = reduce to the global scalar the single-shard API
# always reported, "first" = already shard-invariant (psum'd or computed
# from the replicated exchange matrix), "none" = ring-only (never in the
# stats dict).
ENGINE_METRICS = obs_m.MetricSet("engine")
for _nm in ("util", "want_pages", "link_budget_bytes"):
    ENGINE_METRICS.gauge(_nm, per="node", reduce="concat")
for _nm in ("link_redirect_bytes", "link_spill_bytes"):
    ENGINE_METRICS.counter(_nm, per="node", reduce="concat")
for _nm in ("active", "queued", "offsite_pages"):
    ENGINE_METRICS.gauge(_nm, per="node", reduce="sum")
ENGINE_METRICS.counter("redirected", per="node", reduce="sum")
for _nm in ("attn_norm", "log_commits", "quant_err_norm"):
    ENGINE_METRICS.gauge(_nm, per="scalar", reduce="first")
for _nm in ("cross_redirected", "cross_link_borrowed_bytes"):
    ENGINE_METRICS.counter(_nm, per="scalar", reduce="first")
# ring-only extras: never in the stats dict, captured per window anyway
ENGINE_METRICS.gauge("hbm_pressure", per="node", reduce="none")
# live-migration telemetry (DESIGN.md §13): pages drained off risky
# lenders per home replica, and their LINK_BW byte debit — zero unless
# cfg.migrate_pages_per_step > 0
for _nm in ("migrated_pages", "migration_bytes"):
    ENGINE_METRICS.counter(_nm, per="node", reduce="none")
ENGINE_METRICS.histogram("util_hist", bins=8, lo=0.0, hi=1.6)
del _nm

_GLOBAL_STATS = frozenset(
    s.name for s in ENGINE_METRICS.specs() if s.reduce == "first")
_STAT_KEYS = tuple(sorted(
    s.name for s in ENGINE_METRICS.specs() if s.reduce != "none"))


def _finish_stats(stats):
    out = {}
    for k, v in stats.items():
        red = ENGINE_METRICS.spec(k).reduce  # KeyError: unregistered stat
        if red == "concat":
            out[k] = v.reshape(-1)
        elif red == "sum":
            out[k] = jnp.sum(v)
        elif red == "first":
            out[k] = v.reshape(-1)[0] if v.ndim else v
        else:
            raise ValueError(
                f"stat {k!r} is ring-only (reduce='none') and must not "
                "appear in the step stats dict")
    return out


def _level_split_bytes(exports, n_exp_l, cmd_x):
    """Price each replica's exported requests at the level that granted
    them. ``exports`` int32[R] (fill_by_rank order), ``n_exp_l`` int32[L]
    grants per exchange level (nearest first), ``cmd_x`` float32[L] command
    bytes per export at each level. Both sequences partition the same
    rank order [0, Σ exports), so the [R, L] overlap of their cumulative
    ranges attributes every export to exactly one level — deterministic,
    and at L=1 it degenerates to ``exports * cmd_x[0]`` bitwise."""
    cr = jnp.cumsum(exports)
    cr0 = cr - exports
    cl = jnp.cumsum(n_exp_l)
    cl0 = cl - n_exp_l
    overlap = jnp.maximum(
        jnp.minimum(cr[:, None], cl[None, :])
        - jnp.maximum(cr0[:, None], cl0[None, :]), 0)      # [R, L]
    return overlap.astype(jnp.float32) @ cmd_x


def _shard_step(cfg: EngineConfig, axis, state: EngineState,
                arrivals: jax.Array):
    """One shard-local engine step plus the aggregate inter-shard exchange.

    ``axis`` names the shard mesh axis (None = single-shard mode, no
    collectives). The state carries this shard's `n_replicas / n_shards`
    replicas; everything through route/admit/decode is shard-local, and the
    only cross-shard traffic is two all-gathers of per-shard scalar
    summaries (PROCESSOR overflow/capacity and LINK_BW spare/want bytes) —
    the DESIGN.md §9 two-level round. `step` runs this under vmap,
    `make_sharded_step` under shard_map; identical math either way."""
    n = state.queue.shape[0]
    nsh = cfg.n_shards
    manager = _manager(cfg)
    util = utilization(cfg, state)
    mem = hbm_pressure(cfg, state)
    free = kvp.free_pages(state.pool).astype(jnp.float32)
    if cfg.track_failures:
        # failure plane (DESIGN.md §13): a dead replica takes no arrivals,
        # looks saturated to every trigger (never publishes, never
        # redirects toward it), gate-vetoes its own claims, and offers no
        # pages — the same forced-trigger treatment the sim applies
        dead = state.dead
        arrivals = jnp.where(dead, 0, arrivals)
        util = jnp.where(dead, 1.5, util)
        mem = jnp.where(dead, 1.0, mem)
        free = jnp.where(dead, 0.0, free)
    lendable = free
    want_pages = jnp.zeros((n,), jnp.float32)
    if cfg.trace_driven:
        # kv_pool page-access stream: every physical page the decode batch
        # will attend over this step (active sequences' page tables). Pad
        # slots map to -1 -> 0xFFFFFFFF == EMPTY_REF under uint32, the
        # estimator's masking convention.
        tcfg = _telemetry(cfg)
        pt = state.pool.page_table
        live = (pt >= 0) & state.pool.seq_active[:, :, None]
        addrs = jnp.where(live, pt, -1).astype(jnp.uint32)
        mrc_state = tele_win.update_window(
            state.mrc, addrs.reshape(n, -1), tcfg)
        want_pages = tele_want.want_entries(mrc_state, tcfg)
        # reserve the estimated near-future growth (want beyond the pages
        # already backing local sequences) out of the lendable amount: a
        # replica about to re-grow its working set stops lending BEFORE it
        # runs dry, instead of spilling its own sequences to peers
        footprint = jnp.sum(live, axis=(1, 2)).astype(jnp.float32)
        reserve = jnp.maximum(want_pages - footprint, 0.0)
        lendable = jnp.maximum(free - reserve, 0.0)
        state = state._replace(mrc=mrc_state)
    metered = cfg.link_pages_per_step > 0
    page_b = float(kvp.page_nbytes(state.pool))
    inputs = {
        desc.PROCESSOR: mgr.RoundInputs(util=util, gate_util=mem),
        desc.DRAM: mgr.RoundInputs(amount=lendable),
    }
    if metered:
        # a replica under HBM pressure is about to spill — it borrows idle
        # peers' link budgets; relaxed replicas lend theirs
        link_util = mem
        link_pub = jnp.full((n,), float(cfg.link_pages_per_step),
                            jnp.float32)
        if cfg.track_failures:
            # dead replicas publish a zero allowance and never claim
            # (util 0 keeps them under the watermark on both sides)
            link_util = jnp.where(dead, 0.0, link_util)
            link_pub = jnp.where(dead, 0.0, link_pub)
        inputs[desc.LINK_BW] = mgr.RoundInputs(
            util=link_util, amount=link_pub)
    prev_table = state.table  # obs: grant events = round's table diff
    table = manager.round(state.table, inputs)
    state = state._replace(table=table)
    with obs_x.scope("route"):
        kept, sent = _route(cfg, state, arrivals)
    # DRAM descriptors are amount-gated capacity, never claimed: a replica
    # lends KV pages iff its descriptor is live with pages above threshold.
    # The slot comes from the manager's policy (slot_mask), never a literal
    # index — policy reordering must not silently read another rtype's
    # descriptors.
    dmask = manager.slot_mask(desc.DRAM, table.n_slots)
    dram_lenders = jnp.any(
        table.valid & dmask[None, :] & (table.amount_a > DRAM_MIN_PAGES),
        axis=1)
    spill_budget = None
    link_amt = jnp.zeros((n,), jnp.float32)
    budget_bytes = jnp.zeros((n,), jnp.float32)
    redirect_bytes = jnp.zeros((n,), jnp.float32)
    if metered:
        # ONE LINK_BW byte account per borrower (§4.6 cost table): own port
        # allowance plus whatever idle-link peers pledged through the round
        # (assist_matrix is the budget source — borrowed[b] =
        # Σ_l M[l, b] · amount_l). Pledged allowance leaves the lender's own
        # budget, so total admitted transfers never exceed total published
        # allowance (conservation, mirroring the sim's fluid_transfer debit
        # of the lender).
        Ml = manager.assist_matrix(table, desc.LINK_BW)
        link_amt = jnp.full(
            (n,), float(cfg.link_pages_per_step) * page_b, jnp.float32)
        borrowed = link_amt @ Ml
        lent = link_amt * jnp.sum(Ml, axis=1)
        budget_bytes = link_amt - lent + borrowed
        # §4.4 shadow-slot redirection commands debit the account FIRST
        # (the command stream is issued before decode spills): redirects
        # beyond the byte budget stay home and retry via the queue —
        # backpressure, the same rule as a denied spill
        with obs_x.scope("route"):
            cmd_b = float(costs.REDIRECT_CMD_BYTES)
            red_cap = jnp.floor(budget_bytes / cmd_b).astype(jnp.int32)
            cum = jnp.cumsum(sent, axis=1)
            capped = jnp.maximum(
                jnp.minimum(cum, red_cap[:, None]) - (cum - sent), 0)
            kept = kept + jnp.sum(sent - capped, axis=1)
            sent = capped
            redirect_bytes = (jnp.sum(sent, axis=1).astype(jnp.float32)
                              * cmd_b)

    # ---- topology-plane exchange (DESIGN.md §9/§11) ----------------------
    # Shard-local claims above already matched local lenders; only the
    # post-local leftovers cross shards, as ONE (spare, want) scalar pair
    # per shard per rtype. The leftovers settle level by level through
    # `topology.hierarchical_exchange` — nearest level first, each level's
    # grants debited at its own tier's extra-hop price. A flat topology
    # (shards_per_enclosure=0) is the PR 6 two-level round bitwise: one
    # exchange level over all shards at the enclosure tier.
    cross = (axis is not None) and cfg.cross_shard and nsh > 1
    imports = import_src = import_home = None
    cross_red = jnp.zeros((), jnp.float32)
    cross_borrowed = jnp.zeros((), jnp.float32)
    extra_link = jnp.zeros((n,), jnp.float32)
    xch_events = []  # obs: (rows, mask) from this shard's exchange grants
    if cross:
        sid = jax.lax.axis_index(axis)
        shard_topo = shard_topology(cfg)
        levels = range(len(shard_topo.group_sizes))
        # PROCESSOR: requests beyond this shard's normal-slot capacity
        # export to shards with watermark-idle replicas holding free shadow
        # slots (after their own inbound redirects) and spare DRAM.
        cmd_x = tuple(
            float(costs.tier_link_bytes(desc.PROCESSOR,
                                        level=shard_topo.level_tier(lv)))
            for lv in levels)
        free_slots = ~state.pool.seq_active
        free_normal = jnp.sum(free_slots[:, : cfg.seq_slots], axis=1)
        free_shadow = jnp.sum(free_slots[:, cfg.seq_slots:], axis=1)
        overflow = jnp.maximum(kept - free_normal, 0)
        if metered:
            # each exported request debits its level's extra-hop command
            # price from the SAME unified byte account, before spill
            # traffic; the cap is conservative at the priciest tier
            afford = jnp.floor(
                (budget_bytes - redirect_bytes) / max(cmd_x)
            ).astype(jnp.int32)
            overflow = jnp.minimum(overflow, jnp.maximum(afford, 0))
        inbound = jnp.sum(sent, axis=0)
        host_ok = (util <= WATERMARK) & (free > DRAM_MIN_PAGES)
        host_cap = jnp.where(
            host_ok, jnp.maximum(free_shadow - inbound, 0), 0)
        summary = jnp.stack([jnp.sum(host_cap).astype(jnp.float32),
                             jnp.sum(overflow).astype(jnp.float32)])
        gathered = jax.lax.all_gather(summary, axis)       # [S, 2]
        grants, _ = topo.hierarchical_exchange(
            gathered[:, 0], gathered[:, 1], shard_topo)
        g_int = jnp.floor(grants).astype(jnp.int32)  # [level, host, source]
        n_exp_l = jnp.sum(g_int[:, :, sid], axis=1)        # [L]
        exports = mgr.fill_by_rank(overflow, jnp.sum(n_exp_l))
        kept = kept - exports
        if metered:
            redirect_bytes = redirect_bytes + _level_split_bytes(
                exports, n_exp_l, jnp.asarray(cmd_x, jnp.float32))
        imports = mgr.fill_by_rank(host_cap, jnp.sum(g_int[:, sid, :]))
        import_src = jnp.sum(g_int[:, sid, :], axis=0)
        import_home = jnp.arange(nsh, dtype=jnp.int32) * n
        cross_red = jnp.sum(g_int).astype(jnp.float32)
        if cfg.obs.enabled:
            # lender-side attribution: each shard logs only the rows where
            # it is the granting host, so the merged log holds every
            # exchange grant exactly once (shard ids in lender/borrower)
            for lv in levels:
                xch_events.append(obs_s.grant_event_rows(
                    g_int[lv][sid][None, :].astype(jnp.float32),
                    rtype=desc.PROCESSOR, level=shard_topo.level_tier(lv),
                    t=state.step_count, price=cmd_x[lv],
                    lender_base=sid))
        if metered:
            # LINK_BW: pressured shards borrow idle shards' leftover byte
            # allowance; the detour pays its level's extra-hop command
            # bytes as the exchange overhead, so a borrowed page is worth
            # less than a local one — and strictly less again when it
            # crosses the enclosure boundary to the fabric tier
            link_ohs = tuple(
                float(costs.tier_link_bytes(
                    desc.LINK_BW, 0.0,
                    level=shard_topo.level_tier(lv))) / page_b
                for lv in levels)
            l_spare = jnp.where(
                mem <= WATERMARK,
                jnp.maximum(budget_bytes - redirect_bytes, 0.0), 0.0)
            l_want = jnp.where(mem > WATERMARK, link_amt, 0.0)
            lsummary = jnp.stack([jnp.sum(l_spare), jnp.sum(l_want)])
            lgathered = jax.lax.all_gather(lsummary, axis)  # [S, 2]
            lgrants, lrecv = topo.hierarchical_exchange(
                lgathered[:, 0], lgathered[:, 1], shard_topo, link_ohs)
            lent_x = jnp.sum(lgrants[:, sid, :])
            recv_x = jnp.sum(lrecv[:, sid])
            spare_tot = jnp.sum(l_spare)
            lent_each = jnp.where(
                spare_tot > 0,
                l_spare * (lent_x / jnp.maximum(spare_tot, 1e-9)), 0.0)
            want_tot = jnp.sum(l_want)
            extra_link = jnp.where(
                want_tot > 0,
                l_want * (recv_x / jnp.maximum(want_tot, 1e-9)), 0.0)
            budget_bytes = budget_bytes - lent_each
            cross_borrowed = _pall(recv_x, axis)
            if cfg.obs.enabled:
                for lv in levels:
                    xch_events.append(obs_s.grant_event_rows(
                        lgrants[lv][sid][None, :],
                        rtype=desc.LINK_BW,
                        level=shard_topo.level_tier(lv),
                        t=state.step_count, price=link_ohs[lv] * page_b,
                        lender_base=sid))
    migrated = jnp.zeros((n,), jnp.int32)
    mig_bytes = jnp.zeros((n,), jnp.float32)
    if cfg.migrate_pages_per_step > 0:
        # live migration (DESIGN.md §13): fold this step's lender
        # utilization into the reclaim predictor; lenders projected to
        # cross the reclaim threshold stop accepting new spill AND their
        # held offsite pages start draining home (or to a calm second
        # lender) under the per-step page allowance. The drain debits the
        # SAME unified LINK_BW byte account as spill traffic, before the
        # spill floor — migrating early costs link budget now to avoid
        # the recovery burst later.
        rstate, risk = tele_reclaim.update(state.reclaim, mem, cfg.reclaim)
        if cfg.track_failures:
            risk = risk & ~dead  # a dead pool is already freed — no drain
        dram_lenders = dram_lenders & ~risk
        headroom = jnp.full((n,), float(cfg.migrate_pages_per_step),
                            jnp.float32)
        if metered:
            headroom = jnp.minimum(headroom, jnp.maximum(
                budget_bytes - redirect_bytes + extra_link, 0.0) / page_b)
        pool2, migrated = kvp.drain_offsite(
            state.pool, risk, jnp.floor(headroom).astype(jnp.int32),
            dram_lenders)
        mig_bytes = migrated.astype(jnp.float32) * page_b
        state = state._replace(pool=pool2, reclaim=rstate)
    if metered:
        # spill pages get whatever bytes the command stream left over, plus
        # any cross-shard borrowed allowance (already net of the hop tax)
        avail = budget_bytes - redirect_bytes + extra_link
        if cfg.migrate_pages_per_step > 0:
            avail = avail - mig_bytes
        spill_budget = jnp.floor(avail / page_b).astype(jnp.int32)
        budget_bytes = budget_bytes + extra_link

    home_base = jnp.int32(0) if axis is None else jax.lax.axis_index(axis) * n
    with obs_x.scope("admit"):
        state = _admit(cfg, state, kept, sent, home_base=home_base,
                       imported=imports, import_src=import_src,
                       import_home=import_home)
    key = jax.random.fold_in(jax.random.key(7), state.step_count)
    (state, active, attn_norm, spill_pages, offsite_after,
     quant_err) = _decode_all(cfg, state, dram_lenders, spill_budget, key)
    stats = {
        "active": active,
        "redirected": jnp.sum(sent, axis=1),
        "queued": state.queue,
        "util": utilization(cfg, state),
        "attn_norm": _pall(attn_norm, axis),
        "offsite_pages": offsite_after,
        "log_commits": _pall(jnp.sum(state.pool.logs.commits), axis),
        "want_pages": want_pages,
        # unified LINK_BW account telemetry, per replica. With metering on
        # (link_pages_per_step > 0): spill + redirect ≤ budget each step
        # (budget includes cross-shard borrowed bytes, net of the hop tax).
        # With metering off, budget and redirect bytes are zero while
        # spill bytes still report the (unmetered) offsite page traffic.
        "link_budget_bytes": budget_bytes,
        "link_redirect_bytes": redirect_bytes,
        "link_spill_bytes": spill_pages.astype(jnp.float32) * page_b,
        # hierarchical-round telemetry: requests exchanged across shards
        # and LINK bytes borrowed across shards this step (both global,
        # identical on every shard by construction)
        "cross_redirected": cross_red,
        "cross_link_borrowed_bytes": cross_borrowed,
        # write-side int8 quantization error this step (sum of squared
        # dequant-read-back error over the token rows written); zero when
        # kv_quant="none"
        "quant_err_norm": _pall(quant_err, axis),
    }
    if cfg.obs.enabled:
        with obs_x.scope("obs_record"):
            base = (jnp.int32(0) if axis is None
                    else jax.lax.axis_index(axis) * n)
            ring_vals = dict(stats)
            ring_vals["hbm_pressure"] = hbm_pressure(cfg, state)
            ring_vals["migrated_pages"] = migrated.astype(jnp.float32)
            ring_vals["migration_bytes"] = mig_bytes
            ring_vals["util_hist"] = stats["util"]
            ms = ENGINE_METRICS.record(state.obs.metrics, ring_vals)
            rows, mask = obs_s.table_event_rows(
                prev_table, state.table, state.step_count, base=base)
            # ONE scatter per step: concatenating the table-diff rows with
            # the exchange-grant rows keeps the bounded-log append a single
            # buffer update (three separate appends tripled the cost)
            rows = jnp.concatenate([rows] + [r for r, _ in xch_events])
            mask = jnp.concatenate([mask] + [m for _, m in xch_events])
            log = obs_s.append(state.obs.events, rows, mask)
            state = state._replace(obs=EngineObs(metrics=ms, events=log))
    return state, stats


# vmap axes for the hierarchical state: shard-owned fields map over their
# leading (shard) axis, replicated fields stay unmapped
_STATE_AXES = EngineState(
    pool=0, table=0, home_of=0, remaining=0, queue=0,
    step_count=None, mrc=0, wq=None, wk=None, wv=None, wo=None, obs=0,
    dead=0, reclaim=0)


def _to_shards(cfg: EngineConfig, state: EngineState) -> EngineState:
    """Canonical [R, ...] layout -> [S, R/S, ...] vmap layout for the
    shard-owned fields (the pool's [S] WAL counters become [S, 1] — the
    same per-shard local shape shard_map produces)."""
    s = cfg.n_shards

    def split(x):
        return x.reshape(s, x.shape[0] // s, *x.shape[1:])

    return state._replace(**{
        f: jax.tree.map(split, getattr(state, f)) for f in SHARDED_FIELDS})


def _from_shards(cfg: EngineConfig, state: EngineState) -> EngineState:
    def merge(x):
        return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])

    return state._replace(**{
        f: jax.tree.map(merge, getattr(state, f)) for f in SHARDED_FIELDS})


def _step_impl(cfg: EngineConfig, state: EngineState, arrivals: jax.Array):
    """Unjitted step body shared by `step` (one jit per call) and
    `run_steps` (lax.scan over many)."""
    if cfg.n_shards == 1:
        out, stats = _shard_step(cfg, None, state, arrivals)
    else:
        nl = local_replicas(cfg)
        out, stats = jax.vmap(
            partial(_shard_step, cfg, SHARD_AXIS),
            in_axes=(_STATE_AXES, 0), out_axes=(_STATE_AXES, 0),
            axis_name=SHARD_AXIS,
        )(_to_shards(cfg, state), arrivals.reshape(cfg.n_shards, nl))
        out = _from_shards(cfg, out)
    out = out._replace(step_count=state.step_count + 1)
    return out, _finish_stats(stats)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def step(cfg: EngineConfig, state: EngineState, arrivals: jax.Array):
    """One engine step: local management round(s) -> route -> admit ->
    decode -> stats. With cfg.n_shards > 1 the hierarchy executes under
    vmap over the shard axis on the current device — numerically identical
    to `make_sharded_step`'s shard_map execution on a real mesh. The input
    state is donated: callers must rebind (`state, stats = step(...)`)."""
    return _step_impl(cfg, state, arrivals)


@partial(jax.jit, static_argnames=("cfg", "k"), donate_argnums=(1,))
def run_steps(cfg: EngineConfig, state: EngineState,
              arrivals_txr: jax.Array, k: int | None = None):
    """Multi-step driver: `lax.scan` over `k` engine steps with a DONATED
    carry — one dispatch and one compiled loop instead of k round-trips
    through `step`, which is where short-step benchmarks spend most of
    their wall clock.

    ``arrivals_txr``: int32[T, R] arrival schedule; step i consumes row
    ``i % T`` (so a one-row schedule is a constant rate). ``k`` defaults to
    T. Returns (state', stats) with every stat stacked along a leading
    [k] step axis — same keys and per-step values as `step`."""
    t = arrivals_txr.shape[0]
    n = t if k is None else int(k)

    def body(carry, i):
        return _step_impl(cfg, carry, arrivals_txr[i % t])

    return jax.lax.scan(body, state, jnp.arange(n))


def obs_history(state: EngineState) -> dict:
    """Host-decode the metric rings of a canonical-layout state:
    {metric: [windows, lanes(, bins)]} oldest-first (empty when obs is
    disabled)."""
    if state.obs is None:
        return {}
    return ENGINE_METRICS.history(state.obs.metrics)


def obs_totals(state: EngineState) -> dict:
    if state.obs is None:
        return {}
    return ENGINE_METRICS.totals(state.obs.metrics)


def obs_events(state: EngineState):
    """Host-decode the grant-lifecycle log: (records, n_dropped). Level-0
    lender/borrower ids are global replica ids; level>=1 rows carry shard
    ids (the exchange's scope)."""
    if state.obs is None:
        return [], 0
    return obs_s.decode(state.obs.events)


def state_partition_specs(cfg: EngineConfig) -> EngineState:
    """Per-leaf PartitionSpec pytree for an EngineState on the 1-D
    replica-shard mesh: shard-owned fields (SHARDED_FIELDS, including the
    pool's [n_shards] WAL counters) shard their leading axis over
    SHARD_AXIS; step_count and the decode weights replicate. Feed through
    `launch.sharding.engine_state_shardings` to device_put a state before
    calling the `make_sharded_step` step."""
    shapes = jax.eval_shape(lambda: init(cfg, jax.random.key(0)))
    fields = {}
    for f in EngineState._fields:
        spec = P(SHARD_AXIS) if f in SHARDED_FIELDS else P()
        fields[f] = jax.tree.map(lambda _, s=spec: s, getattr(shapes, f))
    return EngineState(**fields)


def make_sharded_step(cfg: EngineConfig, mesh=None):
    """Build the jitted shard_map'ed engine step: each mesh device owns
    `n_replicas / n_shards` replicas' pool, descriptor table, and telemetry
    state, runs the full local round on them, and participates in the
    aggregate inter-shard exchange as real collectives (DESIGN.md §9).

    ``mesh`` defaults to `launch.mesh.make_serving_mesh(cfg.n_shards)`.
    Returns step_fn(state, arrivals) -> (state', stats) over the canonical
    [R, ...] state layout, bitwise-matching `step`'s vmap execution."""
    if cfg.n_shards < 2:
        raise ValueError("make_sharded_step needs cfg.n_shards >= 2; "
                         "single-shard serving is just `step`")
    if mesh is None:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(cfg.n_shards)
    state_specs = state_partition_specs(cfg)
    stats_specs = {k: (P() if k in _GLOBAL_STATS else P(SHARD_AXIS))
                   for k in _STAT_KEYS}
    fn = jax.shard_map(
        partial(_shard_step, cfg, SHARD_AXIS), mesh=mesh,
        in_specs=(state_specs, P(SHARD_AXIS)),
        out_specs=(state_specs, stats_specs),
        check_vma=False)

    @partial(jax.jit, donate_argnums=(0,))
    def sharded_step(state: EngineState, arrivals: jax.Array):
        out, stats = fn(state, arrivals)
        out = out._replace(step_count=state.step_count + 1)
        return out, _finish_stats(stats)

    return sharded_step
