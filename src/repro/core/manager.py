"""The unified decentralized resource-management round (paper §4.3–§4.5).

One authoritative implementation of the publish/claim machinery that every
substrate consumes — the JBOF fluid simulator (`repro.jbof.sim`), the
trigger state machine (`repro.core.harvest.apply_processor_round`), and the
serving engine (`repro.serving.engine`). The round is *resource-generic*:
a `ManagerConfig` carries a tuple of `ResourcePolicy` entries — one per
harvestable rtype (compute-end clocks, memory segments/pages, flash-backbone
channel time, link bytes, custom) — and `round()` is a loop over them. No
per-rtype code forks: policy differences (slot ranges, claim-sweep count,
hysteresis watermarks, whether claims persist across rounds, capacity- vs
utilization-triggered publishing) are data.

A round, per registered policy (see DESIGN.md §2):

  trigger     quadrant logic on (own util, gate util) via
              `harvest.harvest_triggers`, with optional `gate_watermark`
              hysteresis; capacity-style policies (`amount_gated`) instead
              lend whenever their amount exceeds `min_amount`
  publish     every lender simultaneously (re)writes the policy's
              descriptor slots — surplus fragmented across `slots`
  release     claims whose borrower no longer qualifies, and claims on
              withdrawn descriptors, drop to FREE
  claim       `claim_rounds` deterministic sweeps, busiest borrower first
              (a stable sort on `-util`: ties by node id), each sweep
              claiming at most one lender per borrower up to `lender_cap`
  sync        `descriptors.sync_utilization` refreshes the amount fields
              per-rtype via the ResourceSpec registry

Everything is a pure function of (table, inputs); under SPMD every
replica computes identical rounds on the replicated table, which is what
replaces the paper's CAS atomicity (DESIGN.md §3).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..obs import export as obs_x
from . import descriptors as d
from . import harvest as hv

_EPS = 1e-9


class ResourcePolicy(NamedTuple):
    """Static per-rtype knobs for the management round. All fields are
    Python scalars so a tuple of policies is hashable and rides through
    ``jax.jit(..., static_argnames=...)`` unchanged."""

    rtype: int                    # descriptors.REGISTRY key
    slot0: int = 0                # first descriptor slot owned by this rtype
    slots: int = 1                # slots carrying the fragmented surplus
    claim_rounds: int = 1         # deterministic claim sweeps (0 = no claims)
    max_lenders: int = 0          # cap lenders per borrower (0 = claim_rounds)
    watermark: float = hv.WATERMARK        # busy threshold on own utilization
    gate_watermark: float | None = None    # borrow-cancel hysteresis (§4.4)
    min_amount: float = 0.0       # publish only above this amount
    preserve_claims: bool = False  # keep claims across rounds (harvest-style)
    amount_gated: bool = False    # capacity style: lend = amount > min_amount
    # The futility gate vetoes ACQUIRING new claims only; existing claims
    # are retained while the borrower's own resource stays busy. Without
    # this, two harvestable rtypes gating on each other 2-cycle: a flash
    # grant makes the data-end read "exhausted" and cancels proc claims the
    # same round, which un-saturates the backbone and cancels the flash
    # grant one round later, forever. Requires preserve_claims.
    gate_new_only: bool = False

    @property
    def lender_cap(self) -> int:
        return self.max_lenders if self.max_lenders > 0 else max(self.claim_rounds, 1)


class RoundInputs(NamedTuple):
    """Per-rtype dynamic inputs to one management round.

    ``util``:      float32[N] the resource's own measured utilization
                   (trigger + claim ordering + sync).
    ``gate_util``: float32[N] the paired resource's utilization — the §4.4
                   "borrowing is futile" gate (e.g. data-end util gates
                   compute-end borrowing; link util gates backbone borrowing).
    ``amount``:    float32[N] current lendable amount (capacity types; also
                   published into amount_a and kept fresh by sync).
    """

    util: jax.Array | None = None
    gate_util: jax.Array | None = None
    amount: jax.Array | None = None


class ManagerConfig(NamedTuple):
    """Static per-consumer config: the descriptor-table width plus one
    `ResourcePolicy` per harvestable resource type."""

    n_slots: int = 2                               # descriptor slots per node
    policies: tuple[ResourcePolicy, ...] = ()

    def policy(self, rtype: int) -> ResourcePolicy:
        for pol in self.policies:
            if pol.rtype == rtype:
                return pol
        raise KeyError(f"no policy registered for rtype {rtype}")


def table_transitions(
    prev: d.IdleResourceTable, new: d.IdleResourceTable
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Grant-lifecycle transitions between two table snapshots.

    The obs plane derives publish/claim/release events as a diff of the
    table entering a management round against the table leaving it, so
    nothing threads a logger through the claim sweeps. Returns bool[n, s]
    masks ``(published, withdrawn, claimed, released)``:

    - published: descriptor went invalid -> valid (lender started lending)
    - withdrawn: valid -> invalid (lender pulled the offer)
    - claimed:   borrower_id landed on a (new) borrower
    - released:  a standing claim dropped or changed hands
    """
    changed = new.borrower_id != prev.borrower_id
    published = new.valid & ~prev.valid
    withdrawn = prev.valid & ~new.valid
    claimed = (new.borrower_id != d.FREE) & changed
    released = (prev.borrower_id != d.FREE) & changed
    return published, withdrawn, claimed, released


def revoke_nodes(
    table: d.IdleResourceTable, dead: jax.Array
) -> tuple[d.IdleResourceTable, jax.Array]:
    """Invalidate every descriptor a dead node published and release
    every claim a dead node holds (§4.3 descriptor invalidation, forced
    by failure instead of the lend trigger).

    ``dead``: bool[n]. A failed *lender*'s rows go invalid — borrowers
    drawing on them lose the grant at the very next transfer derivation,
    well inside one management interval. A failed *borrower*'s claims
    revert to FREE so the descriptors are immediately re-claimable.
    Idempotent: re-revoking an already-dead node counts zero, so the
    per-window revocation tally only ticks on the transition.

    Returns ``(table, n_revoked)`` with ``n_revoked`` (i32 scalar) the
    number of slots whose lender side invalidated or whose claim
    released.
    """
    dead = jnp.asarray(dead, bool)
    n = dead.shape[0]
    dead_lender = dead[:, None] & table.valid
    bid = jnp.clip(table.borrower_id.astype(jnp.int32), 0, n - 1)
    dead_borrower = (table.borrower_id != d.FREE) & dead[bid]
    n_revoked = jnp.sum(dead_lender | dead_borrower).astype(jnp.int32)
    return table._replace(
        valid=table.valid & ~dead[:, None],
        borrower_id=jnp.where(
            dead_lender | dead_borrower, jnp.int32(d.FREE),
            table.borrower_id),
    ), n_revoked


def fluid_transfer(
    assist: jax.Array,
    surplus: jax.Array,
    deficit: jax.Array,
    overhead: float | jax.Array = 0.0,
) -> tuple[jax.Array, jax.Array]:
    """Turn an assist matrix into conserved fluid capacity transfers.

    ``assist``: float32[lender, borrower] pledge fractions (rows sum ≤ 1).
    ``surplus``/``deficit``: float32[N] spare / missing capacity per node,
    in the resource's own unit (clock-seconds, channel-seconds, link-seconds).
    ``overhead``: fractional tax on redirected work — either the flat §5.3
    sync constant (scalar) or a per-borrower float32[N] array priced from
    the per-op §4.6 cost table (`core.costs.overhead_frac`), which makes
    the tax scale with each borrower's I/O size.

    Returns ``(assist_in, used_from)``: per-borrower capacity received (net
    of overhead) and the [lender, borrower] lender-time actually consumed.
    Conservation holds by construction: each lender donates at most its
    surplus (row sums ≤ 1, draw ≤ 1) and each borrower receives at most its
    deficit — the property the conservation tests pin down.
    """
    with obs_x.scope("fluid_transfer"):
        pledged = assist * surplus[:, None]              # [l, b]
        gross = jnp.sum(pledged, axis=0)
        avail = gross / (1.0 + overhead)
        used = jnp.minimum(avail, deficit)
        draw = jnp.where(
            gross > 0, used * (1.0 + overhead) / jnp.maximum(gross, _EPS),
            0.0)
        used_from = pledged * draw[None, :]
    return used, used_from


def shard_exchange(
    spare: jax.Array,
    want: jax.Array,
    overhead: float | jax.Array = 0.0,
) -> tuple[jax.Array, jax.Array]:
    """The inter-shard half of a hierarchical management round (DESIGN.md §9).

    ``spare`` / ``want``: float32[S] per-shard AGGREGATE exportable surplus
    and unmet demand for one rtype. Each shard's local round has already
    matched local lenders to local borrowers, so these are post-local
    leftovers — one scalar pair per shard is all that crosses the fabric.
    ``overhead``: fractional cross-shard tax (the §4.6 extra-hop price from
    `core.costs.tier_overhead_s`): a borrower draws ``1 + overhead`` units of
    lender surplus per unit actually received.

    Local-first netting: a shard reporting both spare and want resolves
    internally first; only the net crosses shards — "claims prefer
    shard-local lenders and spill cross-shard only when the local pool is
    dry". The cross-shard fill is proportional: total net demand is scaled
    to what net surplus can fund, and each lender shard contributes in
    proportion to its net spare.

    Returns ``(grants, received)``: ``grants`` float32[lender_shard,
    borrower_shard] units drawn from each lender's surplus; ``received``
    float32[S] usable units at each borrower (net of overhead).
    Conservation by construction: Σ_b grants[l, b] ≤ spare[l],
    received[b] ≤ want[b], and grants[s, s] == 0 (netting zeroes one side
    of every shard). Every shard computes the identical matrix from the
    all-gathered summaries — determinism replacing CAS at the second level,
    exactly as it does within a shard (DESIGN.md §3).
    """
    spare = jnp.asarray(spare, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    spare_net = jnp.maximum(spare - want, 0.0)
    want_net = jnp.maximum(want - spare, 0.0)
    total_spare = jnp.sum(spare_net)
    draw_full = want_net * (1.0 + overhead)
    total_draw = jnp.sum(draw_full)
    scale = jnp.where(
        total_draw > 0,
        jnp.minimum(1.0, total_spare / jnp.maximum(total_draw, _EPS)),
        0.0)
    draw = draw_full * scale
    frac = jnp.where(
        total_spare > 0, spare_net / jnp.maximum(total_spare, _EPS), 0.0)
    grants = frac[:, None] * draw[None, :]
    received = draw / (1.0 + overhead)
    return grants, received


def fill_by_rank(capacity: jax.Array, total) -> jax.Array:
    """Deterministically split integer ``total`` across nodes by filling
    ``capacity`` in index order: out[i] = clip(total − Σ_{j<i} cap[j], 0,
    cap[i]). Every shard computing this on identical inputs assigns the
    same per-node portions — the integer-grant distribution step of the
    hierarchical round (no CAS, DESIGN.md §3/§9)."""
    capacity = jnp.asarray(capacity)
    cum = jnp.cumsum(capacity) - capacity
    return jnp.clip(total - cum, 0, capacity)


def busy_split(
    work: jax.Array,
    cap: jax.Array,
    assist_in: jax.Array,
    used_from: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Decompose each node's performed work into busy-time attribution.

    ``work``: float32[N] resource time actually done (post-scale);
    ``cap``: own capacity; ``assist_in``/``used_from``: a `fluid_transfer`
    grant. Own capacity runs first, the overflow ran on lenders' donated
    capacity, and each lender's donation is charged by its borrowers'
    actual usage fraction. Returns ``(own_done, remote_done, out_done)``;
    a node's busy time is ``own_done + out_done``.
    """
    remote = jnp.clip(work - cap, 0.0, assist_in)
    own = jnp.clip(work - remote, 0.0, cap)
    usage = jnp.where(
        assist_in > 0, remote / jnp.maximum(assist_in, _EPS), 0.0)
    out = used_from @ usage
    return own, remote, out


class ResourceManager:
    """Config-bound view of the management round. Stateless: the descriptor
    table is threaded through, never stored, so instances can be created
    freely inside jitted code."""

    def __init__(self, cfg: ManagerConfig):
        for pol in cfg.policies:
            if pol.gate_new_only and not pol.preserve_claims:
                raise ValueError(
                    f"rtype {pol.rtype}: gate_new_only retains claims across "
                    "rounds and therefore requires preserve_claims=True "
                    "(without it the publish phase wipes claims every round "
                    "and the flag silently does nothing)")
            if pol.amount_gated and (pol.preserve_claims or pol.claim_rounds > 0):
                raise ValueError(
                    f"rtype {pol.rtype}: amount_gated policies have no borrow "
                    "trigger — claims are never made (claim_rounds must be 0) "
                    "and preserve_claims would drop every claim each round; "
                    "consumers pull capacity via lenders_of/amount instead")
        self.cfg = cfg

    # ------------------------------------------------------------- setup
    def init_table(self, n_nodes: int) -> d.IdleResourceTable:
        return d.make_table(n_nodes, self.cfg.n_slots)

    # ------------------------------------------------------------- round
    def round(
        self,
        table: d.IdleResourceTable,
        inputs: dict[int, RoundInputs],
    ) -> d.IdleResourceTable:
        """One full management round: loop the registered policies through
        trigger → publish → release → claim, then one per-rtype sync."""
        with obs_x.scope("mgmt_round"):
            return self._round(table, inputs)

    def _round(
        self,
        table: d.IdleResourceTable,
        inputs: dict[int, RoundInputs],
    ) -> d.IdleResourceTable:
        n = table.n_nodes
        zeros = jnp.zeros((n,), jnp.float32)
        utils: dict[int, jax.Array] = {}
        amounts: dict[int, jax.Array] = {}
        for pol in self.cfg.policies:
            inp = inputs.get(pol.rtype)
            if inp is None:
                # a silently skipped policy would leave its previously
                # published descriptors valid with stale amounts/claims
                raise KeyError(
                    f"round() missing RoundInputs for configured rtype "
                    f"{pol.rtype}; every policy needs inputs every round")
            util = zeros if inp.util is None else jnp.asarray(inp.util, jnp.float32)
            gate = zeros if inp.gate_util is None else jnp.asarray(
                inp.gate_util, jnp.float32)
            amount = None if inp.amount is None else jnp.asarray(
                inp.amount, jnp.float32)
            if pol.amount_gated:
                if amount is None:
                    raise ValueError(
                        f"amount_gated policy for rtype {pol.rtype} needs an amount")
                lend = amount > pol.min_amount
                borrow = jnp.zeros((n,), jnp.bool_)
                keep = borrow
            else:
                lend, borrow = hv.harvest_triggers(
                    util, gate, pol.watermark, pol.gate_watermark)
                keep = (util > pol.watermark) if pol.gate_new_only else borrow
                if amount is not None and pol.min_amount > 0.0:
                    lend = lend & (amount > pol.min_amount)
            table = self._publish(table, pol, lend, util, amount)
            if pol.preserve_claims:
                table = self._release_stale(table, pol, keep)
            if pol.claim_rounds > 0:
                with obs_x.scope("claim_sweep"):
                    table = self._claim_sweeps(table, pol, util, borrow)
            utils[pol.rtype] = util
            if amount is not None:
                amounts[pol.rtype] = amount
        return d.sync_utilization(table, utils, amounts)

    # ----------------------------------------------------------- publish
    def _slot_mask(self, pol: ResourcePolicy, n_slots: int) -> jax.Array:
        sid = jnp.arange(n_slots)
        return (sid >= pol.slot0) & (sid < pol.slot0 + pol.slots)

    def slot_mask(self, rtype: int, n_slots: int | None = None) -> jax.Array:
        """bool[S] — which descriptor slots ``rtype``'s policy owns. The
        supported way for consumers to locate a policy's descriptors in the
        table; hardcoded slot indices break silently when the policy tuple
        is reordered or a policy is inserted before them."""
        pol = self.cfg.policy(rtype)
        return self._slot_mask(
            pol, self.cfg.n_slots if n_slots is None else n_slots)

    def _publish(
        self,
        table: d.IdleResourceTable,
        pol: ResourcePolicy,
        lend: jax.Array,
        util: jax.Array,
        amount: jax.Array | None,
    ) -> d.IdleResourceTable:
        """Vectorized publish/withdraw: every node writes the policy's
        descriptor slots at once, fragmenting its surplus across them."""
        n, s = table.valid.shape
        sel = jnp.broadcast_to(self._slot_mask(pol, s)[None, :], (n, s))
        if pol.preserve_claims:
            # only stale claims — those sitting on a withdrawn descriptor —
            # are dropped; live claims survive re-publication
            drop = sel & (~lend)[:, None] & (table.rtype == jnp.int8(pol.rtype))
            borrower = jnp.where(drop, jnp.int32(d.FREE), table.borrower_id)
        else:
            borrower = jnp.where(sel, jnp.int32(d.FREE), table.borrower_id)
        amount_a = table.amount_a
        if amount is not None:
            amount_a = jnp.where(sel, amount[:, None], amount_a)
        return table._replace(
            valid=jnp.where(sel, lend[:, None], table.valid),
            rtype=jnp.where(sel, jnp.int8(pol.rtype), table.rtype),
            amount_a=amount_a,
            amount_b=jnp.where(sel, util[:, None], table.amount_b),
            borrower_id=borrower,
        )

    # ----------------------------------------------------------- release
    @staticmethod
    def _release_stale(
        table: d.IdleResourceTable, pol: ResourcePolicy, borrow: jax.Array
    ) -> d.IdleResourceTable:
        """Claims of nodes that stopped qualifying as borrowers drop."""
        n = table.n_nodes
        safe_bid = jnp.clip(table.borrower_id, 0, n - 1)
        mine = (table.borrower_id != d.FREE) & (
            table.rtype == jnp.int8(pol.rtype))
        keep = ~mine | borrow[safe_bid]
        return table._replace(
            borrower_id=jnp.where(keep, table.borrower_id, jnp.int32(d.FREE))
        )

    # ------------------------------------------------------------- claim
    def _claim_sweeps(
        self,
        table: d.IdleResourceTable,
        pol: ResourcePolicy,
        util: jax.Array,
        borrow: jax.Array,
    ) -> d.IdleResourceTable:
        """``claim_rounds`` sequential-deterministic sweeps, busiest borrower
        first ("most starved first"); each sweep a borrower claims its best
        lender by `descriptors.claim_one`, capped at ``lender_cap``.

        Claims move only ``borrower_id``, so the offers and their scores are
        computed once and the sweeps carry ``borrower_id`` alone. One stable
        sort gives the busiest-first order (ties by node id) and each
        node's borrow flag in it, so the node step reads no table or vector
        by index: it is compares, reductions and one argmax, O(N·S).

        Cap semantics (pinned by test_manager.py::
        test_lender_cap_counts_distinct_lenders_not_slots): ``have`` is the
        any-slot `lenders_of` reduction, so ``lender_cap`` bounds DISTINCT
        lender nodes per borrower — claiming a second slot of an
        already-claimed lender does not consume cap. That is the
        fragmentation feature (a borrower may take several fragments of one
        lender's surplus), not a leak: total claimed slots are separately
        bounded by ``claim_rounds`` (at most one claim per sweep), and a
        borrower at the distinct-lender cap acquires nothing further."""
        offers, score = d.claim_score(table, pol.rtype)
        node_ids = jnp.arange(table.n_nodes, dtype=jnp.int32)
        _, order, wants = jax.lax.sort(
            (-util, node_ids, borrow), num_keys=1, is_stable=True)

        def node_step(bids, x):
            node, want = x
            have = jnp.sum(jnp.any(offers & (bids == node), axis=1))
            bids, _, _ = d.claim_one(
                offers, score, bids, node, want & (have < pol.lender_cap))
            return bids, None

        def sweep(bids, _):
            bids, _ = jax.lax.scan(node_step, bids, (order, wants))
            return bids, None

        bids, _ = jax.lax.scan(
            sweep, table.borrower_id, None, length=pol.claim_rounds)
        return table._replace(borrower_id=bids)

    # ------------------------------------------------------------ derive
    def assist_matrix(
        self, table: d.IdleResourceTable, rtype: int
    ) -> jax.Array:
        """float32[lender, borrower] — fraction of each lender's surplus
        pledged to each borrower (claimed ``rtype`` slots / the policy's
        ``slots``). Rows sum to at most 1."""
        pol = self.cfg.policy(rtype)
        n, s = table.valid.shape
        claimed = (
            table.valid
            & (table.borrower_id != d.FREE)
            & (table.rtype == jnp.int8(rtype))
        )
        b = jnp.clip(table.borrower_id, 0, n - 1)
        onehot = jax.nn.one_hot(b, n, dtype=jnp.float32) * claimed[..., None]
        return jnp.sum(onehot, axis=1) / float(pol.slots)

    @staticmethod
    def sync_utilization(
        table: d.IdleResourceTable, node_utils, amounts=None
    ) -> d.IdleResourceTable:
        return d.sync_utilization(table, node_utils, amounts)
