"""Idle resource descriptors and the idle-resource table (paper §4.3).

Each node (SSD in the JBOF substrate, replica in the serving substrate)
publishes descriptors for resources it is willing to lend. The table lives in
"globally coherent memory": in the paper this is CXL G-FAM; here it is a
struct-of-arrays pytree that is either replicated SPMD state (serving) or a
plain simulator array (JBOF). All operations are pure functions so they are
jit/vmap/scan friendly and deterministic — determinism is what replaces the
paper's CAS atomicity in the SPMD setting (see DESIGN.md §3).

Descriptor layout (paper Fig. 7), one row per (node, slot):
  valid        bool     descriptor holds a lendable resource
  rtype        int8     PROCESSOR=0 | DRAM=1 | FLASH_BW=2 | LINK_BW=3
  borrower_id  int32    FREE (=0xFF) when unclaimed, else borrower node id
  amount_a     float32  PROCESSOR: borrower utilization | others: lendable amount
  amount_b     float32  PROCESSOR/FLASH_BW/LINK_BW: lender utilization
  info_a       int32    PROCESSOR: mapping-directory addr | DRAM: segment-list head
  info_b       int32    PROCESSOR: (borrowerCQ<<16 | shadowCQ) | DRAM: log-page addr

Resource types are *data*, not code forks: every rtype is described by a
`ResourceSpec` in `REGISTRY` — its claim-score weights and its sync rules.
`claim_score` and `sync_utilization` are generic over the registry, so
adding a harvestable resource is one `register()` call plus a
`manager.ResourcePolicy` entry (DESIGN.md §5); none of the publish/claim
machinery changes. What an *assisted op* of each rtype costs (dequeue/
unwrap events, CXL hops, link bytes — the paper's §4.6 numbers) lives in
the sibling table `repro.core.costs.OP_COSTS`, priced per operation so the
tax scales with I/O size (DESIGN.md §8).

DRAM descriptors flow through this table in BOTH substrates: the JBOF sim
publishes MRC-spare mapping-cache segments and grants them through claim
sweeps (amount_a = lendable segments, DESIGN.md §6), while the serving
engine publishes free KV pages as amount-gated capacity that lenders pull
directly. Locate a policy's slots via `manager.ResourceManager.slot_mask`,
never hardcoded indices.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

PROCESSOR = 0   # compute-end clocks (§4.4)
DRAM = 1        # mapping-cache segments / KV pages (§4.5)
FLASH_BW = 2    # data-end (flash backbone) channel time (§3 disaggregation)
LINK_BW = 3     # CXL link bytes (inter-SSD assist traffic budget)
FREE = 0xFF  # borrower_id sentinel: not borrowed


class ResourceSpec(NamedTuple):
    """Per-rtype policy *data* consumed by the generic descriptor machinery.

    ``score_a``/``score_b``: claim score = score_a * amount_a + score_b *
    amount_b — the borrower claims the highest-scoring descriptor. PROCESSOR
    prefers the most-idle lender (score_b = -1); capacity-style resources
    prefer the largest published amount (score_a = +1).

    ``sync_a``: how the periodic sync refreshes ``amount_a``:
      "borrower_util"  claimant's utilization (PROCESSOR)
      "amount"         lender's current lendable amount (capacity types)
      "none"           untouched
    ``sync_b``: how it refreshes ``amount_b``: "lender_util" | "none".
    """

    rtype: int
    name: str
    score_a: float = 0.0
    score_b: float = 0.0
    sync_a: str = "none"
    sync_b: str = "none"


REGISTRY: dict[int, ResourceSpec] = {}


def register(spec: ResourceSpec) -> ResourceSpec:
    """Register (or redefine) a resource type. Returns the spec."""
    if not 0 <= spec.rtype < 127:
        raise ValueError(f"rtype must fit int8, got {spec.rtype}")
    if spec.sync_a not in ("borrower_util", "amount", "none"):
        raise ValueError(f"bad sync_a {spec.sync_a!r}")
    if spec.sync_b not in ("lender_util", "none"):
        raise ValueError(f"bad sync_b {spec.sync_b!r}")
    REGISTRY[spec.rtype] = spec
    return spec


register(ResourceSpec(PROCESSOR, "processor",
                      score_b=-1.0, sync_a="borrower_util", sync_b="lender_util"))
register(ResourceSpec(DRAM, "dram", score_a=1.0, sync_a="amount"))
register(ResourceSpec(FLASH_BW, "flash_bw",
                      score_a=1.0, sync_a="amount", sync_b="lender_util"))
register(ResourceSpec(LINK_BW, "link_bw",
                      score_a=1.0, sync_a="amount", sync_b="lender_util"))


def spec_of(rtype: int) -> ResourceSpec:
    return REGISTRY[int(rtype)]


class IdleResourceTable(NamedTuple):
    """Struct-of-arrays descriptor table, shape [n_nodes, n_slots]."""

    valid: jax.Array        # bool   [N, S]
    rtype: jax.Array        # int8   [N, S]
    borrower_id: jax.Array  # int32  [N, S]
    amount_a: jax.Array     # float32[N, S]
    amount_b: jax.Array     # float32[N, S]
    info_a: jax.Array       # int32  [N, S]
    info_b: jax.Array       # int32  [N, S]

    @property
    def n_nodes(self) -> int:
        return self.valid.shape[0]

    @property
    def n_slots(self) -> int:
        return self.valid.shape[1]


def make_table(n_nodes: int, n_slots: int = 2) -> IdleResourceTable:
    """Fresh table: all descriptors invalid / unclaimed."""
    shape = (n_nodes, n_slots)
    return IdleResourceTable(
        valid=jnp.zeros(shape, jnp.bool_),
        rtype=jnp.zeros(shape, jnp.int8),
        borrower_id=jnp.full(shape, FREE, jnp.int32),
        amount_a=jnp.zeros(shape, jnp.float32),
        amount_b=jnp.zeros(shape, jnp.float32),
        info_a=jnp.zeros(shape, jnp.int32),
        info_b=jnp.zeros(shape, jnp.int32),
    )


def publish(
    table: IdleResourceTable,
    node_id: jax.Array | int,
    slot: jax.Array | int,
    rtype: jax.Array | int,
    amount_a: jax.Array | float,
    amount_b: jax.Array | float = 0.0,
    info_a: jax.Array | int = 0,
    info_b: jax.Array | int = 0,
) -> IdleResourceTable:
    """Lender announces an idle resource (paper workflow step 2)."""
    idx = (node_id, slot)
    return table._replace(
        valid=table.valid.at[idx].set(True),
        rtype=table.rtype.at[idx].set(jnp.int8(rtype)),
        borrower_id=table.borrower_id.at[idx].set(FREE),
        amount_a=table.amount_a.at[idx].set(jnp.float32(amount_a)),
        amount_b=table.amount_b.at[idx].set(jnp.float32(amount_b)),
        info_a=table.info_a.at[idx].set(jnp.int32(info_a)),
        info_b=table.info_b.at[idx].set(jnp.int32(info_b)),
    )


def withdraw(
    table: IdleResourceTable, node_id: jax.Array | int, slot: jax.Array | int
) -> IdleResourceTable:
    """Lender stops lending: tag the descriptor invalid (paper §4.3)."""
    return table._replace(valid=table.valid.at[node_id, slot].set(False))


def release(table: IdleResourceTable, borrower_id: jax.Array | int) -> IdleResourceTable:
    """Borrower ends harvesting: reset its claims to FREE (paper §4.3)."""
    mine = table.borrower_id == jnp.int32(borrower_id)
    return table._replace(
        borrower_id=jnp.where(mine, jnp.int32(FREE), table.borrower_id)
    )


def claim_score(
    table: IdleResourceTable, rtype: int
) -> tuple[jax.Array, jax.Array]:
    """``(offers, score)``, bool/float32[N, S]: the valid descriptors of
    ``rtype``, and each one's claim score by the rtype's registered weights
    (`ResourceSpec`; -inf elsewhere). PROCESSOR prefers the lowest lender
    utilization (amount_b), capacity types (DRAM, FLASH_BW, LINK_BW,
    custom) the highest lendable amount_a. A claim moves only
    ``borrower_id``, so both hold through every claim of a round."""
    spec = spec_of(rtype)
    offers = table.valid & (table.rtype == jnp.int8(rtype))
    score = jnp.where(
        offers, spec.score_a * table.amount_a + spec.score_b * table.amount_b,
        -jnp.inf)
    return offers, score


def claim_one(
    offers: jax.Array,
    score: jax.Array,
    borrower_ids: jax.Array,
    borrower: jax.Array | int,
    gate: jax.Array | bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The claim rule: if ``gate`` holds, ``borrower`` takes the
    highest-scoring unclaimed offer that is not its own, ties broken by
    lowest flat index. The write is a compare against an iota, not a
    scatter, so a sweep of claims stays elementwise on the device.

    Returns ``(borrower_ids', flat, success)``: the claimed flat index is
    meaningful only where ``success`` (any claimable offer)."""
    n, s = offers.shape
    borrower = jnp.asarray(borrower, jnp.int32)
    node_ids = jnp.arange(n, dtype=jnp.int32)[:, None]
    mask = offers & (borrower_ids == FREE) & (node_ids != borrower)
    flat = jnp.argmax(jnp.where(mask, score, -jnp.inf).reshape(-1))
    success = jnp.any(mask)
    hit = (jnp.arange(n * s, dtype=jnp.int32).reshape(n, s) == flat) \
        & success & gate
    return jnp.where(hit, borrower, borrower_ids), flat, success


def claim_best(
    table: IdleResourceTable,
    borrower_id: jax.Array | int,
    rtype: int,
) -> tuple[IdleResourceTable, jax.Array, jax.Array, jax.Array]:
    """Borrower atomically claims the best matching descriptor (workflow 3)
    by `claim_one`, scored by `claim_score`.

    Returns (table', lender_id, slot, success). Under SPMD every replica
    computes the same argmax on the same replicated table, so the claim is
    race-free by determinism (ties broken by lowest flat index — stable).
    """
    offers, score = claim_score(table, rtype)
    bids, flat, success = claim_one(
        offers, score, table.borrower_id, borrower_id)
    lender = jnp.where(success, flat // table.n_slots, -1).astype(jnp.int32)
    slot = jnp.where(success, flat % table.n_slots, -1).astype(jnp.int32)
    return table._replace(borrower_id=bids), lender, slot, success


def sync_utilization(
    table: IdleResourceTable,
    node_utils: jax.Array | dict | None = None,
    amounts: dict | None = None,
) -> IdleResourceTable:
    """Periodic (10 ms in the paper; per-step here) descriptor refresh,
    per-rtype via the registry.

    ``node_utils``: float32[N] (shorthand for ``{PROCESSOR: utils}``) or a
    dict ``{rtype: float32[N]}`` of each resource's current utilization.
    ``amounts``: dict ``{rtype: float32[N]}`` of each node's current
    lendable amount for capacity-style resources.

    For every registered rtype the spec's sync rules apply:
      sync_b == "lender_util":   amount_b tracks the descriptor owner's util
      sync_a == "borrower_util": amount_a tracks the claimant's util
      sync_a == "amount":        amount_a tracks the current lendable amount
                                 (so grants never leave it stale)
    """
    n, s = table.valid.shape
    if node_utils is None:
        utils: dict = {}
    elif isinstance(node_utils, dict):
        utils = node_utils
    else:
        utils = {PROCESSOR: node_utils}
    amounts = amounts or {}

    amount_a, amount_b = table.amount_a, table.amount_b
    claimed = table.borrower_id != FREE
    safe_bid = jnp.clip(table.borrower_id, 0, n - 1)
    for rtype in sorted(REGISTRY):
        spec = REGISTRY[rtype]
        is_r = table.rtype == jnp.int8(rtype)
        u = utils.get(rtype)
        if u is not None:
            u = jnp.asarray(u, jnp.float32)
            if spec.sync_b == "lender_util":
                lender_u = jnp.broadcast_to(u[:, None], (n, s))
                amount_b = jnp.where(is_r & table.valid, lender_u, amount_b)
            if spec.sync_a == "borrower_util":
                amount_a = jnp.where(
                    is_r & table.valid & claimed, u[safe_bid], amount_a)
        amt = amounts.get(rtype)
        if amt is not None and spec.sync_a == "amount":
            cur = jnp.broadcast_to(
                jnp.asarray(amt, jnp.float32)[:, None], (n, s))
            amount_a = jnp.where(is_r & table.valid, cur, amount_a)
    return table._replace(amount_a=amount_a, amount_b=amount_b)


def lenders_of(table: IdleResourceTable, borrower_id: jax.Array | int, rtype: int) -> jax.Array:
    """bool[N] — which nodes currently lend ``rtype`` to ``borrower_id``."""
    m = (
        table.valid
        & (table.borrower_id == jnp.int32(borrower_id))
        & (table.rtype == jnp.int8(rtype))
    )
    return jnp.any(m, axis=1)
