"""Topology plane: ONE hierarchical exchange for node → enclosure → fabric.

Both substrates grow the same shape when they scale out: full descriptor
machinery inside a local pool, then aggregate (spare, want) summaries that
settle level by level — pool ↔ pool inside an enclosure, enclosure ↔
enclosure across the JBOF fabric, and so on. Before this module each
substrate hand-rolled its own copy (the serving engine's two-level
`shard_exchange` round, the sim's flat global round); now there is one
`Topology` spec and one `hierarchical_exchange` both route through
(DESIGN.md §11).

The exchange is *nearest-level-first*: level 1 settles each innermost
group internally (the cheap boundary), only the unmet residuals spill to
level 2, and so on outward — "claims prefer the nearest level and spill
outward only when the local pool is dry". Every level's grants are priced
at that level's hop tax (`core.costs.LEVEL_EXTRA_HOPS` tier table), so a
cross-fabric unit is strictly more expensive than an enclosure-local one
and the caller can debit each tier's command bytes on its unified byte
account separately.

Like everything in `core`, the machinery is deterministic pure math on
replicated summaries: every participant computes the identical per-level
grant matrices from the same gathered (spare, want) vectors — determinism
replacing CAS at every level of the tree, exactly as it does inside one
pool (DESIGN.md §3).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..obs import export as obs_x
from . import manager as mgr

# canonical level names, innermost boundary first: index 0 crosses between
# node-local pools of one enclosure, index 1 between enclosures of one
# fabric. Deeper topologies keep appending fabric stages.
LEVEL_NAMES = ("node", "enclosure", "fabric")


class Topology(NamedTuple):
    """Spec of the exchange tree above the leaves.

    ``group_sizes``: members per group at each exchange level, innermost
    first. ``group_sizes=(g1, g2)`` over N leaves means: level 1 settles
    within each block of g1 leaves, level 2 settles the residuals within
    each block of g1*g2 leaves; prod(group_sizes) must equal N. The
    serving engine's PR 6 flat exchange is ``group_sizes=(n_shards,)`` —
    depth 2 (local round + one exchange level).

    ``tiers``: the `costs.LEVEL_EXTRA_HOPS` tier index each exchange level
    prices at (same length as group_sizes). The leaf-local round is always
    tier 0; the first exchange level defaults to tier 1, the next to
    tier 2, ... — matching LEVEL_NAMES.
    """

    group_sizes: tuple[int, ...]
    tiers: tuple[int, ...] = ()

    @property
    def depth(self) -> int:
        """Levels including the leaf-local round (PR 6 engine == 2)."""
        return 1 + len(self.group_sizes)

    @property
    def n_leaves(self) -> int:
        return math.prod(self.group_sizes)

    def level_tier(self, level: int) -> int:
        """Price tier of exchange level ``level`` (0-based)."""
        if self.tiers:
            return self.tiers[level]
        return level + 1

    def level_name(self, level: int) -> str:
        t = self.level_tier(level)
        return (LEVEL_NAMES[t] if t < len(LEVEL_NAMES)
                else f"fabric+{t - len(LEVEL_NAMES) + 1}")

    def validate(self, n: int) -> "Topology":
        if not self.group_sizes:
            raise ValueError("Topology needs at least one exchange level")
        if any(g < 1 for g in self.group_sizes):
            raise ValueError(f"group sizes must be >= 1: {self.group_sizes}")
        if self.n_leaves != n:
            raise ValueError(
                f"topology covers {self.n_leaves} leaves "
                f"(group_sizes={self.group_sizes}) but got {n}")
        if self.tiers and len(self.tiers) != len(self.group_sizes):
            raise ValueError(
                f"tiers {self.tiers} must match group_sizes "
                f"{self.group_sizes} in length")
        return self


def flat(n: int) -> Topology:
    """The PR 6 engine shape: one exchange level over all n leaves."""
    return Topology(group_sizes=(n,))


def two_level(inner: int, outer: int) -> Topology:
    """node → enclosure → fabric: settle within enclosures of ``inner``
    leaves first, then across ``outer`` enclosures."""
    return Topology(group_sizes=(inner, outer))


def _block_exchange(spare, want, overhead, block: int):
    """One exchange level at leaf resolution: settle within each
    contiguous block of ``block`` leaves. Returns (grants[N, N] block-
    diagonal, received[N]). A single all-covering block calls
    `manager.shard_exchange` directly — bitwise the PR 6 primitive."""
    n = spare.shape[0]
    g = n // block
    if g == 1:
        return mgr.shard_exchange(spare, want, overhead)
    gr, rc = jax.vmap(
        lambda s, w: mgr.shard_exchange(s, w, overhead)
    )(spare.reshape(g, block), want.reshape(g, block))
    idx = jnp.arange(g)
    full = jnp.zeros((g, block, g, block), gr.dtype)
    full = full.at[idx, :, idx, :].set(gr)
    return full.reshape(n, n), rc.reshape(n)


def hierarchical_exchange(
    spare: jax.Array,
    want: jax.Array,
    topo: Topology,
    overheads: tuple | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Settle per-leaf (spare, want) summaries level by level, nearest
    level first.

    ``spare`` / ``want``: float32[N] post-local-round leftovers per leaf —
    exactly what `manager.shard_exchange` takes, but per leaf of an
    arbitrary tree instead of per shard of one flat pool.
    ``overheads``: per-level fractional hop taxes, len == len(topo.
    group_sizes) (a borrower draws 1 + overhead units of lender surplus
    per unit received at that level). Defaults to zero at every level —
    callers that debit hop costs on a byte account instead pass zeros and
    price each level's grants from the returned per-level matrices.

    Returns ``(grants, received)``: ``grants`` float32[L, N, N] per-LEVEL
    grant matrices (level l is block-diagonal at that level's group span)
    and ``received`` float32[L, N] per-level usable units at each leaf.
    Sum over the level axis for totals; keep it to debit each level at its
    own tier price.

    Invariants, per level and in aggregate (pinned by the conservation
    suite): Σ_b grants[l][a, b] ≤ residual spare of a entering level l;
    received bounded by residual want; and a leaf never both lends and
    borrows — netting inside `shard_exchange` zeroes one side at the first
    level, and each later level only sees the shrunken residuals, so
    lending at one level and borrowing through another is impossible by
    construction.
    """
    spare = jnp.asarray(spare, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    n = spare.shape[0]
    topo.validate(n)
    if overheads is None:
        overheads = (0.0,) * len(topo.group_sizes)
    if len(overheads) != len(topo.group_sizes):
        raise ValueError(
            f"need one overhead per level: got {len(overheads)} for "
            f"{len(topo.group_sizes)} levels")
    grants_l, recv_l = [], []
    sp, wt = spare, want
    block = 1
    for lv, (gsize, oh) in enumerate(zip(topo.group_sizes, overheads)):
        block *= gsize
        with obs_x.scope("hier_exchange", topo.level_name(lv)):
            gr, rc = _block_exchange(sp, wt, oh, block)
        grants_l.append(gr)
        recv_l.append(rc)
        # residuals for the next (outer, pricier) level: netting first —
        # a leaf's own want is served by its own spare before either side
        # crosses any boundary — then subtract what this level moved
        lent = jnp.sum(gr, axis=1)
        sp, wt = (jnp.maximum(jnp.maximum(sp - wt, 0.0) - lent, 0.0),
                  jnp.maximum(jnp.maximum(wt - sp, 0.0) - rc, 0.0))
    return jnp.stack(grants_l), jnp.stack(recv_l)


class RoundResult(NamedTuple):
    """What `hierarchical_round` hands back to a substrate."""

    tables: object           # leaf-local tables after the local rounds
    grants: jax.Array        # [L, N, N] per-level exchange grants
    received: jax.Array      # [L, N] per-level usable units per leaf
    lent: jax.Array          # [N] total units drawn from each leaf
    spare_resid: jax.Array   # [N] spare left after every level settled
    want_resid: jax.Array    # [N] want left after every level settled


def hierarchical_round(
    manager: mgr.ResourceManager,
    tables,
    inputs,
    spare: jax.Array,
    want: jax.Array,
    topo: Topology,
    overheads: tuple | None = None,
) -> RoundResult:
    """Full local `ResourceManager.round()` at every leaf, then the
    recursive per-level settlement of the (spare, want) leftovers.

    ``tables``: the leaves' descriptor tables stacked on a leading [N]
    axis (each leaf's table covers its own pool); ``inputs``: the per-
    rtype `RoundInputs`, leading [N] axis on every array. The local round
    runs vmapped over leaves — the same `manager.round` the flat
    substrates run, untouched. ``spare``/``want`` are the post-local
    leftovers the caller derives from its own accounting (each substrate
    knows its own units); they settle through `hierarchical_exchange`.

    Substrates running under a collective axis (the serving engine's
    shard_map) gather their summaries themselves and call
    `hierarchical_exchange` directly — the leaf round there IS the
    surrounding shard-local step. This wrapper is the single-controller
    form the sim uses, and the reference shape for both.
    """
    new_tables = jax.vmap(manager.round)(tables, inputs)
    grants, received = hierarchical_exchange(spare, want, topo, overheads)
    lent = jnp.sum(grants, axis=(0, 2))
    spare_net = jnp.maximum(spare - want, 0.0)
    want_net = jnp.maximum(want - spare, 0.0)
    return RoundResult(
        tables=new_tables,
        grants=grants,
        received=received,
        lent=lent,
        spare_resid=jnp.maximum(spare_net - lent, 0.0),
        want_resid=jnp.maximum(want_net - jnp.sum(received, axis=0), 0.0),
    )


def invalidate_block_grants(
    grants: jax.Array, dead: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """A leaf dropping off the fabric invalidates exactly its block's
    standing cross-level grants — the §4.3 descriptor-invalidation story
    one level up the tree.

    ``grants``: [L, N, N] per-level lender×borrower amounts (the shape
    `hierarchical_exchange` emits); ``dead``: bool[N]. Every grant a
    dead leaf lends (its rows) or borrows (its columns) zeroes at every
    level; grants strictly between surviving leaves are untouched
    bitwise. Returns ``(grants, released)`` with ``released`` the total
    units invalidated (f32 scalar) — zero when re-applied to an
    already-drained block, so the tally ticks only on the transition.
    """
    dead = jnp.asarray(dead, bool)
    kill = dead[None, :, None] | dead[None, None, :]
    released = jnp.sum(jnp.where(kill, grants, 0.0))
    return jnp.where(kill, 0.0, grants), released
