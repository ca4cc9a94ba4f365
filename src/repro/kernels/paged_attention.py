"""Paged decode attention (TPU Pallas) — the XBOF data path on a TPU.

One decode token attends over a paged KV cache: the page table (logical
sequence position -> physical page id) is the FTL mapping table of the
paper, and pages may physically live in a *peer replica's* pool segment
(XBOF DRAM harvesting) — the kernel is oblivious, exactly as the paper's
data-end is oblivious to which compute-end drives it.

Schedule: grid (B, n_blocks) with the page table as a PREFETCHED SCALAR
(PrefetchScalarGridSpec), so the K/V BlockSpec index maps chase page-table
pointers ahead of the compute — the TPU-native version of "metadata lookup
then flash read". Online softmax over page blocks in VMEM scratch.

Lane alignment: the score tile is [kv, group, tokens] and the TPU vector
lane dimension is 128 wide, so a single KV page of 8–16 tokens would leave
the lane dim 8–16x padded. At production head sizes (head_dim % 128 == 0,
where the K/V tiles themselves are lane-aligned) each grid step therefore
fetches `block_pages` = 128/page pages — one lane-filling 128-token span —
through that many independently prefetched K/V blocks (pages are scattered
in the pool; one block cannot span them). The page table pads to a multiple
of the block size with -1 (unmapped) columns, masked like any other hole.

Oracle: repro.kernels.ref.paged_attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANE = 128  # TPU vector register lane width


def block_pages(page: int, head_dim: int) -> int:
    """KV pages fetched per grid step. Lane-filling (128 tokens) when the
    head size keeps the K/V tiles aligned anyway and pages tile the span
    evenly; otherwise one page per step (the pre-alignment schedule)."""
    if head_dim % LANE == 0 and LANE % page == 0:
        return LANE // page
    return 1


def _kernel(*args, page: int, group: int, bp: int, quant: bool):
    if quant:
        # int8 mode: per-page dequant scales ride the scalar prefetch
        # right behind the page table (same SMEM residency).
        table_ref, lengths_ref, k_scale_ref, v_scale_ref, q_ref, *refs = args
    else:
        table_ref, lengths_ref, q_ref, *refs = args
    k_refs = refs[:bp]                          # bp x [1, page, KV, D]
    v_refs = refs[bp:2 * bp]
    o_ref = refs[2 * bp]
    m_scr, l_scr, acc_scr = refs[2 * bp + 1:]
    b = pl.program_id(0)
    ib = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(ib == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                # [H, D]
    if quant:
        # fused dequant: only int8 codes crossed HBM/fabric into VMEM;
        # scale-up happens here, right before the dot — MXU math stays f32
        pid = [jnp.maximum(table_ref[b, ib * bp + j], 0) for j in range(bp)]
        k = jnp.concatenate(
            [k_refs[j][0].astype(jnp.float32) * k_scale_ref[pid[j]]
             for j in range(bp)], axis=0)        # [span, KV, D]
        v = jnp.concatenate(
            [v_refs[j][0].astype(jnp.float32) * v_scale_ref[pid[j]]
             for j in range(bp)], axis=0)
    else:
        k = jnp.concatenate([r[0] for r in k_refs], axis=0)  # [span, KV, D]
        v = jnp.concatenate([r[0] for r in v_refs], axis=0)
    h, d = q.shape
    kv = k.shape[1]
    span = bp * page

    qg = q.reshape(kv, group, d)
    s = jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32,
    ) * (d ** -0.5)                             # [kv, group, span]

    # validity: slot index within the sequence length, and the sub-page's
    # table entry mapped (>= 0) — padding columns and pool holes mask out
    col = jax.lax.broadcasted_iota(jnp.int32, (kv, group, span), 2)
    valid = ib * span + col < lengths_ref[b]
    # per-sub-page mask from scalar compares against an iota: Mosaic has
    # no layout for a stacked [bp] bool vector broadcast to the span
    for j in range(bp):
        valid &= (col // page != j) | (table_ref[b, ib * bp + j] >= 0)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                         # [kv, group]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[..., None])           # [kv, group, span]
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=2)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32,
    )                                           # [kv, group, D]
    acc_scr[...] = acc_scr[...] * alpha[..., None] + pv
    m_scr[...] = m_cur

    @pl.when(ib == nb - 1)
    def _done():
        denom = jnp.maximum(l_scr[...], 1e-30)
        out = (acc_scr[...] / denom[..., None])
        o_ref[0] = out.reshape(h, d).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("interpret", "pages_per_block"))
def paged_attention(
    q: jax.Array,            # [B, H, D]
    k_pool: jax.Array,       # [P, page, KV, D]
    v_pool: jax.Array,
    page_table: jax.Array,   # [B, max_pages] int32 (-1 = unmapped)
    lengths: jax.Array,      # [B] int32
    k_scale: jax.Array | None = None,   # [P] f32 — int8 pool dequant scales
    v_scale: jax.Array | None = None,
    interpret: bool = False,
    pages_per_block: int | None = None,
) -> jax.Array:
    b, h, d = q.shape
    p_total, page, kv, _ = k_pool.shape
    mp = page_table.shape[1]
    group = h // kv
    bp = block_pages(page, d) if pages_per_block is None else pages_per_block
    quant = k_scale is not None

    mp_pad = -(-mp // bp) * bp
    if mp_pad != mp:
        page_table = jnp.concatenate(
            [page_table,
             jnp.full((b, mp_pad - mp), -1, page_table.dtype)], axis=1)

    # index maps take the scalar-prefetch refs as trailing args; the page
    # table is always the first of them, whatever else (scales) rides along
    def kv_spec(j):
        return pl.BlockSpec(
            (1, page, kv, d),
            lambda b_, ib, *s, j=j: (
                jnp.maximum(s[0][b_, ib * bp + j], 0), 0, 0, 0),
        )

    kernel = functools.partial(
        _kernel, page=page, group=group, bp=bp, quant=quant)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if quant else 2,
        grid=(b, mp_pad // bp),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda b_, ib, *s: (b_, 0, 0)),
            *[kv_spec(j) for j in range(bp)],
            *[kv_spec(j) for j in range(bp)],
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda b_, ib, *s: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, group), jnp.float32),
            pltpu.VMEM((kv, group), jnp.float32),
            pltpu.VMEM((kv, group, d), jnp.float32),
        ],
    )
    scalars = (page_table, lengths)
    if quant:
        scalars += (k_scale.astype(jnp.float32), v_scale.astype(jnp.float32))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(*scalars, q, *([k_pool] * bp), *([v_pool] * bp))
