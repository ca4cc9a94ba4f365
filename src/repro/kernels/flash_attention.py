"""Flash attention (TPU Pallas): tiled online-softmax causal GQA attention
with optional sliding window.

Tiling: grid (B, H, n_q_blocks, n_k_blocks); the k-axis is the innermost
(sequential) grid dimension, with running max / denominator / accumulator in
VMEM scratch — the classic TPU flash schedule. Q/K/V tiles are VMEM-resident
[block, head_dim] slabs; head_dim is expected MXU-aligned (128 multiples).
The wrapper transposes [B, S, H, D] to head-major [B, H, S, D] so each
block's two minor dims are (block, head_dim): a TPU block's last two dims
must tile (8, 128) or span the array, which a (1, head_dim) slice of the
head axis does not. GQA is handled in the K/V index maps (kv_head = q_head
// group) so K/V tiles are fetched once per kv head, not per q head.

Oracle: repro.kernels.ref.attention (tests sweep shapes/dtypes/causal/window).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            seq_q: int, seq_k: int):
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...]                             # [bq, d]
    k = k_ref[...]                             # [bk, d]
    v = v_ref[...]                             # [bk, d]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                   # [bq, bk]

    iq = pl.program_id(2)
    rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (seq_k - seq_q)                       # global key-pos of each q row
    cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= cols <= rows
    if window:
        mask &= cols > rows - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_cur

    @pl.when(ik == nk - 1)
    def _done():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jax.Array,            # [B, S, H, D]
    k: jax.Array,            # [B, T, KV, D]
    v: jax.Array,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, s, h, d = q.shape
    t, kv = k.shape[1], k.shape[2]
    group = h // kv
    bq = min(block_q, s)
    bk = min(block_k, t)
    grid = (b, h, pl.cdiv(s, bq), pl.cdiv(t, bk))
    scale = d ** -0.5

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, seq_q=s, seq_k=t,
    )
    q_spec = pl.BlockSpec((None, None, bq, d),
                          lambda b_, h_, iq, ik: (b_, h_, iq, 0))
    kv_spec = pl.BlockSpec((None, None, bk, d),
                           lambda b_, h_, iq, ik: (b_, h_ // group, ik, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max
            pltpu.VMEM((bq,), jnp.float32),      # running denominator
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)
