"""RWKV6 WKV recurrence (TPU Pallas): per-(batch, head) chunked scan with the
[K, V] state matrix resident in VMEM scratch across sequential chunk steps.

Each timestep is a rank-1 state update plus a [1,K]x[K,V] MXU matvec:
    out_t = r_t · (S + u ⊙ k_t v_tᵀ);   S <- diag(w_t) S + k_t v_tᵀ

Oracle: repro.kernels.ref.rwkv6_wkv.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0, :, 0, :].astype(jnp.float32)   # [chunk, K]
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)   # [chunk, V]
    w = w_ref[0, :, 0, :].astype(jnp.float32)
    u = u_ref[0, :].astype(jnp.float32)          # [K]

    def body(t, S):
        r_t = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)      # [1, K]
        k_t = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)
        v_t = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)      # [1, V]
        w_t = jax.lax.dynamic_slice_in_dim(w, t, 1, 0)
        kv = k_t.T @ v_t                                     # [K, V]
        out = r_t @ (S + u[:, None] * kv)                    # [1, V]
        o_ref[0, t, 0, :] = out[0].astype(o_ref.dtype)
        return w_t.T * S + kv

    S0 = s_scr[...].astype(jnp.float32)
    S = jax.lax.fori_loop(0, chunk, body, S0)
    s_scr[...] = S.astype(s_scr.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_wkv(r, k, v, w, u, chunk: int = 128, interpret: bool = False):
    """r,k,w: [B,T,H,K]; v: [B,T,H,V]; u: [H,K] -> [B,T,H,V]."""
    b, t, h, dk = r.shape
    dv = v.shape[-1]
    chunk = min(chunk, t)
    grid = (b, h, pl.cdiv(t, chunk))
    kernel = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, dk), lambda b_, h_, ic: (b_, ic, h_, 0)),
            pl.BlockSpec((1, chunk, 1, dk), lambda b_, h_, ic: (b_, ic, h_, 0)),
            pl.BlockSpec((1, chunk, 1, dv), lambda b_, h_, ic: (b_, ic, h_, 0)),
            pl.BlockSpec((1, chunk, 1, dk), lambda b_, h_, ic: (b_, ic, h_, 0)),
            pl.BlockSpec((1, dk), lambda b_, h_, ic: (h_, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, dv), lambda b_, h_, ic: (b_, ic, h_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, t, h, dv), r.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, w, u)
