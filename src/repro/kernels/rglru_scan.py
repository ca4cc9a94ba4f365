"""RG-LRU linear recurrence (TPU Pallas): chunked sequential scan.

Grid (B, n_chunks) with the chunk axis sequential; the recurrent state h
persists in VMEM scratch across chunk steps. Within a chunk the recurrence
h_t = a_t*h + sqrt(1-a_t^2)*x_t runs as a fori over [W]-vector VPU ops —
the chunk size just amortizes HBM->VMEM tile traffic.

Oracle: repro.kernels.ref.rglru.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, a_ref, o_ref, h_scr, *, chunk: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)   # [chunk, W]
    a = a_ref[0].astype(jnp.float32)

    def body(t, carry):
        h = carry
        a_t = jax.lax.dynamic_slice_in_dim(a, t, 1, 0)[0]
        x_t = jax.lax.dynamic_slice_in_dim(x, t, 1, 0)[0]
        h = a_t * h + jnp.sqrt(jnp.clip(1.0 - a_t * a_t, 0.0)) * x_t
        h_scr[t, :] = h.astype(h_scr.dtype)
        return h

    h0 = h_scr[chunk, :].astype(jnp.float32)  # carry row
    h_last = jax.lax.fori_loop(0, chunk, body, h0)
    h_scr[chunk, :] = h_last.astype(h_scr.dtype)
    o_ref[0] = h_scr[:chunk, :].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rglru(x: jax.Array, a: jax.Array, chunk: int = 128, interpret: bool = False):
    """x, a: [B, T, W] -> (outputs [B, T, W], final state [B, W])."""
    b, t, w = x.shape
    chunk = min(chunk, t)
    grid = (b, pl.cdiv(t, chunk))
    kernel = functools.partial(_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, w), lambda b_, ic: (b_, ic, 0)),
            pl.BlockSpec((1, chunk, w), lambda b_, ic: (b_, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, w), lambda b_, ic: (b_, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((b, t, w), x.dtype),
        scratch_shapes=[pltpu.VMEM((chunk + 1, w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, a)
    return out, out[:, -1]
