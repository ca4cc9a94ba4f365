"""Public kernel entry points (the jit'd wrappers).

Dispatch policy: the Pallas TPU kernels engage on TPU backends (or when
REPRO_FORCE_PALLAS=1 requests interpret-mode execution, used by the kernel
tests); everywhere else — CPU smoke tests and the 512-host-device dry-run —
the jnp oracle executes, which also keeps `cost_analysis()` clean for the
dry-run.
"""
from __future__ import annotations

import os
import jax
import jax.numpy as jnp

from ..obs import export as obs_x
from . import ref


def _use_pallas() -> bool:
    if os.environ.get("REPRO_FORCE_PALLAS") == "1":
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------- attention
def attention(q, k, v, causal: bool = True, window: int = 0, scale=None):
    if _use_pallas() and q.shape[1] >= 128 and q.shape[-1] % 128 == 0:
        from .flash_attention import flash_attention
        return flash_attention(
            q, k, v, causal=causal, window=window, interpret=_interpret()
        )
    return ref.attention(q, k, v, causal=causal, window=window, scale=scale)


def decode_attention(q, k, v, valid):
    return ref.decode_attention(q, k, v, valid)


def paged_attention(q, k_pool, v_pool, page_table, lengths,
                    k_scale=None, v_scale=None):
    """Pass `k_scale`/`v_scale` ([P] f32) when the pool holds int8 codes —
    the kernel dequantizes in VMEM right before the dot (ref path folds the
    scales into scores/weights); omit them for fp pools."""
    with obs_x.scope("paged_attention"):
        if _use_pallas() and q.shape[-1] % 128 == 0:
            from .paged_attention import paged_attention as pa
            return pa(q, k_pool, v_pool, page_table, lengths,
                      k_scale=k_scale, v_scale=v_scale, interpret=_interpret())
        if k_scale is not None:
            return ref.paged_attention_quant(
                q, k_pool, v_pool, k_scale, v_scale, page_table, lengths)
        return ref.paged_attention(q, k_pool, v_pool, page_table, lengths)


# ------------------------------------------------------------ ftl lookup
def ftl_lookup(lpns, directory, mapping_cache, entries_per_segment: int):
    if _use_pallas():
        from .ftl_lookup import ftl_lookup as fk
        return fk(lpns, directory, mapping_cache, entries_per_segment,
                  interpret=_interpret())
    return ref.ftl_lookup(lpns, directory, mapping_cache, entries_per_segment)


# ------------------------------------------------------------ moe router
def topk_router(scores, k: int, bias=None):
    if _use_pallas() and scores.shape[-1] >= 128:
        from .moe_router import topk_router as tk
        return tk(scores, k, bias=bias, interpret=_interpret())
    return ref.topk_router(scores, k, bias=bias)


# ------------------------------------------------------------ recurrences
def rwkv6_wkv(r, k, v, w, u):
    if _use_pallas() and r.shape[1] % 128 == 0:
        from .rwkv6_scan import rwkv6_wkv as wkv
        return wkv(r, k, v, w, u, interpret=_interpret())
    return ref.rwkv6_wkv(r, k, v, w, u)


rwkv6_wkv_step = ref.rwkv6_wkv_step


def rglru(x, a, h0=None):
    if _use_pallas() and x.shape[1] % 128 == 0 and h0 is None:
        from .rglru_scan import rglru as rg
        return rg(x, a, interpret=_interpret())
    return ref.rglru(x, a, h0=h0)


rglru_step = ref.rglru_step
