"""Algorithmic work of the engine's decode step, from shapes.

Counted the same whatever implements it: only live tokens' keys and values
at their stored precision, the queries, the outputs and any scales. Blocks a
kernel fetches past a sequence's end, padding and recomputation are not
work.
"""
from __future__ import annotations

from typing import NamedTuple


class Work(NamedTuple):
    flops: float
    bytes: float


def paged_attention(live_lengths, n_heads: int, kv_heads: int,
                    head_dim: int, kv_bytes: int = 4, q_bytes: int = 4,
                    scale_bytes: int = 0) -> Work:
    """One decode token per sequence over its ``live_lengths`` cached
    tokens. FLOPs: q·k and p·v, 2 multiply-adds each per head and token.
    Bytes: live K and V rows, q in, output out, ``scale_bytes`` per live
    page-scale read (int8 pools)."""
    tokens = float(sum(int(n) for n in live_lengths))
    seqs = sum(1 for n in live_lengths if int(n) > 0)
    flops = 4.0 * n_heads * head_dim * tokens
    kv = 2.0 * tokens * kv_heads * head_dim * kv_bytes
    qo = 2.0 * seqs * n_heads * head_dim * q_bytes
    return Work(flops, kv + qo + scale_bytes)


def engine_step(decoded: int, live_lengths, d_model: int, n_heads: int,
                kv_heads: int, head_dim: int, kv_bytes: int = 4,
                w_bytes: int = 4, x_bytes: int = 4) -> Work:
    """One engine step over ``decoded`` sequences: the q, k and v
    projections of each decoded token, the append of its K and V row, and
    paged attention over ``live_lengths``. Weights are read once a step."""
    kvd = kv_heads * head_dim
    proj_flops = 2.0 * decoded * d_model * (n_heads * head_dim + 2 * kvd)
    proj_bytes = (w_bytes * d_model * (n_heads * head_dim + 2 * kvd)
                  + x_bytes * decoded * d_model)
    append_bytes = 2.0 * decoded * kvd * kv_bytes
    att = paged_attention(live_lengths, n_heads, kv_heads, head_dim,
                          kv_bytes=kv_bytes)
    return Work(proj_flops + att.flops, proj_bytes + append_bytes + att.bytes)
