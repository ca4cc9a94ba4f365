"""Kernel microbenchmarks: Pallas vs jnp oracle. On a TPU the Pallas rows
time the compiled kernels; elsewhere they run the Pallas interpreter
(correctness timing only — real perf is a TPU measurement).

Covers the fp32 AND int8 (fused-dequant) paged-attention variants: the
int8 path moves 1/4 the K/V bytes per page and must stay within rel-err
5e-2 of the fp32 oracle — the deterministic half of that claim (the error
bound and the page-byte ratio) gates through check_regression.py; the
timings are wall-clock (tracked, never gated).

    PYTHONPATH=src python benchmarks/kernels_micro.py [--quick]
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention as paged_pallas

try:
    from ._util import bench_json, emit, timed
except ImportError:  # direct invocation: python benchmarks/kernels_micro.py
    from _util import bench_json, emit, timed


def main(quick: bool = False):
    key = jax.random.key(0)
    b, s, h, kv, d = 1, 256, 4, 2, 128
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    results = []

    jit_ref = jax.jit(lambda q, k, v: ref.attention(q, k, v))
    t = timed(jit_ref, q, k, v)
    emit("kernel_attn_ref_jnp", f"{t:.0f}", "us")
    results.append({"kernel": "attention_ref", "us_wall": round(t)})

    # ---- paged attention: fp32 ref / int8 ref / pallas-interpret variants
    pool, page, mp = 16, 8, 6
    kp = jax.random.normal(ks[1], (pool, page, kv, d))
    vp = jax.random.normal(ks[2], (pool, page, kv, d))
    pt = jnp.array([[3, 1, 7, 2, -1, -1]], jnp.int32)
    lens = jnp.array([27], jnp.int32)
    qd = jax.random.normal(ks[0], (1, h, d))
    jit_paged = jax.jit(lambda *a: ref.paged_attention(*a))
    t = timed(jit_paged, qd, kp, vp, pt, lens)
    emit("kernel_paged_ref_jnp", f"{t:.0f}", "us")
    results.append({"kernel": "paged_ref_fp32", "us_wall": round(t)})

    # int8 codes + per-page scales (running max-abs convention)
    k_s = jnp.max(jnp.abs(kp), axis=(1, 2, 3)) / 127.0
    v_s = jnp.max(jnp.abs(vp), axis=(1, 2, 3)) / 127.0
    kq = jnp.clip(jnp.round(kp / k_s[:, None, None, None]),
                  -127, 127).astype(jnp.int8)
    vq = jnp.clip(jnp.round(vp / v_s[:, None, None, None]),
                  -127, 127).astype(jnp.int8)
    jit_paged_q = jax.jit(lambda *a: ref.paged_attention_quant(*a))
    t = timed(jit_paged_q, qd, kq, vq, k_s, v_s, pt, lens)
    emit("kernel_paged_ref_int8", f"{t:.0f}", "us (fused-dequant oracle)")
    results.append({"kernel": "paged_ref_int8", "us_wall": round(t)})

    out_f = ref.paged_attention(qd, kp, vp, pt, lens)
    out_q = ref.paged_attention_quant(qd, kq, vq, k_s, v_s, pt, lens)
    rel = float(np.linalg.norm(np.asarray(out_q - out_f))
                / np.linalg.norm(np.asarray(out_f)))
    emit("kernel_paged_int8_rel_err", f"{rel:.2e}",
         "vs fp32 oracle (bound 5e-2)")

    # Pallas kernels: compiled on a TPU, interpreted elsewhere (where
    # dispatch/lowering overhead dominates and correctness is the point)
    interpret = jax.default_backend() != "tpu"
    mode = "interpret" if interpret else "tpu"
    iters = 1 if quick else 2
    t = timed(lambda: paged_pallas(qd, kp, vp, pt, lens, interpret=interpret),
              iters=iters)
    emit("kernel_paged_pallas_fp32", f"{t:.0f}", f"us {mode}")
    results.append({"kernel": "paged_pallas_fp32", "us_wall": round(t)})
    t = timed(lambda: paged_pallas(qd, kq, vq, pt, lens, k_scale=k_s,
                                   v_scale=v_s, interpret=interpret),
              iters=iters)
    emit("kernel_paged_pallas_int8", f"{t:.0f}", f"us {mode} fused dequant")
    results.append({"kernel": "paged_pallas_int8", "us_wall": round(t)})

    rng = np.random.default_rng(0)
    directory = jnp.asarray(rng.integers(-1, 16, 64), jnp.int32)
    cache = jnp.asarray(rng.integers(0, 1 << 20, (16, 128)), jnp.int32)
    lpns = jnp.asarray(rng.integers(0, 64 * 128, 4096), jnp.int32)
    jit_ftl = jax.jit(lambda *a: ref.ftl_lookup(*a, 128))
    t = timed(jit_ftl, lpns, directory, cache)
    emit("kernel_ftl_ref_jnp", f"{t:.0f}", "us per 4096 translations")
    results.append({"kernel": "ftl_ref", "us_wall": round(t)})

    scores = jax.nn.softmax(jax.random.normal(ks[0], (4096, 256)), -1)
    jit_router = jax.jit(lambda s: ref.topk_router(s, 8))
    t = timed(jit_router, scores)
    emit("kernel_router_ref_jnp", f"{t:.0f}", "us per 4096 tokens")
    results.append({"kernel": "router_ref", "us_wall": round(t)})

    # deterministic gate material: the int8 accuracy bound and the stored
    # page-byte ratio (int8 codes + 2 fp32 scales over fp32 payload)
    ratio = (page * kv * d * 2 * 1 + 8) / (page * kv * d * 2 * 4)
    bench_json("kernels_micro", results,
               int8_rel_err_le_5e2=bool(rel <= 5e-2),
               int8_page_bytes_ratio=round(ratio, 4))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    main(quick=args.quick)
