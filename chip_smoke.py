#!/usr/bin/env python3
"""Bring-up smoke test: the serving stack on TPU, end to end, in one process.

    python chip_smoke.py            # one chip: phases 1-3
    python chip_smoke.py --chips 4  # four chips: sharded engine vs vmap only

Phases (one chip):
  1. model serve path — `launch.serve.serve` on qwen2-vl-2b at its full
     published width (bf16, 28 layers, d_model 1536, 12H/2KV x 128, vocab
     151936, random weights from --seed): prefill batch 4 x 256 tokens,
     16 greedy decode steps. Requires finite logits at every step, the
     flash-attention kernel in the compiled prefill, and the kernel within
     FLASH_ATOL of `kernels.ref.attention` at that layer's shapes.
  2. serving engine (harvesting runtime) at the same attention width, fp32
     and int8 KV pages, 32 skewed-arrival steps through `engine.run_steps`.
     Requires the paged-attention kernel in the compiled program, §4.4
     redirects, the LINK_BW byte-account invariant at every step, and the
     kernel against the jnp oracles on the final pool.
  3. JBOF simulator — the fig22_fabric scenario at 256 enclosures x 16 SSDs
     through `jbof.sim.simulate`. Requires finite per-SSD latency and
     throughput, sum(borrowed) <= sum(spare) segments in every window, and
     per-SSD statistics within SIM_RTOL of the same scenario run on the host
     CPU backend at a small fleet (the scenario is scale-invariant).

With --chips 4 only the mesh path runs: `engine.make_sharded_step` over a
4-device serving mesh against `engine.step` (vmap) on one device.

Every oracle runs on the host CPU backend of this process. A failed check
raises, so the exit code is non-zero and the result line is not printed.
The last stdout line is the result:
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
from functools import partial

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "benchmarks")]

# stated tolerances
FLASH_ATOL = 3e-2        # bf16 kernel vs fp32 oracle, max abs (the bf16
                         # tolerance of tests/test_kernels.py)
PAGED_REL = 5e-3         # kernel vs its own oracle, relative L2
INT8_REL = 5e-2          # int8 kernel vs fp32 oracle, relative L2 (the
                         # repo's int8 bound)
SIM_RTOL = 2e-2          # chip vs CPU per-SSD means, relative
SHARD_RTOL = SHARD_ATOL = 1e-5   # shard_map vs vmap float stats
STATE_RTOL = STATE_ATOL = 1e-6   # shard_map vs vmap float state

ENGINE_WIDTH = dict(n_heads=12, kv_heads=2, head_dim=128, page=16,
                    max_pages=16, pages_per_replica=64,
                    link_pages_per_step=2)


class SmokeFailure(AssertionError):
    pass


def require(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_kernel(hlo: str, what: str) -> None:
    """The compiled program holds a Pallas TPU kernel, not an XLA
    fallback."""
    require("tpu_custom_call" in hlo, f"{what}: no tpu_custom_call")


def log(msg: str) -> None:
    print(msg, flush=True)


def _host_cpu():
    import jax
    return jax.devices("cpu")[0]


def _on_cpu(fn, *args):
    """Run an oracle on the host CPU backend (exact fp32 matmuls)."""
    import jax
    cpu = _host_cpu()
    with jax.default_device(cpu):
        return fn(*jax.device_put(args, cpu))


def _rel(a, b, mask=None) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if mask is not None:
        a, b = a[mask], b[mask]
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _peak(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else str(peak)


# ------------------------------------------------------------- phase 1
def phase_serve(cfg, batch: int, prompt: int, gen: int, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention
    from repro.launch.serve import serve
    from repro.models import transformer as T

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(partial(T.init_params, cfg))(jax.random.key(seed)))
    init_s = time.perf_counter() - t0
    run = serve(cfg, params, batch, prompt, gen, seed=seed)
    require(bool(np.asarray(run.finite).all()),
            f"non-finite logits at steps "
            f"{np.nonzero(~np.asarray(run.finite))[0].tolist()}")
    require(run.tokens.shape == (batch, gen), "token shape")
    require_kernel(run.prefill_hlo, "prefill")
    del params

    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.key(seed + 10), 3)
    q = jax.random.normal(ks[0], (batch, prompt, h, dh), cfg.param_dtype)
    k = jax.random.normal(ks[1], (batch, prompt, kv, dh), cfg.param_dtype)
    v = jax.random.normal(ks[2], (batch, prompt, kv, dh), cfg.param_dtype)
    interpret = jax.default_backend() != "tpu"
    got = jax.jit(partial(flash_attention, interpret=interpret))(q, k, v)
    want = _on_cpu(lambda *a: ref.attention(
        *(x.astype(jnp.float32) for x in a)), q, k, v)
    err = float(np.max(np.abs(np.asarray(got, np.float32)
                              - np.asarray(want))))
    require(err <= FLASH_ATOL, f"flash vs oracle max abs {err}")
    log(f"phase1 serve {cfg.name}: ok init {init_s:.3f}s compile "
        f"{run.compile_s:.3f}s prefill {batch}x{prompt} {run.prefill_s:.4f}s "
        f"decode {gen} steps {run.decode_s:.4f}s "
        f"({batch * gen / run.decode_s:.1f} tok/s) "
        f"flash_max_abs_err {err:.3e} (tol {FLASH_ATOL}) "
        f"sample {np.asarray(run.tokens)[0, :8].tolist()}")


# ------------------------------------------------------------- phase 2
def _pool_views(cfg, pool):
    """The flat views the engine hands the paged kernel: (k pool, v pool,
    page table, lengths, int8 scales or {})."""
    from repro.serving import engine as E
    from repro.serving import kv_pool as kvp

    rows = cfg.n_replicas * E.total_slots(cfg)
    shape = (cfg.n_replicas * cfg.pages_per_replica, cfg.page,
             cfg.kv_heads, cfg.head_dim)
    scales = {}
    if kvp.quantized(pool):
        scales = dict(k_scale=pool.k_scale.reshape(-1),
                      v_scale=pool.v_scale.reshape(-1))
    return (pool.k.reshape(shape), pool.v.reshape(shape),
            pool.page_table.reshape(rows, cfg.max_pages),
            pool.seq_len.reshape(rows), scales)


def _paged(q, k, v, table, lengths, **scales):
    import jax
    from repro.kernels.paged_attention import paged_attention
    interpret = jax.default_backend() != "tpu"
    return jax.jit(partial(paged_attention, interpret=interpret))(
        q, k, v, table, lengths, **scales)


def _quantize_pages(x):
    """Per-page int8 codes + fp32 scales (max-abs / 127, the kv_pool
    convention); an all-zero page keeps scale 1."""
    import jax.numpy as jnp
    m = jnp.max(jnp.abs(x), axis=(1, 2, 3))
    s = jnp.where(m > 0, m / 127.0, 1.0)
    codes = jnp.clip(jnp.round(x / s[:, None, None, None]), -127, 127)
    return codes.astype(jnp.int8), s


def phase_engine(n_replicas: int, steps: int, seed: int,
                 width: dict = ENGINE_WIDTH) -> None:
    import jax
    import numpy as np

    from repro.kernels import ref
    from repro.launch.serve import skewed_arrivals
    from repro.serving import engine as E
    from repro.serving.scenarios import check_link_account

    arr = skewed_arrivals(n_replicas)[None]
    for quant in ("none", "int8"):
        cfg = E.EngineConfig(n_replicas=n_replicas, kv_quant=quant, **width)
        state = E.init(cfg, jax.random.key(seed))
        t0 = time.perf_counter()
        run = E.run_steps.lower(cfg, state, arr, steps).compile()
        compile_s = time.perf_counter() - t0
        require_kernel(run.as_text(), f"engine step ({quant})")
        t0 = time.perf_counter()
        state, stats = jax.block_until_ready(run(state, arr))
        run_s = time.perf_counter() - t0
        stats = jax.tree.map(np.asarray, stats)

        for i in range(steps):
            check_link_account(i, stats["link_budget_bytes"][i],
                               stats["link_redirect_bytes"][i],
                               stats["link_spill_bytes"][i])
        redirected = int(stats["redirected"].sum())
        require(redirected > 0, f"no §4.4 redirects ({quant})")
        require(np.isfinite(stats["attn_norm"]).all(),
                f"non-finite attention ({quant})")

        # the kernel on the final pool, rows that hold tokens
        k, v, table, lengths, scales = _pool_views(cfg, state.pool)
        q = jax.random.normal(jax.random.key(seed + 20),
                              (table.shape[0], cfg.n_heads, cfg.head_dim))
        live = np.asarray(lengths > 0)
        require(live.any(), f"no live sequence in the final pool ({quant})")
        out = _paged(q, k, v, table, lengths, **scales)
        if quant == "none":
            want = _on_cpu(ref.paged_attention, q, k, v, table, lengths)
            # int8 codes of this same pool through the fused-dequant
            # kernel, against the fp32 oracle
            kq, ks = _quantize_pages(k)
            vq, vs = _quantize_pages(v)
            err8 = _rel(_paged(q, kq, vq, table, lengths, k_scale=ks,
                               v_scale=vs), want, live)
            require(err8 <= INT8_REL, f"int8 kernel vs fp32 oracle: {err8}")
            extra = f"; int8_kernel_vs_fp32_oracle_rel {err8:.3e} " \
                    f"(tol {INT8_REL})"
        else:
            want = _on_cpu(ref.paged_attention_quant, q, k, v,
                           scales["k_scale"], scales["v_scale"], table,
                           lengths)
            extra = ""
        err = _rel(out, want, live)
        require(err <= PAGED_REL, f"paged kernel vs oracle ({quant}): {err}")
        log(f"phase2 engine kv_quant={quant}: ok R={n_replicas} "
            f"{steps} steps compile {compile_s:.3f}s run {run_s:.4f}s "
            f"({steps / run_s:.1f} steps/s) redirected {redirected} "
            f"spill_bytes {float(stats['link_spill_bytes'].sum()):.0f} "
            f"redirect_bytes {float(stats['link_redirect_bytes'].sum()):.0f}"
            f" budget_bytes {float(stats['link_budget_bytes'].sum()):.0f} "
            f"link_account ok; kernel_vs_oracle_rel {err:.3e} "
            f"(tol {PAGED_REL}) live_rows {int(live.sum())}{extra}")


# ------------------------------------------------------------- phase 3
def _sim_stats(n: int) -> dict:
    import jax
    import numpy as np

    import fig22_fabric as F
    from repro.jbof import platforms, sim

    wls, arr, e, n_busy = F._scenario(n)
    res = sim.simulate(platforms.xbof(), wls, arr,
                       cfg=sim.SimConfig(warmup=F.WARMUP, n_enclosures=e))
    res = jax.block_until_ready(res)
    lat, thr = np.asarray(res.latency_s), np.asarray(res.throughput_bps)
    require(np.isfinite(lat).all() and np.isfinite(thr).all(),
            f"non-finite per-SSD latency/throughput at {n} SSDs")
    borrowed = np.asarray(res.rings["borrowed_seg"]).sum(axis=1)
    spare = np.asarray(res.rings["spare_seg"]).sum(axis=1)
    bad = np.nonzero(borrowed > spare)[0]
    require(bad.size == 0,
            f"sum(borrowed) > sum(spare) at windows {bad[:8].tolist()}")
    far = np.asarray(res.borrowed_far)
    return {
        "busy_latency_s": float(lat[:n_busy].mean()),
        "idle_latency_s": float(lat[n_busy:].mean()),
        "busy_throughput_bps": float(thr[:n_busy].mean()),
        "idle_throughput_bps": float(thr[n_busy:].mean()),
        "far_segments_per_ssd": float(far.sum() / n),
        "windows": int(borrowed.shape[0]),
        "max_sum_borrowed": float(borrowed.max()),
        "min_sum_spare": float(spare.min()),
    }


def phase_sim(n_chip: int, n_cpu: int) -> None:
    import jax

    t0 = time.perf_counter()
    chip = _sim_stats(n_chip)
    chip_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    with jax.default_device(_host_cpu()):
        host = _sim_stats(n_cpu)
    cpu_s = time.perf_counter() - t0
    worst = 0.0
    for k in ("busy_latency_s", "idle_latency_s", "busy_throughput_bps",
              "idle_throughput_bps", "far_segments_per_ssd"):
        d = abs(chip[k] - host[k]) / max(abs(host[k]), 1e-30)
        require(d <= SIM_RTOL, f"sim {k}: chip {chip[k]} vs cpu {host[k]}")
        worst = max(worst, d)
    log(f"phase3 sim fig22 {n_chip} SSDs: ok {chip_s:.3f}s (cpu {n_cpu} "
        f"SSDs {cpu_s:.3f}s) borrowed<=spare in all {chip['windows']} "
        f"windows (max sum borrowed {chip['max_sum_borrowed']:.1f}, min sum "
        f"spare {chip['min_sum_spare']:.1f}) chip_vs_cpu_max_rel "
        f"{worst:.3e} (tol {SIM_RTOL}) chip {json.dumps(chip)} "
        f"cpu {json.dumps(host)}")


# --------------------------------------------------------- four chips
def phase_sharded(n_replicas: int, n_shards: int, steps: int, seed: int,
                  width: dict = ENGINE_WIDTH, mesh=None) -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import make_serving_mesh
    from repro.launch.serve import skewed_arrivals
    from repro.launch.sharding import engine_state_shardings
    from repro.serving import engine as E
    from repro.serving.scenarios import check_link_account

    cfg = E.EngineConfig(n_replicas=n_replicas, n_shards=n_shards,
                         cross_shard=True, **width)
    mesh = make_serving_mesh(n_shards) if mesh is None else mesh
    arr = skewed_arrivals(n_replicas)
    sv = jax.device_put(E.init(cfg, jax.random.key(seed)), jax.devices()[0])
    sm = jax.device_put(E.init(cfg, jax.random.key(seed)),
                        engine_state_shardings(cfg, mesh))
    n_dev = len(sm.pool.k.sharding.device_set)
    require(n_dev == n_shards, f"sharded pool spans {n_dev} devices")
    step_sm = E.make_sharded_step(cfg, mesh)
    int_stats = ("active", "queued", "redirected", "offsite_pages",
                 "cross_redirected", "log_commits")
    cross = 0
    t_v = t_m = 0.0
    for i in range(steps):
        t0 = time.perf_counter()
        sv, stv = jax.block_until_ready(E.step(cfg, sv, arr))
        t_v += time.perf_counter() - t0
        t0 = time.perf_counter()
        sm, stm = jax.block_until_ready(step_sm(sm, arr))
        t_m += time.perf_counter() - t0
        for k in stv:
            a, b = np.asarray(stv[k]), np.asarray(stm[k])
            if k in int_stats:
                require(np.array_equal(a, b), f"step {i} stat {k}: {a} {b}")
            else:
                require(np.allclose(a, b, rtol=SHARD_RTOL, atol=SHARD_ATOL),
                        f"step {i} stat {k}: {a} vs {b}")
        check_link_account(i, stm["link_budget_bytes"],
                           stm["link_redirect_bytes"],
                           stm["link_spill_bytes"])
        cross += int(stm["cross_redirected"])
    worst = 0.0
    for j, (lv, lm) in enumerate(zip(jax.tree.leaves(sv),
                                     jax.tree.leaves(sm))):
        a, b = np.asarray(lv), np.asarray(lm)
        if np.issubdtype(a.dtype, np.floating):
            require(np.allclose(b, a, rtol=STATE_RTOL, atol=STATE_ATOL),
                    f"state leaf {j} differs")
            worst = max(worst, float(np.max(np.abs(a - b), initial=0.0)))
        else:
            require(np.array_equal(a, b), f"state leaf {j} differs")
    n_dev = len(sm.pool.k.sharding.device_set)
    require(n_dev == n_shards, f"stepped pool spans {n_dev} devices")
    log(f"sharded engine R={n_replicas} S={n_shards}: ok {steps} steps, "
        f"shard_map == vmap (int stats/state bitwise, float state max abs "
        f"diff {worst:.3e}), state on {n_dev} devices, cross_redirected "
        f"{cross}, link_account ok; vmap {t_v:.4f}s shard_map {t_m:.4f}s "
        f"(first step includes compile)")


# ---------------------------------------------------------------- main
def _allow_host_cpu() -> None:
    """The oracles run on the host CPU backend, so keep it available when
    the platform list is pinned (the TPU stays the default device)."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    _allow_host_cpu()
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {len(devs)} "
                 f"{dev.platform} device(s) ({dev.device_kind})")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devs)} {dev.device_kind} device(s)")
    log(f"device: {dev.platform} {dev.device_kind} count={len(devs)}")

    from repro import configs
    from repro.launch.runtime import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    phases = []
    if args.chips == 4:
        phases.append(("sharded", lambda: phase_sharded(32, 4, 16,
                                                        args.seed)))
    else:
        phases += [
            ("serve", lambda: phase_serve(configs.get("qwen2-vl-2b"),
                                          4, 256, 16, args.seed)),
            ("engine", lambda: phase_engine(8, 32, args.seed)),
            ("sim", lambda: phase_sim(4096, 256)),
        ]
    for name, fn in phases:
        t0 = time.perf_counter()
        fn()
        log(f"phase {name}: {time.perf_counter() - t0:.3f}s, device peak "
            f"bytes {_peak(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
