"""Serving launcher: prefill + batched greedy decode for --arch <id>
(`serve` is the callable body; smoke scale on CPU, full width on a TPU).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --smoke \
      --batch 4 --prompt-len 32 --gen 16

``--replicas N`` additionally drives the XBOF harvesting runtime (the
`serving.engine` continuous-batching layer on top of the decode path): N DP
replicas under skewed arrivals, redirecting overload through the unified
`core.manager` round (DESIGN.md §2).
"""
from __future__ import annotations

import argparse
import time
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch.runtime import enable_compile_cache
from repro.models import decode as D
from repro.models import transformer as T


def run_runtime_layer(n_replicas: int, steps: int = 12) -> None:
    """Skewed-load demo of the batched harvesting engine."""
    from repro.serving import engine as E

    cfg = E.EngineConfig(n_replicas=n_replicas)
    state = E.init(cfg, jax.random.key(0))
    arrivals = skewed_arrivals(n_replicas)
    # warmup step so the printed rate is steady-state, not trace+compile
    state, stats = E.step(cfg, state, arrivals)
    redirected = int(stats["redirected"])
    offsite = 0
    t0 = time.time()
    for _ in range(steps):
        state, stats = E.step(cfg, state, arrivals)
        redirected += int(stats["redirected"])
    jax.block_until_ready(stats["active"])
    offsite = int(stats["offsite_pages"])
    dt = time.time() - t0
    print(f"runtime layer: {n_replicas} replicas x {steps} steps in {dt:.2f}s"
          f" ({steps / dt:.1f} steps/s)")
    print(f"  redirected={redirected} offsite_pages={offsite} "
          f"wal_commits={int(stats['log_commits'])} "
          f"utils={[round(float(u), 2) for u in stats['util']]}")


def skewed_arrivals(n_replicas: int) -> jax.Array:
    """Per-step arrivals with replica 0 hot and replica 1 warm — the load
    that makes the harvesting runtime redirect."""
    return jnp.zeros((n_replicas,), jnp.int32).at[0].set(5).at[1].set(1)


class ServeRun(NamedTuple):
    tokens: jax.Array     # [B, gen] int32 greedy tokens
    finite: jax.Array     # [gen + 1] bool: logits finite after prefill and
                          # after every decode step
    prefill_hlo: str      # optimized HLO of the compiled prefill
    compile_s: float      # prefill + decode-step compilation
    prefill_s: float
    decode_s: float


def serve_inputs(cfg, batch: int, prompt_len: int, seed: int = 0):
    """Seeded prompt for one prefill: (token ids or None, kwargs). Configs
    with a stub frontend take seeded ``input_embeds`` / ``enc_embeds``."""
    tokens = jax.random.randint(jax.random.key(seed + 1), (batch, prompt_len),
                                0, cfg.vocab)
    kwargs = {}
    if cfg.frontend and not cfg.is_encdec:
        kwargs["input_embeds"] = jax.random.normal(
            jax.random.key(seed + 2), (batch, prompt_len, cfg.d_model),
            jnp.float32)
        tokens = None
    if cfg.is_encdec:
        kwargs["enc_embeds"] = jax.random.normal(
            jax.random.key(seed + 3), (batch, cfg.enc_seq, cfg.d_model),
            jnp.float32)
    return tokens, kwargs


def serve(cfg, params, batch: int, prompt_len: int, gen: int,
          seed: int = 0) -> ServeRun:
    """Prefill a seeded batch, then greedy-decode ``gen`` tokens.

    Both programs compile before the clock starts (``compile_s``), and
    each timing ends in `jax.block_until_ready`, so ``prefill_s`` and
    ``decode_s`` are device time plus dispatch, not enqueue time."""
    tok_arg, kwargs = serve_inputs(cfg, batch, prompt_len, seed)
    prefill_fn = partial(D.prefill, cfg, max_len=prompt_len + gen)
    t0 = time.perf_counter()
    prefill = jax.jit(prefill_fn).lower(params, tok_arg, **kwargs).compile()
    _, cache_shape = jax.eval_shape(prefill_fn, params, tok_arg, **kwargs)
    step = jax.jit(partial(D.decode_step, cfg), donate_argnums=(1,)).lower(
        params, cache_shape,
        jax.ShapeDtypeStruct((batch,), jnp.int32)).compile()
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    logits, cache = jax.block_until_ready(prefill(params, tok_arg, **kwargs))
    prefill_s = time.perf_counter() - t0

    finite = [jnp.all(jnp.isfinite(logits))]
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = []
    t0 = time.perf_counter()
    for _ in range(gen):
        out.append(tok)
        logits, cache = step(params, cache, tok)
        finite.append(jnp.all(jnp.isfinite(logits)))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    tokens, finite = jax.block_until_ready(
        (jnp.stack(out, axis=1), jnp.stack(finite)))
    decode_s = time.perf_counter() - t0
    return ServeRun(tokens, finite, prefill.as_text(), compile_s, prefill_s,
                    decode_s)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=0,
                    help="also run the XBOF harvesting runtime layer")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    params = jax.jit(partial(T.init_params, cfg))(jax.random.key(args.seed))
    b, s = args.batch, args.prompt_len
    run = serve(cfg, params, b, s, args.gen, seed=0)
    print(f"compile: {run.compile_s:.2f}s")
    print(f"prefill {b}x{s}: {run.prefill_s:.2f}s")
    print(f"decoded {args.gen} tokens/seq in {run.decode_s:.2f}s "
          f"({b * args.gen / run.decode_s:.1f} tok/s)")
    print("sample:", run.tokens[0][:12].tolist())
    if not bool(run.finite.all()):
        raise SystemExit("non-finite logits")

    if args.replicas > 0:
        run_runtime_layer(args.replicas)


if __name__ == "__main__":
    main()
