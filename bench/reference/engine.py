"""Plain reference of the serving engine's unit of work.

The engine decodes one token per active sequence slot per step through one
attention layer: activations x of step t are N(0, 0.01) from
`fold_in(key(7), t)` over [replicas per shard, slots, d_model] (the same
block for every shard); q, k and v are x times the layer's weights; the new
k and v rows append to the sequence's cache; and q attends over the
sequence's cached rows (grouped heads: head h reads KV head h // (heads /
KV heads), scores scaled by head_dim ** -0.5, softmax over the live rows).
A request lives 16 steps and decodes 16 tokens, so a slot holding L tokens
after step t appended them at steps t - L + 1 .. t.

Written from that description in float64 NumPy; it imports nothing of the
program. Two things come from the platform the cell runs on, as the
configuration states them:
- the random numbers: `weights` and `activations` draw with JAX's threefry
  on the cell's device (the chip's normal sampler differs from the CPU's
  by up to a few hundred float32 ulps); the harness hands the same weights
  to the program, so both sides start from the benchmark's own draw;
- the operands of every matrix product (`operand`): one bf16 pass on a TPU
  rounds both operands of each dot to bfloat16 and accumulates in float32.
  The reference rounds the same operands (x and the weights; q and k for
  the scores; the softmax weights and v for the output) and keeps the rest
  in float64, where products of bfloat16 numbers are exact. A sound program
  then differs from it by float32 accumulation and `exp` alone.

``cast`` puts every array in another type after every operation (the
control runs the whole layer in bfloat16 and stores its K/V so).
"""
from __future__ import annotations

import functools

import numpy as np

ACT_KEY = 7
ACT_SCALE = 0.1


def weights(key_seed: int, d_model: int, n_heads: int, kv_heads: int,
            head_dim: int):
    """(wq, wk, wv, wo), float32 N(0, 1/fan_in), drawn with JAX's threefry
    from ``key_seed``. Pure function of its arguments: jit it to draw on
    the device, or call it on the CPU for the reference."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.key(key_seed), 4)
    sc = lambda k, sh: jax.random.normal(k, sh, jnp.float32) * sh[0] ** -0.5
    kvd = kv_heads * head_dim
    return (sc(ks[0], (d_model, n_heads * head_dim)),
            sc(ks[1], (d_model, kvd)), sc(ks[2], (d_model, kvd)),
            sc(ks[3], (n_heads * head_dim, d_model)))


def _draw(step, n_local: int, slots: int, d_model: int):
    import jax

    key = jax.random.fold_in(jax.random.key(ACT_KEY), step)
    return jax.random.normal(key, (n_local, slots, d_model)) * ACT_SCALE


@functools.lru_cache(maxsize=128)
def activations(step: int, n_local: int, slots: int, d_model: int,
                device=None):
    """float32[n_local, slots, d_model] activations of engine step ``step``,
    drawn on ``device`` (default: JAX's default device)."""
    import jax

    global _jitted
    if _jitted is None:
        _jitted = jax.jit(_draw, static_argnums=(1, 2, 3))
    with jax.default_device(device):
        return np.asarray(_jitted(np.int32(step), n_local, slots, d_model))


_jitted = None


def round_to(dtype: str | None):
    """Rounding of a float array to ``dtype`` (None: none), kept in float64."""
    if dtype in (None, "float32"):
        return lambda a: np.asarray(a, np.float64)
    import ml_dtypes

    rdt = getattr(ml_dtypes, dtype)
    return lambda a: np.asarray(a, np.float32).astype(rdt).astype(np.float64)


def float64(x):
    return np.asarray(x, np.float64)


class Layer:
    """The reference layer's rows and attention, in float64 (or ``cast``)."""

    def __init__(self, w, n_heads, kv_heads, head_dim, n_local, slots,
                 operand=None, cast=float64, device=None):
        rnd = round_to(operand)
        self.rnd, self.cast, self.device = rnd, cast, device
        self.wq, self.wk, self.wv = (rnd(a) for a in w[:3])
        self.h, self.kv, self.dh = n_heads, kv_heads, head_dim
        self.n_local, self.slots = n_local, slots
        self._rows = {}

    def _x(self, step, row):
        d = self.wk.shape[0]
        return self.rnd(activations(step, self.n_local, self.slots, d,
                                    self.device)[
            row // self.slots % self.n_local, row % self.slots])

    def rows(self, step, row):
        """(q [heads, head_dim], k, v [kv_heads, head_dim]) of slot ``row``
        (global replica * slots + slot) at ``step``; k and v are the rows
        it appends."""
        key = (step, row)
        if key not in self._rows:
            x, c = self._x(step, row), self.cast
            self._rows[key] = (
                c(c(x @ self.wq).reshape(self.h, self.dh)),
                c(c(x @ self.wk).reshape(self.kv, self.dh)),
                c(c(x @ self.wv).reshape(self.kv, self.dh)))
        return self._rows[key]

    def kv_row(self, step, row):
        return self.rows(step, row)[1:]

    def attention(self, step, row, length):
        """[heads, head_dim] output of slot ``row`` at ``step``, attending
        over the ``length`` rows it holds after that step's append."""
        c, rnd = self.cast, self.rnd
        q = self.rows(step, row)[0]
        ks, vs = zip(*(self.kv_row(s, row)
                       for s in range(step - length + 1, step + 1)))
        k, v = c(np.stack(ks)), c(np.stack(vs))          # [L, kv, dh]
        g = self.h // self.kv
        qg = q.reshape(self.kv, g, self.dh)
        s = c(c(np.einsum("kgd,lkd->kgl", c(rnd(qg)), c(rnd(k))))
              * self.dh ** -0.5)
        m = c(np.max(s, axis=2, keepdims=True))
        p = c(np.exp(c(s - m)))
        den = c(np.sum(p, axis=2, keepdims=True))
        out = c(c(np.einsum("kgl,lkd->kgd", c(rnd(p)), c(rnd(v)))) / den)
        return np.asarray(out, np.float64).reshape(self.h, self.dh)

    def attn_norm(self, step, attended):
        """Sum of squared attention outputs over ``attended`` [(row,
        length)] at ``step``: the engine's per-step attention statistic."""
        return float(sum(np.sum(self.attention(step, r, n) ** 2)
                         for r, n in attended))
