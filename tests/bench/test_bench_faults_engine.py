"""A run of an engine cell drives the harness past the look for a chip, on
the CPU at a small size: sound, it comes out correct; with the timed path
broken underneath, `correct` comes out false, once for each fault an
engine cell can have."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import cpu_run  # noqa: E402

SEED = 3_000_000_021


def _state_unchanged(monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.serving import engine as E

    orig = E.step

    def step(cfg, state, arrivals):
        _, stats = orig(cfg, jax.tree.map(jnp.copy, state), arrivals)
        return state, stats

    monkeypatch.setattr(E, "step", step)


def _half_batch(monkeypatch):
    import jax.numpy as jnp
    from repro.serving import kv_pool

    orig = kv_pool.append_tokens

    def append(pool, k_toks, v_toks, active, *a, **kw):
        half = jnp.arange(active.shape[1])[None, :] % 2 == 0
        return orig(pool, k_toks, v_toks, active & half, *a, **kw)

    monkeypatch.setattr(kv_pool, "append_tokens", append)


def _token_altered(monkeypatch):
    from repro.serving import kv_pool

    orig = kv_pool.append_tokens

    def append(pool, k_toks, v_toks, *a, **kw):
        return orig(pool, k_toks * 1.05, v_toks, *a, **kw)

    monkeypatch.setattr(kv_pool, "append_tokens", append)


def _attention_short(monkeypatch):
    """The paged attention leaves out each sequence's newest row, as a
    kernel that skips a live block would."""
    from repro.kernels import ops

    orig = ops.paged_attention

    def attn(q, k_pool, v_pool, page_table, lengths, **kw):
        return orig(q, k_pool, v_pool, page_table,
                    lengths - (lengths > 1), **kw)

    monkeypatch.setattr(ops, "paged_attention", attn)


def test_sound_run_is_correct(monkeypatch):
    line = cpu_run.run(monkeypatch, "engine.skew", SEED)
    assert line["correct"], line["checks"]
    assert line["metrics"]["decode_tok_s"]["value"] > 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered, _attention_short])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    line = cpu_run.run(monkeypatch, "engine.skew", SEED)
    assert not line["correct"], line["checks"]
