"""The main path's Pallas kernels compile for a TPU v5e at production
widths — no chip needed.

The TPU compiler is installed with jax; it compiles for a chip that is
described (`v5e:2x2`) rather than attached, and refuses what the chip
would refuse (block layouts, vector shape casts, VMEM over-use) — the
failures interpret mode cannot see. Each test asserts the program holds a
`tpu_custom_call`, i.e. the kernel itself and not an XLA fallback.

Shapes: the serving engine's paged decode at the `chip_smoke.py` phase-2
geometry (8 replicas x 10 slots, 64 pages of 16 tokens each, 12 query / 2
KV heads of 128), fp32 and int8 pools; and the qwen2-vl-2b prefill's flash
attention (batch 4, prompt 256, bf16).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every pytest-xdist worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention

# chip_smoke.py phase 2: EngineConfig(n_replicas=8, seq_slots=8,
# shadow_slots=2, pages_per_replica=64, page=16, max_pages=16, ...)
REPLICAS, SLOTS, POOL, PAGE, MAX_PAGES = 8, 10, 64, 16, 16
HEADS, KV_HEADS, HEAD_DIM = 12, 2, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable cannot be read back from the persistent
    # cache without the chip; keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged_args(sharding, dtype):
    b, p = REPLICAS * SLOTS, REPLICAS * POOL
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return (s((b, HEADS, HEAD_DIM), jnp.float32),
            s((p, PAGE, KV_HEADS, HEAD_DIM), dtype),
            s((p, PAGE, KV_HEADS, HEAD_DIM), dtype),
            s((b, MAX_PAGES), jnp.int32),
            s((b,), jnp.int32),
            s((p,), jnp.float32))


def test_paged_attention_fp32_compiles_for_v5e(one_chip):
    q, k, v, table, lengths, _ = _paged_args(one_chip, jnp.float32)
    assert "tpu_custom_call" in _hlo(paged_attention, q, k, v, table, lengths)


def test_paged_attention_int8_compiles_for_v5e(one_chip):
    q, k, v, table, lengths, scale = _paged_args(one_chip, jnp.int8)
    hlo = _hlo(lambda *a: paged_attention(*a[:5], k_scale=a[5],
                                          v_scale=a[6]),
               q, k, v, table, lengths, scale, scale)
    assert "tpu_custom_call" in hlo


def test_flash_attention_prefill_compiles_for_v5e(one_chip):
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                           sharding=one_chip)
    q = s((4, 256, HEADS, HEAD_DIM))
    kv = s((4, 256, KV_HEADS, HEAD_DIM))
    assert "tpu_custom_call" in _hlo(flash_attention, q, kv, kv)
