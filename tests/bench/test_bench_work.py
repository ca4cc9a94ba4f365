"""The roofline and step-work functions against hand counts at a tiny
shape, and the peaks table."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2] / "bench"))

import peaks  # noqa: E402
import work  # noqa: E402


def test_paged_attention_counts_live_tokens_only():
    # 2 heads sharing 1 KV head of width 4; sequences of 3 and 0 tokens
    w = work.paged_attention([3, 0], n_heads=2, kv_heads=1, head_dim=4,
                             kv_bytes=4, q_bytes=4)
    # q.k and p.v: 2 FLOPs each per head, token and lane: 2*2*2*4*3
    assert w.flops == 2 * 2 * 2 * 4 * 3
    # K and V rows of the 3 live tokens, q in and out of the 1 live seq
    assert w.bytes == 2 * 3 * 1 * 4 * 4 + 2 * 1 * 2 * 4 * 4


def test_engine_step_adds_projections_and_appends():
    w = work.engine_step(decoded=2, live_lengths=[1, 2], d_model=8,
                         n_heads=2, kv_heads=1, head_dim=4)
    att = work.paged_attention([1, 2], 2, 1, 4)
    proj_flops = 2 * 2 * 8 * (8 + 2 * 4)
    proj_bytes = 4 * 8 * (8 + 2 * 4) + 4 * 2 * 8
    append = 2 * 2 * 4 * 4
    assert w.flops == proj_flops + att.flops
    assert w.bytes == proj_bytes + append + att.bytes


def test_least_time_names_its_bound():
    pk = peaks.peaks("TPU v5 lite")
    t, bound = peaks.least_time_s(1e9, 1e3, pk)
    assert bound == "compute" and t == pytest.approx(1e9 / 197e12)
    t, bound = peaks.least_time_s(1.0, 819e9, pk)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")
