"""Peaks and FLOPs/bytes tables against hand counts."""

import os

import pytest

from bench import flops, model_config, peaks
from bench.tests.tiny import REPO


def shape(name):
    return model_config.shape_of(model_config.load(
        os.path.join(REPO, "bench", "configs", name + ".json")))


def test_v5e_peaks_and_unknown_kind():
    p = peaks.peak_for("TPU v5 lite")
    assert (p.flops_per_s, p.hbm_bytes_per_s) == (197e12, 819e9)
    assert "Google Cloud" in p.source
    with pytest.raises(KeyError):
        peaks.peak_for("TPU v9 imaginary")


def test_qwen2_train_flops_per_token():
    # per layer: q 896x14x64, k and v 896x2x64 each, o 14x64x896,
    # gate/up/down 3 x 896x4864; head 896x151936
    layer = 896 * 14 * 64 * 2 + 896 * 2 * 64 * 2 + 3 * 896 * 4864
    assert layer == 14_909_440
    dense = 2 * (24 * layer + 896 * 151936)
    # causal attention at 4096: QK and PV, 2 FLOPs each, over H*hd, for
    # an average of (4096+1)/2 keys per query
    attn = 24 * 2 * 2 * 14 * 64 * 4097 / 2
    want = 3 * (dense + attn)
    assert flops.train_flops_per_token(shape("qwen2-0.5b"), 4096) == want
    assert 3.49e9 < want < 3.50e9


def test_qwen3_d6_decode_tick_bytes():
    s = shape("qwen3-14b-d6")
    layer = 5120 * 40 * 128 * 2 + 5120 * 8 * 128 * 2 + 3 * 5120 * 17408
    assert layer == 330_301_440
    weights = 2 * (6 * layer + 5120 * 151936)
    kv_per_token = 6 * 2 * 8 * 128 * 2                 # layers, K+V, bf16
    ctx = [8192] * 16
    _, nbytes = flops.decode_tick(s, ctx)
    assert nbytes == weights + 16 * 8192 * kv_per_token
    assert round(weights / 1e9, 2) == 5.52 and nbytes == 8_740_667_392
    # the tick is bound by bandwidth, not FLOPs, on a v5e
    f, b = flops.decode_tick(s, ctx)
    p = peaks.peak_for("TPU v5 lite")
    assert b / p.hbm_bytes_per_s > 10 * f / p.flops_per_s


def test_paged_decode_call_counts_live_kv_only():
    s = shape("qwen3-14b-d6")
    f, b = flops.paged_decode_call(s, [100, 1])
    assert b == (101 * 2 * 8 * 128 * 2) + 2 * (2 * 40 * 128 * 2)
    assert f == 4 * 40 * 128 * 101


def test_prefill_chunk_head_only_on_last():
    s = shape("qwen2-0.5b")
    f_mid, _ = flops.prefill_chunk(s, 0, 512, last=False)
    f_last, _ = flops.prefill_chunk(s, 0, 512, last=True)
    assert f_last - f_mid == 2 * 896 * 151936
