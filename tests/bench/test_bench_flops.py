"""The benchmark's operation and byte counts against hand counts at yi-9b's
published widths (d 4096, 32 query heads over 4 KV heads of 128, d_ff
11008, vocab 64000)."""
from __future__ import annotations

import json

import bench_testkit as K
import pytest

from bench.lib import flops as F
from bench.lib import peaks as P

YI = F.Widths(json.loads((K.REPO / "bench" / "configs"
                          / "yi-9b.24L.json").read_text()))


def test_layer_matmuls_by_hand():
    # q and o: 4096*32*128 each; k and v: 4096*4*128 each; MLP 3*4096*11008
    qkvo = 2 * 16_777_216 + 2 * 2_097_152
    mlp = 135_266_304
    assert F.layer_matmul_flops(YI) == 2 * (qkvo + mlp) == 346_030_080


def test_attention_and_head_by_hand():
    assert F.attention_flops(YI, 1000) == 4 * 32 * 128 * 1000
    assert F.head_flops(YI) == 2 * 4096 * 64000


def test_token_flops_sums_layers_and_head():
    want = 24 * (346_030_080 + 4 * 32 * 128 * 300) + 2 * 4096 * 64000
    assert F.token_flops(YI, 300, logits=True) == want
    assert F.token_flops(YI, 300, logits=False) == want - 2 * 4096 * 64000


def test_prefill_counts_each_row_once():
    rows = sum(F.token_flops(YI, p + 1, logits=False) for p in range(256, 512))
    assert F.prefill_flops(YI, 256, 512, last=False) == rows
    assert F.prefill_flops(YI, 256, 512, last=True) == rows + F.head_flops(YI)


def test_paged_decode_call_counts_live_context_only():
    flops, nbytes = F.paged_decode_call(YI, [100, 200])
    assert flops == 4 * 32 * 128 * 300
    kv = 300 * 4 * 128 * 2 * 2            # K and V rows of 4 heads, bf16
    qo = 2 * 32 * (128 * (2 + 4) + 8)     # bf16 query, f32 output, l and m
    assert nbytes == kv + qo
    assert F.paged_decode_call(YI, []) == (0, 0)


def test_roofline_is_the_larger_bound():
    peak = P.peaks("TPU v5 lite")
    assert F.roofline_s(197e12, 0, peak) == pytest.approx(1.0)
    assert F.roofline_s(0, 819e9, peak) == pytest.approx(1.0)
    flops, nbytes = F.paged_decode_call(YI, [2048] * 16)
    assert F.roofline_s(flops, nbytes, peak) == pytest.approx(nbytes / 819e9)
