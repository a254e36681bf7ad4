"""The benchmark's operation and byte counts against hand counts at yi-9b's
published widths (d 4096, 32 query heads over 4 KV heads of 128, d_ff
11008, vocab 64000), and against the formulas they replaced, integer for
integer."""
from __future__ import annotations

import json

import bench_testkit as K
import pytest

from bench.lib import flops as F
from bench.lib import peaks as P
from bench.lib import spec as S

SPEC = json.loads((K.REPO / "bench" / "configs" / "yi-9b.24L.json")
                  .read_text())
YI = S.counts(K.REPO, "llama").Counts(SPEC)


def test_layer_matmuls_by_hand():
    # q and o: 4096*32*128 each; k and v: 4096*4*128 each; MLP 3*4096*11008
    qkvo = 2 * 16_777_216 + 2 * 2_097_152
    mlp = 135_266_304
    assert YI.layer_flops == 2 * (qkvo + mlp) == 346_030_080


def test_attention_and_head_by_hand():
    assert YI.attention_flops(1000) == 4 * 32 * 128 * 1000
    assert YI.head_flops == 2 * 4096 * 64000


def test_token_flops_sums_layers_and_head():
    want = 24 * (346_030_080 + 4 * 32 * 128 * 300) + 2 * 4096 * 64000
    assert YI.token_flops(300, logits=True) == want
    assert YI.token_flops(300, logits=False) == want - 2 * 4096 * 64000


def test_prefill_counts_each_row_once():
    rows = sum(YI.token_flops(p + 1, logits=False) for p in range(256, 512))
    assert YI.prefill_flops(256, 512, last=False) == rows
    assert YI.prefill_flops(256, 512, last=True) == rows + YI.head_flops


def test_paged_decode_call_counts_live_context_only():
    flops, nbytes = YI.paged_decode_call([100, 200])
    assert flops == 4 * 32 * 128 * 300
    kv = 300 * 4 * 128 * 2 * 2            # K and V rows of 4 heads, bf16
    qo = 2 * 32 * (128 * (2 + 4) + 8)     # bf16 query, f32 output, l and m
    assert nbytes == kv + qo
    assert YI.paged_decode_call([]) == (0, 0)


def test_roofline_is_the_larger_bound():
    peak = P.peaks("TPU v5 lite")
    assert F.roofline_s(197e12, 0, peak) == pytest.approx(1.0)
    assert F.roofline_s(0, 819e9, peak) == pytest.approx(1.0)
    flops, nbytes = YI.paged_decode_call([2048] * 16)
    assert F.roofline_s(flops, nbytes, peak) == pytest.approx(nbytes / 819e9)


# -- the formulas the harness used before counts moved per architecture,
# -- as they stood, on yi-9b's keys ------------------------------------------

D, H, HKV, DH, FF, V, LAYERS = 4096, 32, 4, 128, 11008, 64000, 24


def _old_token_flops(context, logits):
    qkvo = D * H * DH * 2 + D * HKV * DH * 2
    per_layer = 2 * (qkvo + 3 * D * FF) + 4 * H * DH * context
    return LAYERS * per_layer + (2 * D * V if logits else 0)


def _old_prefill_flops(start, stop, last):
    n = stop - start
    ctx_sum = (start + 1 + stop) * n // 2
    qkvo = D * H * DH * 2 + D * HKV * DH * 2
    return (LAYERS * (n * 2 * (qkvo + 3 * D * FF) + 4 * H * DH * ctx_sum)
            + (2 * D * V if last else 0))


def _old_paged_decode_call(contexts):
    flops = sum(4 * H * DH * n for n in contexts)
    kv = sum(n * HKV * DH * 2 * 2 for n in contexts)
    qo = len(contexts) * H * (DH * (2 + 4) + 2 * 4)
    return flops, kv + qo


@pytest.mark.parametrize("context", [1, 2, 17, 192, 255, 256, 1000, 2047,
                                     2048])
@pytest.mark.parametrize("logits", [True, False])
def test_yi_token_count_is_unchanged(context, logits):
    assert YI.token_flops(context, logits) == \
        _old_token_flops(context, logits)


@pytest.mark.parametrize("start,stop", [(0, 1), (0, 16), (0, 64), (0, 256),
                                        (256, 512), (512, 700), (768, 1024),
                                        (1000, 1001)])
@pytest.mark.parametrize("last", [True, False])
def test_yi_prefill_count_is_unchanged(start, stop, last):
    assert YI.prefill_flops(start, stop, last) == \
        _old_prefill_flops(start, stop, last)


@pytest.mark.parametrize("contexts", [[], [1], [193], [100, 200],
                                      [17, 400, 900, 1500, 2048],
                                      list(range(200, 216)), [2048] * 16])
def test_yi_decode_step_is_the_paged_kernel_in_every_layer(contexts):
    """One kernel, called once a layer, with the old (flops, bytes) a call:
    the harness charges ``calls * roofline_s`` as it charged
    ``layers * roofline_s``."""
    assert YI.decode_kernels(contexts) == \
        {"paged_decode": (*_old_paged_decode_call(contexts), LAYERS)}
