"""Operations and bytes the model and its kernels need, from shapes alone.

Counts are of what the algorithm requires, not of what a kernel happens to
touch: a paged decode call is charged for the live context of the slots
that are decoding, never for the pool or for dead pages.  Matmuls count 2
operations per multiply-add.
"""
from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2
F32 = 4


class Widths:
    def __init__(self, spec: Dict):
        self.d = int(spec["hidden_size"])
        self.h = int(spec["num_attention_heads"])
        self.hkv = int(spec["num_key_value_heads"])
        self.dh = int(spec.get("head_dim") or self.d // self.h)
        self.f = int(spec["intermediate_size"])
        self.v = int(spec["vocab_size"])
        self.layers = int(spec["num_hidden_layers"])


def layer_matmul_flops(w: Widths) -> int:
    """Projections and MLP of one layer, for one token."""
    qkvo = w.d * w.h * w.dh * 2 + w.d * w.hkv * w.dh * 2
    return 2 * (qkvo + 3 * w.d * w.f)


def attention_flops(w: Widths, context: int) -> int:
    """Scores and weighted values of one layer, one query over ``context``."""
    return 4 * w.h * w.dh * context


def head_flops(w: Widths) -> int:
    return 2 * w.d * w.v


def token_flops(w: Widths, context: int, logits: bool) -> int:
    """One token through the whole model, attending over ``context``
    positions (itself included); ``logits`` adds the output head."""
    per_layer = layer_matmul_flops(w) + attention_flops(w, context)
    return w.layers * per_layer + (head_flops(w) if logits else 0)


def prefill_flops(w: Widths, start: int, stop: int, last: bool) -> int:
    """Prompt rows at positions [start, stop); ``last`` when stop ends the
    prompt, so the head runs on its final row."""
    n = stop - start
    ctx_sum = (start + 1 + stop) * n // 2           # sum of p + 1
    return (w.layers * (n * layer_matmul_flops(w)
                        + 4 * w.h * w.dh * ctx_sum)
            + (head_flops(w) if last else 0))


def paged_decode_call(w: Widths, contexts: Iterable[int]):
    """(flops, bytes) of one paged decode attention call of one layer, over
    the live contexts of the slots decoding in that step: each slot reads
    its K and V rows (bf16) and its query, and writes its f32 output."""
    ctx = list(contexts)
    flops = sum(attention_flops(w, n) for n in ctx)
    kv = sum(n * w.hkv * w.dh * 2 * BF16 for n in ctx)
    qo = len(ctx) * w.h * (w.dh * (BF16 + F32) + 2 * F32)
    return flops, kv + qo


def roofline_s(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
