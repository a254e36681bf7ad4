"""Operations and bytes a Llama-architecture model and its paged decode
kernel need, from shapes alone.

Counts are of what the algorithm requires, not of what a kernel happens to
touch: a paged decode call is charged for the live context of the slots
that are decoding, never for the pool or for dead pages.  Matmuls count 2
operations per multiply-add.  The harness builds ``Counts`` once per cell
and asks it per token and per decode step while the window runs, so what
does not depend on the context is worked out here once.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

from bench.lib.flops import BF16, F32


class Counts:
    def __init__(self, spec: Dict):
        self.d = d = int(spec["hidden_size"])
        self.h = h = int(spec["num_attention_heads"])
        self.hkv = hkv = int(spec["num_key_value_heads"])
        self.dh = dh = int(spec.get("head_dim") or d // h)
        self.f = f = int(spec["intermediate_size"])
        self.v = int(spec["vocab_size"])
        self.layers = int(spec["num_hidden_layers"])
        # projections and MLP of one layer, for one token
        self.layer_flops = 2 * (d * h * dh * 2 + d * hkv * dh * 2
                                + 3 * d * f)
        self.head_flops = 2 * d * self.v

    def attention_flops(self, context: int) -> int:
        """Scores and weighted values of one layer, one query over
        ``context``."""
        return 4 * self.h * self.dh * context

    def token_flops(self, context: int, logits: bool) -> int:
        """One token through the whole model, attending over ``context``
        positions (itself included); ``logits`` adds the output head."""
        per_layer = self.layer_flops + self.attention_flops(context)
        return self.layers * per_layer + (self.head_flops if logits else 0)

    def prefill_flops(self, start: int, stop: int, last: bool) -> int:
        """Prompt rows at positions [start, stop); ``last`` when stop ends
        the prompt, so the head runs on its final row."""
        n = stop - start
        ctx_sum = (start + 1 + stop) * n // 2           # sum of p + 1
        return (self.layers * (n * self.layer_flops
                               + 4 * self.h * self.dh * ctx_sum)
                + (self.head_flops if last else 0))

    def paged_decode_call(self, contexts: Iterable[int]) -> Tuple[int, int]:
        """(flops, bytes) of one paged decode attention call of one layer,
        over the live contexts of the slots decoding in that step: each
        slot reads its K and V rows (bf16) and its query, and writes its
        f32 output."""
        ctx = list(contexts)
        flops = sum(self.attention_flops(n) for n in ctx)
        kv = sum(n * self.hkv * self.dh * 2 * BF16 for n in ctx)
        qo = len(ctx) * self.h * (self.dh * (BF16 + F32) + 2 * F32)
        return flops, kv + qo

    def decode_kernels(self, contexts: Iterable[int]
                       ) -> Dict[str, Tuple[int, int, int]]:
        """{kernel: (flops a call, bytes a call, calls)} of one decode step
        over every layer, the slots decoding at ``contexts``."""
        flops, nbytes = self.paged_decode_call(contexts)
        return {"paged_decode": (flops, nbytes, self.layers)}
