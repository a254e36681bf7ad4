"""Whole decode step: model operations of the tokens decoded in the traced
slice (matmuls, attention over the live context, the head) over the
slice's length times the chip's bf16 peak (%)."""


def read(run):
    w = run.traced_s()
    if w <= 0 or run.traced_decode_flops <= 0:
        return None
    return 100.0 * run.traced_decode_flops / (
        w * run.chips * run.peak["bf16_flops"])
