"""What every architecture's counts share (``bench/counts/<arch>.py`` hold
the formulas): the sizes of the served types, and the roofline."""
from __future__ import annotations

from typing import Dict

BF16 = 2
F32 = 4


def roofline_s(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
