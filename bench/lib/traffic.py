"""The one traffic generator: reads a mix file of parameters and draws the
requests of one run from its seed.

A mix (``bench/traffic/<mix>.json``) gives an arrival law and a length law
for prompts and for outputs:

  arrival  {"law": "poisson", "rate_per_s": r}
  prompt, output
           {"law": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
           {"law": "uniform", "min": a, "max": b}
  shards   optional: requests carry a shard id, an equal number on each
  drain_s  how long a run may go on serving after its window closes

Every seed gets the same work: ``rate * seconds`` requests whose lengths
are the law's quantiles at (i + 1/2) / n, and whose inter-arrival gaps are
the exponential law's quantiles, each set in an order drawn from the seed.
The seed changes the order, the pairing and the token ids, not the sizes,
so runs with different seeds measure the same amount of work.
Output lengths are cut so that prompt + output stays below the engine's
``max_len``.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


@dataclass
class Request:
    due_s: float                 # when it is due, from the window's start
    prompt: np.ndarray           # int32 token ids
    max_new: int
    shard: Optional[int] = None


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(law: Dict, n: int) -> np.ndarray:
    """The n stratified lengths of a length law, in ascending order."""
    u = _quantiles(n)
    lo, hi = int(law["min"]), int(law["max"])
    if law["law"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = float(law["median"]) * np.exp(float(law["sigma"]) * z)
    elif law["law"] == "uniform":
        x = lo + u * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length law {law['law']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def generate(mix: Dict, seed: int, seconds: float, vocab: int,
             max_len: int) -> List[Request]:
    """The requests due in a window of ``seconds``, in due order."""
    rng = order = np.random.default_rng(seed)
    arrival = mix["arrival"]
    if arrival["law"] != "poisson":
        raise ValueError(f"unknown arrival law {arrival['law']!r}")
    n = max(int(round(float(arrival["rate_per_s"]) * seconds)), 1)
    gaps = -np.log1p(-_quantiles(n))
    gaps = gaps[order.permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * seconds / gaps.sum()
    prompt = lengths(mix["prompt"], n)[order.permutation(n)]
    out = lengths(mix["output"], n)[order.permutation(n)]
    out = np.minimum(out, max_len - 1 - prompt)
    if (out < 1).any():
        raise ValueError("a prompt leaves no room for output below max_len")
    shards = None
    if mix.get("shards"):
        k = int(mix["shards"])
        shards = (np.arange(n) % k)[order.permutation(n)]
    reqs = []
    for i in range(n):
        ids = rng.integers(0, vocab, int(prompt[i]), dtype=np.int32)
        reqs.append(Request(float(due[i]), ids, int(out[i]),
                            None if shards is None else int(shards[i])))
    return reqs

