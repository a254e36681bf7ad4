"""The traffic generator: the same seed gives the same requests, every
seed gives the same work in another order, and lengths stay in range."""
from __future__ import annotations

import bench_testkit as K
import numpy as np
import pytest

from bench.lib import spec as S
from bench.lib import traffic as T

MIXES = ["chat"]
MAX_LEN = {"chat": 2048}


def _gen(mix, seed, seconds=30.0):
    return T.generate(S.mix(K.REPO, mix), seed, seconds, 64000, MAX_LEN[mix])


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    a, b = _gen(mix, 2_147_483_659), _gen(mix, 2_147_483_659)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all((x.prompt == y.prompt).all() and x.max_new == y.max_new
               and x.shard == y.shard for x, y in zip(a, b))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_work(mix):
    a, b = _gen(mix, 5), _gen(mix, 6)
    assert len(a) == len(b)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    # the gaps between arrivals, and the last one to the window's end, are
    # one set in another order
    def gaps(rs):
        due = [r.due_s for r in rs] + [30.0]
        return sorted(np.round(np.diff(due), 9))
    assert gaps(a) == gaps(b)


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_clipped_and_due_in_window(mix):
    m = S.mix(K.REPO, mix)
    reqs = _gen(mix, 77, seconds=40.0)
    assert len(reqs) == round(m["arrival"]["rate_per_s"] * 40.0)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 40.0
    for r in reqs:
        assert m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
        assert 1 <= r.max_new <= m["output"]["max"]
        assert len(r.prompt) + r.max_new <= MAX_LEN[mix] - 1
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 64000


def test_lognormal_quantiles_hold_the_median():
    law = {"law": "lognormal", "median": 192, "sigma": 0.8, "min": 16,
           "max": 1024}
    x = T.lengths(law, 201)
    assert x[100] == 192 and x.min() >= 16 and x.max() <= 1024
    assert (np.diff(x) >= 0).all()
