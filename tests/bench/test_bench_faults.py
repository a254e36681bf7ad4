"""A whole run with the chip check left out, at a tiny size on the CPU:
sound, it comes out correct; with a token altered where the engine
produces it, or a request dropped after it was accepted, ``correct`` comes
out false.  On one drive and on four."""
from __future__ import annotations

import json
import time

import bench_testkit as K
import jax
import numpy as np
import pytest

from bench.lib import harness as H
from bench.lib import traffic as T
from repro.train import serve_loop


@pytest.fixture(autouse=True)
def no_persistent_cache():
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def _run(tmp_path, chips):
    root = K.tiny_root(tmp_path, chips)
    return H.run_cell(root, "tiny.chat", 2_147_483_701, 2.0, False,
                      jax.devices(), K.PEAK, time.perf_counter())


@pytest.mark.parametrize("chips", [1, 4])
def test_sound_run_is_correct(tmp_path, chips):
    res = _run(tmp_path, chips)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] == 8
    assert list(res["compared"]) == ["widest_gap", "token_count_mismatches",
                                     "unfinished"]
    bench = json.loads((K.REPO / "BENCHMARK.json").read_text())
    assert set(res["metrics"]) == {m["name"] for m in bench["end_to_end"]}


@pytest.mark.parametrize("chips", [1, 4])
def test_altered_token_is_not_correct(tmp_path, monkeypatch, chips):
    push = serve_loop.ServeEngine._push_token

    def altered(self, slot, tok):
        if len(slot.out) == 2:                  # every request's third token
            tok = (tok + 1) % self.cfg.vocab_size
        return push(self, slot, tok)

    monkeypatch.setattr(serve_loop.ServeEngine, "_push_token", altered)
    res = _run(tmp_path, chips)
    assert res["correct"] is False
    gap = res["compared"]["widest_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("chips", [1, 4])
def test_dropped_request_is_not_correct(tmp_path, monkeypatch, chips):
    submit = serve_loop.ServeEngine.submit

    def dropping(self, prompt, max_new=32, **kw):
        rid = submit(self, prompt, max_new, **kw)
        if rid == 1:                    # accepted, then never served
            self.queue.pop()
        return rid

    monkeypatch.setattr(serve_loop.ServeEngine, "submit", dropping)
    res = _run(tmp_path, chips)
    assert res["correct"] is False
    assert res["compared"]["unfinished"]["value"] >= 1
    assert res["failed"] >= 1


def test_sample_holds_both_prefill_paths():
    def tracked(plen, served):
        r = H.Tracked(T.Request(0.0, np.zeros(plen, np.int32), served))
        r.tokens = [0] * served
        return r

    # the most served tokens go to one-shot prompts; one prompt is chunked
    done = [tracked(100, 90), tracked(300, 10), tracked(50, 80),
            tracked(40, 70), tracked(600, 5)]
    for seed in range(20):
        pick = H.sample(done, seed, 1, 3, 256)
        assert pick[0] is done[0] and pick[1] is done[4]
        assert len(pick) == 3 and len({id(r) for r in pick}) == 3
    # with no one-shot prompt in the lead, one is drawn
    done = [tracked(300, 90), tracked(600, 10), tracked(50, 5)]
    pick = H.sample(done, 3, 1, 2, 256)
    assert [len(r.req.prompt) for r in pick] == [300, 600, 50]
