"""The readers of the engine's own spans and stamps (``bench/lib/spans.py``)
and the TTFT split of ``bench/ttft_split.py``: on synthetic traces with
known answers, on the recorded chip trace, and on a small served window on
the CPU, where the parts of every request's TTFT add up to the harness's
reading."""
from __future__ import annotations

import time

import bench_testkit as K
import jax
import numpy as np
import pytest

from bench.lib import harness as H
from bench.lib import spans as SP
from bench.lib import spec as S
from bench.lib import trace as TR

MS = 1_000_000


@pytest.fixture(autouse=True)
def no_persistent_cache():
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def test_program_is_the_jitted_function_name():
    assert SP.program("jit_decode_block(12)") == "decode_block"
    assert SP.program("jit_splice_pages") == "splice_pages"
    assert SP.program("jit_prefill_chunk(3)") == "prefill_chunk"
    assert SP.program("fusion") == "fusion"


def test_idle_by_span_names_the_innermost_span():
    # device busy 0-10 and 50-100 ms: idle 10-50 (40 ms) and, on device 1,
    # idle 0-100 except 0-60; spans: tick 0-100 holding replay 20-30 and
    # pages 25-28 (nested in replay), nothing over 40-45 beyond the tick
    devices = {0: [(0, 10 * MS, "%a.1 = x"), (50 * MS, 100 * MS, "%b.2 = y")]}
    spans = sorted([(0, 100 * MS, "serve.tick", {}),
                    (20 * MS, 30 * MS, "serve.replay", {}),
                    (25 * MS, 28 * MS, "serve.pages", {}),
                    (110 * MS, 120 * MS, "serve.tick", {})])
    idle = SP.idle_by_span(devices, spans, 0, 100 * MS)
    assert idle == {"serve.tick": pytest.approx(0.030),      # 10-20, 30-50
                    "serve.replay": pytest.approx(0.007),    # 20-25, 28-30
                    "serve.pages": pytest.approx(0.003)}
    # outside every engine span
    idle = SP.idle_by_span(devices, spans[1:2], 0, 100 * MS)
    assert idle[SP.OUTSIDE] == pytest.approx(0.030)
    assert idle["serve.replay"] == pytest.approx(0.010)
    # two devices: averaged
    devices[1] = [(0, 60 * MS, "%c.3 = z")]
    idle = SP.idle_by_span(devices, spans, 0, 100 * MS)
    assert sum(idle.values()) == pytest.approx((0.040 + 0.040) / 2)


def test_module_time_and_prefill_share():
    devices = {0: [(0, 100 * MS, "%while.1 = x")],
               1: [(0, 50 * MS, "%fusion.2 = y")]}
    modules = {0: [(0, 70 * MS, "decode_block"), (70 * MS, 90 * MS, "prefill"),
                   (90 * MS, 100 * MS, "splice_pages")],
               1: [(0, 20 * MS, "prefill_chunk"),
                   (20 * MS, 50 * MS, "decode_block")]}
    t = SP.module_ns(modules, 0, 100 * MS)
    assert t == {"decode_block": 50 * MS, "prefill": 10 * MS,
                 "splice_pages": 5 * MS, "prefill_chunk": 10 * MS}
    # (20 + 10 + 20) / 2 of a mean busy time of (100 + 50) / 2
    assert SP.module_share(modules, devices, 0, 100 * MS) == \
        pytest.approx(100 * 25 / 75)
    assert SP.module_share({0: []}, devices, 0, 100 * MS) is None


def test_recorded_trace_idle_outside_engine_spans_is_its_idle_gaps():
    """On the recorded v5e slice (which predates the engine's spans) the
    new reader puts all idle time outside any engine span, and it agrees
    with the harness's own reading of the same slice."""
    tr = K.recorded_trace()
    lo, hi = tr.window()
    gaps = TR.idle_gaps(tr, lo, hi, n=10 ** 6)
    idle = SP.idle_by_span(tr.devices, [], lo, hi)
    assert list(idle) == [SP.OUTSIDE]
    assert idle[SP.OUTSIDE] == pytest.approx(sum(s for _, s in gaps))


def _tool():
    return S.module(K.REPO / "bench" / "ttft_split.py")


@pytest.mark.parametrize("chips", [1, 4])
def test_ttft_parts_add_up_to_the_harness_ttft(tmp_path, chips):
    """A small served window on the CPU: for every request due, generator
    lateness + admission wait + (admission -> first token) + hold equals
    the harness's TTFT to within 2 ms, and each part is a real interval."""
    root = K.tiny_root(tmp_path, chips)
    cell = H.Cell.load(root, "tiny.chat")
    seed = 2_147_483_719
    reqs = cell.requests(seed, 2.0)
    eng = cell.engine(seed, jax.devices())
    run, rows, traced = _tool().split(cell, eng, reqs, 2.0, K.PEAK,
                                      trace=False)
    assert traced is None
    assert len(rows) == len(reqs) == len(run.ttft_s())
    for r in rows:
        assert abs(r["closure_s"]) < 2e-3, r
        for part in ("lateness_s", "admit_wait_s", "to_first_s", "hold_s"):
            assert r[part] >= -1e-6, (part, r)
    by_key = {r.key: r for r in reqs}
    for row in rows:
        req = by_key[row["key"]]
        assert row["ttft_s"] == pytest.approx(req.first_t - req.req.due_s)


def test_traced_slice_reads_engine_spans_and_leaves_trace_load_alone(
        tmp_path):
    """Traced the way the harness traces, on the CPU: ``spans.load`` finds
    the engine's spans with their counters, while ``trace.load`` of the
    same file still holds only the harness's ``bench.*`` spans, so every
    reading it gives is what it gave before the engine had spans."""
    root = K.tiny_root(tmp_path, 1)
    cell = H.Cell.load(root, "tiny.chat")
    seed = 2_147_483_723
    reqs = cell.requests(seed, 2.0)
    eng = cell.engine(seed, jax.devices())
    prof = tmp_path / "prof"
    rec = SP.Recorder(eng, str(prof), time.perf_counter(), 0.0, 10.0)
    H.serve_window(cell, eng, reqs, 2.0, K.PEAK, False)
    rec.stop_trace()
    assert rec.traced
    (path,) = prof.rglob("*.xplane.pb")
    tr, et = TR.load(str(path)), SP.load(str(path))
    assert {n for _, _, n in tr.host} == {TR.WINDOW}
    names = {n for _, _, n, _ in et.spans}
    assert {"serve.tick", "serve.admit", "serve.prefill", "serve.splice",
            "serve.decode_block", "serve.replay", "serve.pages"} <= names
    blocks = [st for _, _, n, st in et.spans if n == "serve.decode_block"]
    assert blocks and all(st["live_kv_tokens"] > 0 and st["steps"] >= 1
                          for st in blocks)
    lo, hi = tr.window()
    ticks = [(s, e) for s, e, n, _ in et.spans if n == "serve.tick"]
    assert ticks and all(lo <= s <= e <= hi for s, e in ticks)
    assert not tr.devices and not et.modules      # the CPU has no TPU plane
    assert SP.module_share(et.modules, tr.devices, lo, hi) is None
    assert np.isfinite(SP.window_start(reqs, rec.stamps))

