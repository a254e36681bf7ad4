"""The per-layer readers on a scripted run: the harness's accounting driven
through a fixed sequence of engine ticks at yi-9b.24L's widths, read
against a synthetic trace and the recorded v5e slice.  The existing
readers give, to the last bit, what they gave when the counts were
Llama's alone (values read from the harness before the counts moved to
``bench/counts/``); the two new ones read the engine's counters and
modules."""
from __future__ import annotations

import json
from types import SimpleNamespace

import bench_testkit as K
import numpy as np
import pytest

from bench.lib import harness as H
from bench.lib import spans as SP
from bench.lib import spec as S
from bench.lib import trace as TR
from bench.lib import traffic as T
from repro.train.serve_loop import ServeStats

MS = 1_000_000
# (due s, prompt tokens, tokens to serve): one-shot and chunked prompts
PROMPTS = [(0.0, 40, 30), (0.05, 300, 20), (0.12, 700, 41), (0.3, 192, 9),
           (0.31, 64, 64), (0.6, 1024, 12)]
CHUNK, K_BLOCK = 256, 8
SPEC = json.loads((K.REPO / "bench" / "configs" / "yi-9b.24L.json")
                  .read_text())
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
STATS0 = {"decode_s": 1.5, "decode_steps": 60.0, "kv_pages_walked": 1000.0}
STATS1 = {"decode_s": 2.7183, "decode_steps": 109.0,
          "kv_pages_walked": 40000.0}
OLD = ["decode_mfu.steady", "paged_decode_roofline.steady",
       "idle_share.steady", "decode_step_ms.steady",
       "queue_wait_p90_s.steady", "ttft_p90_s.steady"]
BEFORE = {
    "synthetic": {"decode_mfu.steady": 7.72923267248731,
                  "paged_decode_roofline.steady": 28.66512332112332,
                  "idle_share.steady": 63.000000000000014,
                  "decode_step_ms.steady": 24.863265306122454,
                  "queue_wait_p90_s.steady": 0.17500000000000002,
                  "ttft_p90_s.steady": 0.38},
    "recorded": {"decode_mfu.steady": 1.0159959070317186,
                 "paged_decode_roofline.steady": 3.5638954170124584,
                 "idle_share.steady": 2.916491520326736,
                 "decode_step_ms.steady": 24.863265306122454,
                 "queue_wait_p90_s.steady": 0.17500000000000002,
                 "ttft_p90_s.steady": 0.38},
}


def _ticks():
    """(tb, te, before, after, done) of a scripted drive: a prompt over
    ``CHUNK`` rows takes a chunk a tick, a shorter one a tick, and its
    first token comes with its last rows; then ``K_BLOCK`` tokens a tick."""
    state, out, t, nxt = {}, [], 0.0, 0

    def slots():
        return {k: ((r if r < PROMPTS[k][1] else None), n)
                for k, (r, n) in state.items()}

    while nxt < len(PROMPTS) or state:
        before = slots()
        while nxt < len(PROMPTS) and PROMPTS[nxt][0] <= t:
            state[nxt] = (0, 0)
            nxt += 1
        done = []
        for k in sorted(state):
            rows, n = state[k]
            plen, most = PROMPTS[k][1], PROMPTS[k][2]
            if rows < plen:
                rows = plen if plen <= CHUNK else min(rows + CHUNK, plen)
                n = 1 if rows == plen else 0
            else:
                n = min(n + K_BLOCK, most)
            state[k] = (rows, n)
            if n == most:
                done.append(SimpleNamespace(rid=k, tokens=list(range(n)),
                                            drive=0))
        for d in done:
            del state[d.rid]
        out.append((t, t + 0.09, before, slots(), done))
        t = round(t + 0.1, 6)
    return out


def _synthetic():
    tr = TR.Trace()
    tr.devices[0] = sorted([
        (10 * MS, 25 * MS, "%fusion.1 = bf16[8] fusion(%x)"),
        (25 * MS, 30 * MS, "%fusion.2 = bf16[8] fusion(%paged_decode.3)"),
        (50 * MS, 60 * MS, "%paged_decode.3 = (f32[8]) custom-call(%y)"),
        (62 * MS, 64 * MS, "%paged_decode.7 = (f32[8]) custom-call(%y)"),
        (95 * MS, 120 * MS, "%fusion.1 = bf16[8] fusion(%x)")])
    tr.host = [(0, 100 * MS, TR.WINDOW)]
    return tr


def _run(trace):
    reqs = [H.Tracked(T.Request(due, np.zeros(p, np.int32), most))
            for due, p, most in PROMPTS]
    for k, r in enumerate(reqs):
        r.key = k
    run = H.Run(spec=SPEC, chips=1, seconds=40.0, peak=V5E, reqs=reqs,
                counts=S.counts(K.REPO, "llama").Counts(SPEC))
    by_key = {r.key: r for r in reqs}
    eng = SimpleNamespace(engines=[None])
    for tb, te, before, after, done in _ticks():
        H._account(run, eng, by_key, [before], [after], done, tb, te, True)
    run.end_t = 3.0
    run.stats0, run.stats1 = dict(STATS0), dict(STATS1)
    run.trace, run.trace_window = trace, trace.window()
    return run


@pytest.mark.parametrize("trace", ["synthetic", "recorded"])
@pytest.mark.parametrize("name", OLD)
def test_existing_reader_reads_as_before(trace, name):
    run = _run(_synthetic() if trace == "synthetic" else K.recorded_trace())
    assert S.metric_reader(K.REPO, name)(run) == BEFORE[trace][name]


def test_scripted_run_counts_the_paged_kernel_per_layer():
    """One call a layer in each decode step with a slot decoding: a tick
    holds as many steps as its busiest slot decoded tokens (its first
    token comes from the prefill, not a step)."""
    steps = 0
    for _, _, before, after, done in _ticks():
        ends = {**after, **{d.rid: (None, len(d.tokens)) for d in done}}
        steps += max([n - before.get(k, (0, 0))[1]
                      - int(before.get(k, (0, 0))[1] == 0)
                      for k, (_, n) in ends.items()] + [0])
    run = _run(_synthetic())
    assert steps > 0
    assert run.traced_kernel_calls == {"paged_decode": 24 * steps}
    assert run.traced_kernel_ideal_s["paged_decode"] > 0


def test_kv_walk_share_reads_the_walk_over_every_page():
    run = _run(_synthetic())
    got = S.metric_reader(K.REPO, "kv_walk_share.steady")(run)
    # 49 steps x 16 slots x 128 pages a slot x 24 layers
    assert got == 100.0 * 39000.0 / (49 * 16 * 128 * 24)
    run.stats1 = dict(STATS0)
    assert S.metric_reader(K.REPO, "kv_walk_share.steady")(run) is None


def test_prefill_device_share_reads_the_modules():
    run = _run(_synthetic())
    read = S.metric_reader(K.REPO, "prefill_device_share.steady")
    assert read(run) is None                   # no engine trace kept
    # busy 10-30, 50-60, 62-64, 95-100 ms: 37 ms; prefill 14, splice 3
    run.engine_trace = SP.EngineTrace(modules={0: [
        (8 * MS, 22 * MS, "prefill"), (22 * MS, 25 * MS, "splice_pages"),
        (25 * MS, 64 * MS, "decode_block")]})
    assert read(run) == pytest.approx(100.0 * 17 / 37)
    run.engine_trace = SP.EngineTrace(modules={0: []})
    assert read(run) is None


def test_engine_stats_sum_every_counter_of_the_drives():
    a = ServeStats(tokens=5, decode_s=1.5, decode_steps=3,
                   live_kv_tokens=700, kv_pages_walked=96,
                   tier_tokens={"x": 5})
    b = ServeStats(tokens=2, decode_s=0.25, decode_steps=1,
                   live_kv_tokens=20, kv_pages_walked=24, shed_requests=1)
    eng = H.Engine.__new__(H.Engine)
    eng.engines = [SimpleNamespace(stats=a), SimpleNamespace(stats=b)]
    tot = eng.stats()
    assert tot["tokens"] == 7.0 and tot["decode_s"] == 1.75
    assert tot["decode_steps"] == 4.0 and tot["live_kv_tokens"] == 720.0
    assert tot["kv_pages_walked"] == 120.0 and tot["shed_requests"] == 1.0
    assert {"tier_tokens", "ledger", "baseline", "latency"}.isdisjoint(tot)
    assert all(isinstance(v, float) for v in tot.values())
