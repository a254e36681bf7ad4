"""Telemetry tier: the opt-in hub, its instrumentation sites, and the
exporters.

Pure hub tests (fast-marked) cover the keyed-span lifecycle
(double-open/double-close counted, never raised), the bounded event
ring, histograms, detection-latency bookkeeping, and the Chrome-trace
structure through ``scripts/trace_report.py`` — the same checks a
Perfetto import would trip over.

Engine-backed tests assert the honesty contracts: tracing changes no
token (greedy decode with the hub attached is identical to the
untraced oracle), every request span closes exactly once under
retry/hedge/shed/cancel, each track's events stay monotone on its own
clock, and a scheduled crash yields a finite detection latency for
BOTH health authorities (virtual-clock detector and heartbeat
watchdog)."""
import dataclasses
import importlib.util
import json
import math
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.config import reduced_config
from repro.core.faults import DEAD, HEALTHY, FailureDetector, FaultSchedule
from repro.core.runtime import HeartbeatWatchdog
from repro.core.telemetry import NULL_HUB, NullHub, TelemetryHub
from repro.models import model as M
from repro.train.cluster_loop import ClusterEngine
from repro.train.serve_loop import ServeEngine

MAX_LEN = 64
REPO = Path(__file__).resolve().parents[1]


def _trace_report():
    """scripts/ is not a package; load the report tool by path."""
    spec = importlib.util.spec_from_file_location(
        "trace_report", REPO / "scripts" / "trace_report.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# pure: the hub itself
# ---------------------------------------------------------------------------


@pytest.mark.fast
def test_null_hub_is_disabled_and_cheap():
    assert NULL_HUB.enabled is False
    assert isinstance(NULL_HUB, NullHub)
    t0 = time.perf_counter()
    for i in range(100_000):
        if NULL_HUB.enabled:        # the call-site guard pattern
            NULL_HUB.counter("x")
            NULL_HUB.point("t", "n", 0.0, a=i)
    guarded = time.perf_counter() - t0
    # the guarded disabled path is one attribute check per site; even a
    # loaded CI box does 100k of those in well under a second
    assert guarded < 1.0


@pytest.mark.fast
def test_span_lifecycle_double_open_and_double_close_are_counted():
    hub = TelemetryHub()
    hub.open_request(7, 1.0, priority=0)
    assert hub.open_span_count() == 1
    hub.open_request(7, 1.5)            # double open: original kept
    hub.request_point(7, "admit", 2.0, tier="interactive")
    hub.close_request(7, 3.0, "ok", tokens=4)
    hub.close_request(7, 3.5, "ok")     # double close: counted, dropped
    assert hub.open_span_count() == 0
    m = hub.metrics()
    assert m["counters"]["spans.ok"] == 1
    assert m["counters"]["telemetry.span_double_open"] == 1
    assert m["counters"]["telemetry.span_double_close"] == 1
    phases = [e for e in hub.events() if e["ev"] == "phase"]
    assert len(phases) == 1
    (ph,) = phases
    assert ph["name"] == "req7" and ph["t"] == 1.0 and ph["dur"] == 2.0
    # close merges the open attrs with the close attrs plus status
    assert ph["attrs"]["priority"] == 0
    assert ph["attrs"]["tokens"] == 4
    assert ph["attrs"]["status"] == "ok"


@pytest.mark.fast
def test_event_ring_is_bounded_and_drops_are_counted():
    hub = TelemetryHub(capacity=8)
    for i in range(20):
        hub.point("t", "p", float(i))
    assert len(hub.events()) == 8
    assert hub.events_dropped == 12
    assert [e["t"] for e in hub.events()] == [float(i) for i in range(12, 20)]
    with pytest.raises(ValueError, match="capacity"):
        TelemetryHub(capacity=0)


@pytest.mark.fast
def test_histograms_bucket_and_aggregate():
    hub = TelemetryHub()
    for v in (0.0005, 0.002, 0.002, 0.5, 100.0):
        hub.observe("tick_busy_s", v)
    h = hub.metrics()["histograms"]["tick_busy_s"]
    assert h["count"] == 5
    assert h["sum"] == pytest.approx(100.5045)
    assert sum(h["counts"]) == 5
    assert h["counts"][0] == 1          # <= 1ms
    assert h["counts"][1] == 2          # <= 3ms
    assert h["counts"][-1] == 1         # > 30s overflow bin


@pytest.mark.fast
def test_detection_latency_first_transition_per_authority_wins():
    hub = TelemetryHub()
    hub.fault_injected(1, "crash", 2.0, tick=4)
    hub.fault_injected(1, "stall", 9.0, tick=8)    # first injection wins
    hub.health_transition("detector", 1, "healthy", "suspect", 2.5)
    hub.health_transition("detector", 1, "suspect", "dead", 3.25)
    hub.health_transition("detector", 1, "suspect", "dead", 9.0)  # ignored
    hub.health_transition("watchdog", 1, "healthy", "dead", 4.0)
    hub.health_transition("watchdog", 0, "healthy", "suspect", 5.0)  # no inj
    det = hub.metrics()["detection_latency"]
    assert det["detector.drive1"]["kind"] == "crash"
    assert det["detector.drive1"]["suspect_s"] == pytest.approx(0.5)
    assert det["detector.drive1"]["dead_s"] == pytest.approx(1.25)
    assert det["watchdog.drive1"]["dead_s"] == pytest.approx(2.0)
    assert "watchdog.drive0" not in det    # no injection, no latency


@pytest.mark.fast
def test_chrome_trace_structure_loads_through_trace_report(tmp_path):
    hub = TelemetryHub()
    hub.open_request(0, 0.1, priority=1)
    hub.close_request(0, 0.6, "ok", tokens=3)
    hub.phase("drive0", "decode", 0.2, 0.3, steps=2)
    hub.point("coordinator", "fault_injected", 0.4, drive=1)
    hub.counter_sample("coordinator", "queue_depth", 0.5, 2)
    doc = hub.to_chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    named = {e["args"]["name"] for e in meta}
    assert named == {"coordinator", "drive0", "requests"}
    # coordinator is always pid 1 so traces line up across runs
    coord = [e for e in meta if e["args"]["name"] == "coordinator"]
    assert all(e["pid"] == 1 for e in coord)
    # timestamps are microseconds
    x = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in x} == {"req0", "decode"}
    req = next(e for e in x if e["name"] == "req0")
    assert req["ts"] == pytest.approx(0.1e6)
    assert req["dur"] == pytest.approx(0.5e6)

    path = tmp_path / "trace.json"
    hub.write_chrome_trace(str(path))
    tr = _trace_report()
    events = tr.load_trace(str(path))
    names = tr.track_names(events)
    assert set(names.values()) == {"coordinator", "drive0", "requests"}
    agg = tr.phase_breakdown(events)
    assert sum(n for n, _ in agg.values()) == 2
    slow = tr.slowest_requests(events, names, top=5)
    assert [e["name"] for e in slow] == ["req0"]
    assert tr.main([str(path), "--top", "3"]) == 0


@pytest.mark.fast
def test_trace_report_rejects_malformed_traces(tmp_path):
    tr = _trace_report()
    bad_phase = tmp_path / "bad_phase.json"
    bad_phase.write_text(json.dumps(
        {"traceEvents": [{"ph": "Q", "pid": 1, "tid": 0, "ts": 0,
                          "name": "x"}]}))
    with pytest.raises(ValueError, match="unknown phase"):
        tr.load_trace(str(bad_phase))
    bad_dur = tmp_path / "bad_dur.json"
    bad_dur.write_text(json.dumps(
        {"traceEvents": [{"ph": "X", "pid": 1, "tid": 0, "ts": 0,
                          "dur": -1.0, "name": "x"}]}))
    with pytest.raises(ValueError, match="bad dur"):
        tr.load_trace(str(bad_dur))
    nan_ts = tmp_path / "nan_ts.json"
    nan_ts.write_text('{"traceEvents": [{"ph": "i", "pid": 1, "tid": 0, '
                      '"ts": NaN, "name": "x"}]}')
    with pytest.raises(ValueError, match="bad ts"):
        tr.load_trace(str(nan_ts))
    assert tr.main([str(bad_phase)]) == 1
    assert tr.main([str(tmp_path / "missing.json")]) == 1


@pytest.mark.fast
def test_hub_is_thread_safe_under_concurrent_writers():
    hub = TelemetryHub(capacity=100_000)
    n, per = 8, 500

    def writer(w):
        for i in range(per):
            hub.counter("hits")
            hub.open_span(("w", w, i), float(i), f"t{w}", f"s{i}")
            hub.close_span(("w", w, i), float(i) + 0.5, "ok")

    threads = [threading.Thread(target=writer, args=(w,)) for w in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    m = hub.metrics()
    assert m["counters"]["hits"] == n * per
    assert m["counters"]["spans.ok"] == n * per
    assert m["open_spans"] == 0
    assert m["counters"].get("telemetry.span_double_close", 0) == 0


# ---------------------------------------------------------------------------
# engine-backed: instrumentation honesty
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(reduced_config("yi-9b"), dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return M.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def ref_k1(cfg, params):
    """k_block=1 oracle/donor: one decode step per tick, so injected
    faults land mid-flight deterministically."""
    return ServeEngine(cfg, params, max_len=MAX_LEN, num_slots=2, k_block=1,
                       prewarm=True)


@pytest.fixture(scope="module")
def trace(cfg, ref_k1):
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (5, 9, 7, 11)]
    want = [r.tokens for r in ref_k1.generate(prompts, max_new=6)]
    return prompts, want


def _engine(cfg, params, ref, **kw):
    return ServeEngine(cfg, params, jit_donor=ref, max_len=ref.max_len,
                       num_slots=ref.num_slots, k_block=1, **kw)


def _cluster(cfg, params, ref, **kw):
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("num_slots", 2)
    kw.setdefault("k_block", 1)
    kw.setdefault("routing", "round_robin")
    return ClusterEngine(cfg, params, jit_donor=ref, **kw)


def _assert_track_monotone(events):
    """Per track, events are emitted in the order of their stamps on that
    track's own clock: a point or counter at its time, a phase at its end
    (a phase is emitted when it closes, so a span that encloses others
    closes after them).  Request spans are exempt: overlapping requests
    close out of order by design."""
    last: dict = {}
    for e in events:
        track = e["track"]
        if track in ("requests", "orphans"):
            continue
        t = e["t"] + e.get("dur", 0.0)
        assert t >= last.get(track, -math.inf) - 1e-9, \
            f"track {track} went backwards: {e}"
        last[track] = t


def test_engine_tracing_is_token_identical_and_closes_every_span(
        cfg, params, ref_k1, trace):
    prompts, want = trace
    hub = TelemetryHub()
    eng = _engine(cfg, params, ref_k1, telemetry=hub)
    got = [r.tokens for r in eng.generate(prompts, max_new=6)]
    assert got == want                  # `want` came from an untraced engine
    m = hub.metrics()
    assert m["counters"]["spans.ok"] == len(prompts)
    assert m["counters"].get("telemetry.span_double_close", 0) == 0
    assert m["open_spans"] == 0
    names = {e["name"] for e in hub.events()}
    assert {"serve.tick", "serve.admit", "serve.prefill",
            "serve.decode"} <= names
    # first_token precedes every request close
    assert any(e["ev"] == "point" and e["name"] == "first_token"
               for e in hub.events())
    _assert_track_monotone(hub.events())
    # engine tick metrics landed in the registry
    assert m["counters"]["engine.ticks"] > 0
    assert m["counters"]["engine.tokens"] == eng.stats.tokens
    assert m["histograms"]["tick_busy_s"]["count"] > 0


def test_engine_shed_and_cancel_close_spans_exactly_once(cfg, params,
                                                         ref_k1, trace):
    prompts, _ = trace
    hub = TelemetryHub()
    eng = _engine(cfg, params, ref_k1, telemetry=hub)
    # fill both slots so the doomed requests wait in the queue
    rids_ok = [eng.submit(prompts[0], max_new=4),
               eng.submit(prompts[1], max_new=4)]
    rid_shed = eng.submit(prompts[2], max_new=4, deadline_s=1e-9)
    rid_cancel = eng.submit(prompts[3], max_new=4)
    assert eng.cancel(rid_cancel) == 0.0    # still queued: nothing burned
    while eng.queue or eng.num_active:
        eng.step()
    m = hub.metrics()
    assert m["counters"]["spans.ok"] == len(rids_ok)
    assert m["counters"]["spans.shed"] == 1
    assert m["counters"]["spans.canceled"] == 1
    assert m["counters"].get("telemetry.span_double_close", 0) == 0
    assert m["open_spans"] == 0
    shed_phase = next(e for e in hub.events() if e["ev"] == "phase"
                      and e["attrs"].get("status") == "shed")
    assert shed_phase["attrs"]["rid"] == rid_shed
    assert eng.stats.shed_requests == 1


def test_serial_cluster_crash_records_detector_latency_and_retry(
        cfg, params, ref_k1, trace):
    prompts, want = trace
    hub = TelemetryHub()
    faults = FaultSchedule.from_spec(
        [{"drive_id": 1, "kind": "crash", "at_tick": 3}])
    det = FailureDetector(2, suspect_ticks=2, dead_ticks=4,
                          suspect_after_s=math.inf)
    clu = _cluster(cfg, params, ref_k1, n_drives=2, faults=faults,
                   detector=det, telemetry=hub)
    rids = [clu.submit(p, max_new=6) for p in prompts]
    res = {r.rid: r for r in clu.run_until_complete()}
    assert sorted(res) == rids
    assert [res[r].tokens for r in rids] == want
    assert clu.stats.health == [HEALTHY, DEAD]

    m = hub.metrics()
    lat = m["detection_latency"]["detector.drive1"]
    assert lat["kind"] == "crash"
    # the crash is hidden; detection needs silent ticks, so the latency is
    # strictly positive and SUSPECT precedes DEAD on the cluster wall
    assert 0.0 < lat["suspect_s"] <= lat["dead_s"]
    assert math.isfinite(lat["dead_s"])
    # every request span closed ok despite the mid-flight retries
    assert m["counters"]["spans.ok"] == len(rids)
    assert m["counters"].get("telemetry.span_double_close", 0) == 0
    assert m["open_spans"] == 0
    assert m["counters"]["cluster.retries"] == clu.stats.retries > 0
    assert m["counters"]["cluster.drive_failures"] == 1
    retry_pts = [e for e in hub.events()
                 if e["ev"] == "point" and e["name"] == "retry"]
    assert retry_pts and all("from_drive" in e["attrs"] for e in retry_pts)
    _assert_track_monotone(hub.events())
    # per-drive utilization gauges exist and are sane
    for d in (0, 1):
        u = m["gauges"][f"drive.{d}.utilization"]
        assert 0.0 <= u and math.isfinite(u)


def test_concurrent_cluster_crash_records_watchdog_latency_and_valid_trace(
        cfg, params, ref_k1, trace, tmp_path):
    prompts, want = trace
    hub = TelemetryHub()
    faults = FaultSchedule.from_spec(
        [{"drive_id": 1, "kind": "crash", "at_tick": 2}])
    clu = _cluster(cfg, params, ref_k1, n_drives=2, concurrent=True,
                   prewarm=True, faults=faults, max_retries=5,
                   dispatch_timeout_s=0.05, telemetry=hub,
                   watchdog=HeartbeatWatchdog(2, suspect_after_s=0.06,
                                              suspect_misses=3,
                                              dead_after_s=0.5,
                                              dead_misses=60))
    try:
        rids = [clu.submit(p, max_new=6) for p in prompts]
        res = {r.rid: r for r in clu.run_until_complete()}
        assert sorted(res) == rids
        for rid, w in zip(rids, want):
            if res[rid].status == "ok":
                assert res[rid].tokens == w
        assert clu.stats.health[1] == DEAD
    finally:
        clu.close()
    assert not [t for t in threading.enumerate()
                if t.name.startswith("drive-worker-")]

    m = hub.metrics()
    lat = m["detection_latency"]["watchdog.drive1"]
    assert lat["kind"] == "crash"
    assert math.isfinite(lat["dead_s"]) and lat["dead_s"] > 0.0
    if "suspect_s" in lat:              # watchdog may jump straight to DEAD
        assert 0.0 <= lat["suspect_s"] <= lat["dead_s"]
    assert m["open_spans"] == 0
    assert m["counters"].get("telemetry.span_double_close", 0) == 0
    _assert_track_monotone(hub.events())
    # worker heartbeats made it onto the worker tracks, and the crashed
    # worker annotated its own exit
    tracks = {e["track"] for e in hub.events()}
    assert {"worker0", "worker1", "coordinator"} <= tracks
    assert any(e["name"] == "worker_exit" and e["track"] == "worker1"
               for e in hub.events())

    path = tmp_path / "trace.json"
    hub.write_chrome_trace(str(path))
    tr = _trace_report()
    events = tr.load_trace(str(path))
    names = tr.track_names(events)
    assert "requests" in names.values() and "coordinator" in names.values()
    assert tr.main([str(path)]) == 0


def test_hedge_span_settles_exactly_once_with_waste_attr(cfg, params,
                                                         ref_k1, trace):
    prompts, want = trace
    hub = TelemetryHub()
    # the stall outlives the run: the hedged copy must win, the stalled
    # loser is canceled and its burned time booked as hedge waste
    faults = FaultSchedule.from_spec(
        [{"drive_id": 1, "kind": "stall", "at_tick": 2, "duration": 10000}])
    det = FailureDetector(2, suspect_ticks=2, dead_ticks=10 ** 6,
                          suspect_after_s=math.inf)
    clu = _cluster(cfg, params, ref_k1, n_drives=2, faults=faults,
                   detector=det, hedge=True, telemetry=hub)
    rids = [clu.submit(p, max_new=6) for p in prompts[:2]]
    for _ in range(400):
        clu.step()
        if all(r in {x.rid for x in clu._finished} for r in rids):
            break
    got = {r.rid: r for r in clu._finished}
    assert sorted(got) == rids
    assert [got[r].tokens for r in rids] == want[:2]
    assert clu.stats.hedges >= 1 and clu.stats.hedges_won >= 1
    assert clu._hedges == {}

    m = hub.metrics()
    assert m["counters"]["cluster.hedges"] == clu.stats.hedges
    assert m["open_spans"] == 0         # hedge spans settled, none leaked
    hedge_phases = [e for e in hub.events() if e["ev"] == "phase"
                    and e["name"].startswith("hedge")]
    assert len(hedge_phases) == clu.stats.hedges
    # the winner's span closed "ok"; the loser's copy was canceled and the
    # span carries the booked waste either way
    assert all("hedge_wasted_s" in e["attrs"] for e in hedge_phases)
    assert any(e["attrs"]["status"] == "ok" for e in hedge_phases)


@pytest.mark.parametrize("tracer", ["hub", "profiler"])
def test_tracing_on_equals_tracing_off(cfg, params, ref_k1, trace, tracer,
                                       tmp_path):
    """The whole-point gate: attaching the hub, or running the engine
    under a profiler session, changes no token."""
    prompts, want = trace
    eng_off = _engine(cfg, params, ref_k1)
    assert eng_off.tele is NULL_HUB and not eng_off.tele.enabled
    off = [r.tokens for r in eng_off.generate(prompts, max_new=6)]
    if tracer == "hub":
        eng_on = _engine(cfg, params, ref_k1, telemetry=TelemetryHub())
        on = [r.tokens for r in eng_on.generate(prompts, max_new=6)]
    else:
        eng_on = _engine(cfg, params, ref_k1)
        jax.profiler.start_trace(str(tmp_path))
        try:
            on = [r.tokens for r in eng_on.generate(prompts, max_new=6)]
        finally:
            jax.profiler.stop_trace()
        assert list(tmp_path.rglob("*.xplane.pb"))
    assert on == off == want


@pytest.mark.parametrize("system", ["engine", "cluster"])
def test_engine_track_events_fall_inside_the_call(cfg, params, ref_k1,
                                                  trace, system):
    """Engine and drive tracks stamp the wall clock: every event on them
    lies inside the ``perf_counter`` interval of the ``generate()`` call
    that made it (the virtual serving clock starts at 0 and would not)."""
    prompts, want = trace
    hub = TelemetryHub()
    if system == "engine":
        eng, tracks = _engine(cfg, params, ref_k1, telemetry=hub), {"engine"}
    else:
        eng = _cluster(cfg, params, ref_k1, n_drives=2, telemetry=hub)
        tracks = {"drive0", "drive1", "cluster"}
    n0 = len(hub.events())             # set-up's own page uploads
    t0 = time.perf_counter()
    got = [r.tokens for r in eng.generate(prompts, max_new=6)]
    t1 = time.perf_counter()
    assert got == want
    wall = [e for e in hub.events()[n0:] if e["track"] in tracks]
    assert {e["track"] for e in wall} == tracks
    assert {e["name"] for e in wall} >= {"serve.tick", "serve.prefill",
                                          "serve.decode"}
    for e in wall:
        assert t0 <= e["t"] <= e["t"] + e.get("dur", 0.0) <= t1, e
    if system == "cluster":
        ticks = [e for e in wall if e["name"] == "cluster.tick"]
        drive_ticks = [e for e in wall if e["name"] == "serve.tick"]
        assert ticks and drive_ticks
        # every drive tick nests inside a cluster tick
        for d in drive_ticks:
            assert any(c["t"] <= d["t"] and d["t"] + d["dur"]
                       <= c["t"] + c["dur"] for c in ticks), d
    _assert_track_monotone(hub.events())


def _host_spans(path):
    """(name, start_ns, end_ns, stats) of the ``serve.*`` events of every
    host plane in one xplane file."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return out


def test_profiler_spans_nest_in_the_tick_with_live_kv_counter(cfg, params,
                                                              tmp_path):
    """One tick of the reduced model under a CPU profiler session writes
    ``serve.tick`` with ``serve.admit``, ``serve.prefill`` and
    ``serve.decode_block`` nested inside it, and the block's
    ``live_kv_tokens`` stat equals the engine's own count of the rows its
    replayed steps attended."""
    eng = ServeEngine(cfg, params, max_len=MAX_LEN, num_slots=2, k_block=4,
                      bucket_quantum=16, prewarm=True)
    rng = np.random.default_rng(3)
    for n in (5, 9):
        eng.submit(rng.integers(0, cfg.vocab_size, n).tolist(), max_new=6)
    live0 = eng.stats.live_kv_tokens
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
    finally:
        jax.profiler.stop_trace()
    obs = eng.last_tick
    assert obs.steps == 4 and len(obs.first_token_rids) == 2
    # 2 slots x 4 steps, contexts 5+1..5+4 and 9+1..9+4
    assert eng.stats.live_kv_tokens - live0 == (6 + 7 + 8 + 9) \
        + (10 + 11 + 12 + 13)
    (path,) = tmp_path.rglob("*.xplane.pb")
    spans = _host_spans(path)
    ticks = [s for s in spans if s[0] == "serve.tick"]
    assert len(ticks) == 1
    _, lo, hi, _ = ticks[0]
    inside = {n for n, s, e, _ in spans if lo <= s <= e <= hi}
    assert {"serve.admit", "serve.prefill", "serve.decode_block",
            "serve.replay", "serve.pages"} <= inside
    (block,) = [st for n, _, _, st in spans if n == "serve.decode_block"]
    assert block["live_kv_tokens"] == eng.stats.live_kv_tokens - live0
    assert block["steps"] == 4 and block["live_slots"] == 2
    assert block["kv_pages_in_use"] == eng.pager.num_in_use
    (pre,) = [st for n, _, _, st in spans if n == "serve.prefill"]
    assert pre["rows"] == 2 and pre["tokens"] == 14
    (adm,) = [st for n, _, _, st in spans if n == "serve.admit"]
    assert adm["admitted"] == 2 and adm["queued"] == 2
    # the tick's wall stamps: slot given, first token on the host, tick end
    assert len(obs.admitted_at) == len(obs.first_token_at) == 2
    assert max(obs.admitted_at) <= min(obs.first_token_at) \
        <= max(obs.first_token_at) <= obs.ended_at
