"""The engine's own spans and stamps, read beside the harness's.

The served program marks its work with profiler spans (``serve.*`` in
``ServeEngine``, ``cluster.tick`` in ``ClusterEngine``; see
``repro.core.telemetry.span``) and stamps, on ``time.perf_counter``, when
each request got its slot, when its first token reached the host and when
the tick that returns it ended (``TickObservation``).  This module reads
both, without touching what ``trace.load`` and the harness read:

* ``load`` takes from an ``.xplane.pb`` the engine spans of the host planes
  and, per device, each execution of an XLA module from the device's ``XLA
  Modules`` line (a TPU v5e trace has one).  Each program's module is named
  after the function ``jax.jit`` wrapped (``jit_decode_block(12)`` ->
  ``decode_block``).
* ``Recorder`` wraps an engine's ``step`` and the harness engine's
  ``submit`` for one served window, keeps the stamps of every request, and
  can trace a slice of the window the way the harness does.
* ``ttft_split`` cuts each request's TTFT into generator lateness,
  admission wait, admission to first token, and the hold of the first
  token until its tick ends; ``idle_by_span`` sums a device's idle time by
  the innermost engine span over it; ``module_share`` is the share of the
  device's busy time spent in prefill, splice and chunk.
"""
from __future__ import annotations

import math
import re
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from bench.lib import trace as TR

Span = Tuple[int, int, str, Dict]       # (start_ns, end_ns, name, stats)

ENGINE_SPAN = re.compile(r"^(serve|cluster)\.")
MODULE_LINE = "XLA Modules"
PREFILL_PROGRAMS = ("prefill", "splice_pages", "prefill_chunk")
OUTSIDE = "outside any engine span"


@dataclass
class EngineTrace:
    spans: List[Span] = field(default_factory=list)
    # per device: (start_ns, end_ns, program) of each module execution
    modules: Dict[int, List[TR.Event]] = field(default_factory=dict)


def program(module: str) -> str:
    """A module's program: ``jit_decode_block(12)`` -> ``decode_block``."""
    name = module.split("(", 1)[0].strip()
    return name[4:] if name.startswith("jit_") else name


def load(path: str) -> EngineTrace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = EngineTrace()
    for plane in data.planes:
        m = TR.DEVICE_PLANE.match(plane.name)
        if m:
            out.modules[int(m.group(1))] = sorted(
                (int(e.start_ns), int(e.start_ns + e.duration_ns),
                 program(e.name))
                for ln in plane.lines if ln.name == MODULE_LINE
                for e in ln.events)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                out.spans.extend(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns),
                     e.name, dict(e.stats))
                    for e in ln.events if ENGINE_SPAN.match(e.name))
    out.spans.sort(key=lambda s: (s[0], -s[1]))
    return out


# -- readings ----------------------------------------------------------------


def module_ns(modules: Dict[int, List[TR.Event]], lo: int, hi: int
              ) -> Dict[str, float]:
    """Device time of each program inside [lo, hi), averaged over the
    devices, in ns."""
    tot: Dict[str, float] = {}
    for events in modules.values():
        for s, e, name in TR._clip(events, lo, hi):
            tot[name] = tot.get(name, 0) + (e - s)
    k = max(len(modules), 1)
    return {name: ns / k for name, ns in tot.items()}


def module_share(modules: Dict[int, List[TR.Event]],
                 devices: Dict[int, List[TR.Event]], lo: int, hi: int
                 ) -> Optional[float]:
    """Device time in the prefill programs (``PREFILL_PROGRAMS``) over
    device busy time in [lo, hi) (%); None where the slice holds no
    device work or no module is known."""
    busy = sum(TR.busy_ns(ev, lo, hi) for ev in devices.values())
    busy /= max(len(devices), 1)
    if busy <= 0 or not any(modules.values()):
        return None
    t = module_ns(modules, lo, hi)
    return 100.0 * sum(t.get(p, 0.0) for p in PREFILL_PROGRAMS) / busy


def idle_by_span(devices: Dict[int, List[TR.Event]], spans: List[Span],
                 lo: int, hi: int) -> Dict[str, float]:
    """Idle time of the devices in [lo, hi), each stretch put down to the
    innermost engine span over it (the latest to start of those covering
    it), averaged over the devices, in seconds."""
    tot: Dict[str, float] = {}
    for events in devices.values():
        t = lo
        for s, e in TR.busy_intervals(events, lo, hi) + [(hi, hi)]:
            if s > t:
                _attribute(t, s, spans, tot)
            t = max(t, e)
    k = max(len(devices), 1)
    return {name: ns / k / 1e9 for name, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])}


def _attribute(a: int, b: int, spans: List[Span], tot: Dict[str, float]
               ) -> None:
    over = [sp for sp in spans if sp[0] < b and sp[1] > a]
    cuts = sorted({a, b} | {x for sp in over for x in sp[:2] if a < x < b})
    for x, y in zip(cuts, cuts[1:]):
        name = OUTSIDE
        for s, e, n, _ in over:          # by start: the last is innermost
            if s <= x and e >= y:
                name = n
        tot[name] = tot.get(name, 0) + (y - x)


@dataclass
class Stamps:
    """One request's engine stamps, on ``time.perf_counter``."""
    submit: float = math.nan            # the harness called submit
    admit: float = math.nan             # the engine gave it a slot
    first: float = math.nan             # its first token reached the host
    end: float = math.nan               # the tick that returns it ended


def ttft_split(reqs, stamps: Dict[int, Stamps], t0: float) -> List[Dict]:
    """Per request with a first token: its harness TTFT (due time to the
    end of the tick in which the harness saw the token) and its parts,
    in seconds: generator lateness (due to submit), admission wait
    (submit to slot), admission to first token on the host, and the hold
    (first token to the end of its tick).  ``t0`` is the harness's start
    of the window on ``perf_counter``; ``closure`` is what the parts
    leave of the TTFT."""
    rows = []
    for r in reqs:
        st = stamps.get(r.key)
        if st is None or not math.isfinite(r.first_t) \
                or not math.isfinite(st.first):
            continue
        parts = {"lateness_s": r.submit_t - r.req.due_s,
                 "admit_wait_s": st.admit - t0 - r.submit_t,
                 "to_first_s": st.first - st.admit,
                 "hold_s": st.end - st.first}
        ttft = r.first_t - r.req.due_s
        rows.append(dict(key=r.key, prompt=len(r.req.prompt), ttft_s=ttft,
                         closure_s=ttft - sum(parts.values()), **parts))
    return rows


def window_start(reqs, stamps: Dict[int, Stamps]) -> float:
    """The harness's window start on ``perf_counter``: each submit stamp
    less the harness's own submit time (relative to the window) bounds it
    from above; the tightest bound is within microseconds of it."""
    return min(stamps[r.key].submit - r.submit_t for r in reqs
               if r.key in stamps)


class Recorder:
    """Stamps of every request of one served window, from the engine.

    Wraps ``eng.sys.step`` and ``eng.submit`` (``eng`` a harness
    ``Engine``) with functions that copy each tick's ``last_tick`` stamps:
    a ``ServeEngine``'s, or a ``ClusterEngine``'s, which holds its drives'
    stamps under global rids and ends where the cluster's tick ends.  With
    ``trace_dir`` it also traces the slice ``[at, at + length]`` seconds
    after ``start`` in whole ticks, in a ``bench.window`` span, as the
    harness's ``--trace 1`` does; the slice closes at the first tick that
    starts past its end, or at ``stop_trace``.
    """

    def __init__(self, eng, trace_dir: Optional[str] = None,
                 start: float = 0.0, at: float = 0.0, length: float = 0.0):
        self.eng = eng
        self.stamps: Dict[int, Stamps] = {}
        self.trace_dir = trace_dir
        self._trace_from = start + at
        self._trace_len = length
        self._trace_lo = math.nan
        self._window = None
        self.traced = False
        self._step, self._submit = eng.sys.step, eng.submit
        eng.sys.step = self.step
        eng.submit = self.submit

    def submit(self, req) -> int:
        t = time.perf_counter()
        key = self._submit(req)
        self.stamps[key] = Stamps(submit=t)
        return key

    def _stamp(self, key: int, attr: str, t: float) -> None:
        st = self.stamps.get(key)
        if st is not None and math.isnan(getattr(st, attr)):
            setattr(st, attr, t)

    def step(self):
        # the profiler starts and stops before a tick, never between a
        # tick's end and the harness's reading of it
        now = time.perf_counter()
        if self._window is not None:
            if now >= self._trace_lo + self._trace_len:
                self.stop_trace()
        elif self.trace_dir and not self.traced and now >= self._trace_from:
            import jax
            jax.profiler.start_trace(self.trace_dir)
            self._window = jax.profiler.TraceAnnotation(TR.WINDOW)
            self._window.__enter__()
            self._trace_lo = time.perf_counter()
        done = self._step()
        obs = self.eng.sys.last_tick
        for rid, t in zip(obs.admitted_rids, obs.admitted_at):
            self._stamp(rid, "admit", t)
        for rid, t in zip(obs.first_token_rids, obs.first_token_at):
            self._stamp(rid, "first", t)
            self._stamp(rid, "end", obs.ended_at)
        return done

    def stop_trace(self) -> None:
        """End the traced slice, if it is still open."""
        if self._window is None:
            return
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._window, self.traced = None, True
