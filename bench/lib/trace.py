"""Reduction of a profiler trace to device metrics.

``load`` reads the ``.xplane.pb`` the JAX profiler writes: for each TPU
device plane the operations of its "XLA Ops" line, and from the host
planes the spans the harness opened (names starting ``bench.``).  Both are
on the profiler's one clock, in nanoseconds.

An event's name is its HLO instruction (``%paged_decode.9 = ... custom-
call(...)``); ``op`` cuts it to the instruction's own name, so a kernel is
matched by that and never by an operand that names it.  Events nest (a
``while`` loop's event spans its body's events), so the top operations are
ranked by self time, their duration less that of the events inside them.

The traced window is the harness's ``bench.window`` span.  Within it:
busy time is the union of a device's operation intervals; a kernel's time
is the sum of its events' durations; an idle gap is a stretch with no
operation on the device, named after the host span that covers its middle.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Event = Tuple[int, int, str]            # (start_ns, end_ns, name)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
WINDOW = "bench.window"


@dataclass
class Trace:
    devices: Dict[int, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)

    def window(self) -> Tuple[int, int]:
        spans = [e for e in self.host if e[2] == WINDOW]
        if not spans:
            raise ValueError(f"trace holds no {WINDOW!r} span")
        return spans[0][0], spans[0][1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = [ln for ln in plane.lines if ln.name == OP_LINE]
            tr.devices[int(m.group(1))] = sorted(
                (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                for ln in ops for e in ln.events)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                tr.host.extend(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                    for e in ln.events if e.name.startswith("bench."))
    tr.host.sort()
    return tr


def op(name: str) -> str:
    """The instruction's own name: ``%paged_decode.9 = ...`` -> paged_decode.9"""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _clip(events: List[Event], lo: int, hi: int) -> List[Event]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def busy_intervals(events: List[Event], lo: int, hi: int
                   ) -> List[Tuple[int, int]]:
    """Union of the operation intervals inside [lo, hi)."""
    out: List[List[int]] = []
    for s, e, _ in sorted(_clip(events, lo, hi)):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: List[Event], lo: int, hi: int) -> int:
    return sum(e - s for s, e in busy_intervals(events, lo, hi))


def _is(kernel: str, name: str) -> bool:
    o = op(name)
    return o == kernel or o.startswith(kernel + ".")


def kernel_ns(events: List[Event], kernel: str, lo: int, hi: int) -> int:
    """Device time of the kernel's events (instructions named ``kernel`` or
    ``kernel.<n>``)."""
    return sum(e - s for s, e, n in _clip(events, lo, hi) if _is(kernel, n))


def kernel_count(events: List[Event], kernel: str, lo: int, hi: int) -> int:
    return sum(1 for _, _, n in _clip(events, lo, hi) if _is(kernel, n))


def self_ns(events: List[Event]) -> List[Tuple[str, int]]:
    """(op name, self time) of each event: its duration less the events
    nested inside it."""
    out: List[List] = []
    stack: List[int] = []
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= min(e, out[stack[-1]][2]) - s
        out.append([op(name), e - s, e])
        stack.append(len(out) - 1)
    return [(n, t) for n, t, _ in out]


def top_ops(tr: Trace, lo: int, hi: int, n: int = 10
            ) -> List[List]:
    """The operations that took most device self time, summed by name over
    the devices and averaged over them, in seconds."""
    tot: Dict[str, int] = {}
    for events in tr.devices.values():
        for name, t in self_ns(_clip(events, lo, hi)):
            tot[name] = tot.get(name, 0) + t
    k = max(len(tr.devices), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in best]


def _host_at(tr: Trace, t: int) -> str:
    """The innermost harness span (latest to start) covering time t."""
    name: Optional[str] = None
    for s, e, n in tr.host:
        if s > t:
            break
        if e >= t and n != WINDOW:
            name = n
    return name or "outside any harness span"


def idle_gaps(tr: Trace, lo: int, hi: int, n: int = 10) -> List[List]:
    """The longest stretches with no operation on a device, each named by
    what the host was doing in its middle, in seconds."""
    gaps = []
    for events in tr.devices.values():
        t = lo
        for s, e in busy_intervals(events, lo, hi) + [(hi, hi)]:
            if s > t:
                gaps.append((s - t, _host_at(tr, (s + t) // 2)))
            t = max(t, e)
    gaps.sort(key=lambda g: -g[0])
    return [[name, ns / 1e9] for ns, name in gaps[:n]]
