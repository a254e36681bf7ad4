#!/usr/bin/env python3
"""Where a cell's time to first token goes, from the engine's own stamps.

    python bench/ttft_split.py --workload <cell> --seed <n> --seconds <s> \
        [--trace 1] [--hub 1]

One window of the cell, served and measured as ``bench/run.py`` serves it
(``harness.serve_window``, tracing off), with ``spans.Recorder`` copying
the engine's ``TickObservation`` stamps.  The last line of standard output
is a JSON object: the harness's ``ttft_p50_s``; the medians of each
request's parts, due time to the engine's admission
(``admit_wait_p50_s``), admission to its first token on the host, and the
hold of that token until its tick ends (``first_token_hold_p50_s``); the
widest gap by which the parts miss the harness's TTFT (``closure_max_ms``);
and the decode step (``decode_step_ms``, as ``decode_step_ms.steady``
reads it).  ``--hub 1`` attaches a ``TelemetryHub`` to the engines, to
price it.  ``--trace 1`` also traces the harness's slice of the window
and adds the device time of each program, the share of device busy time
in prefill, splice and chunk (``prefill_device_share``), and the idle time
by the innermost engine span over it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def split(cell, eng, reqs, seconds: float, peak, trace: bool):
    """Serve one window of ``reqs`` and read the engine's stamps; returns
    the harness's ``Run``, the per-request rows of ``spans.ttft_split``,
    and with ``trace`` the readings of the traced slice."""
    from bench.lib import harness as H
    from bench.lib import spans as SP
    from bench.lib import trace as TR
    prof_dir = tempfile.mkdtemp(prefix="ttft_split_") if trace else None
    rec = SP.Recorder(eng, prof_dir, time.perf_counter(),
                      H.TRACE_AT * seconds, H.TRACE_S)
    run = H.serve_window(cell, eng, reqs, seconds, peak, False)
    rec.stop_trace()
    rows = SP.ttft_split(reqs, rec.stamps, SP.window_start(reqs, rec.stamps))
    traced = None
    if trace:
        path = str(sorted(Path(prof_dir).rglob("*.xplane.pb"))[-1])
        tr, et = TR.load(path), SP.load(path)
        shutil.rmtree(prof_dir, ignore_errors=True)
        lo, hi = tr.window()
        traced = {
            "window_s": (hi - lo) / 1e9,
            "program_s": {k: v / 1e9 for k, v in sorted(
                SP.module_ns(et.modules, lo, hi).items(),
                key=lambda kv: -kv[1])},
            "prefill_device_share": SP.module_share(et.modules, tr.devices,
                                                    lo, hi),
            "idle_by_span_s": SP.idle_by_span(tr.devices, et.spans, lo, hi),
            "engine_spans": len(et.spans),
        }
    return run, rows, traced


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--hub", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    from bench.lib import harness as H
    cell = H.Cell.load(ROOT, args.workload)
    peak = H.check_devices(jax.devices(), cell.chips)
    H.cache_dir(ROOT)
    reqs = cell.requests(args.seed, args.seconds)
    eng = cell.engine(args.seed, jax.devices())
    if args.hub:
        from repro.core.telemetry import TelemetryHub
        hub = TelemetryHub()
        for e in [eng.sys, *eng.engines]:
            e.tele = hub
    H.log(f"set-up {time.perf_counter() - T_START:.3f} s")
    run, rows, traced = split(cell, eng, reqs, args.seconds, peak,
                              bool(args.trace))
    for r in sorted(rows, key=lambda r: r["key"]):
        H.log(f"request {r['key']} prompt {r['prompt']}: ttft "
              f"{r['ttft_s']:.4f} s = lateness {r['lateness_s']:.4f} + wait "
              f"{r['admit_wait_s']:.4f} + to first {r['to_first_s']:.4f} + "
              f"hold {r['hold_s']:.4f} (closure "
              f"{1e3 * r['closure_s']:.4f} ms)")
    med = {k: H.pct([r[k] for r in rows], 50) for k in
           ("lateness_s", "admit_wait_s", "to_first_s", "hold_s")}
    steps = run.delta("decode_steps")
    out = {
        "workload": args.workload, "seed": args.seed, "hub": bool(args.hub),
        "device": jax.devices()[0].device_kind,
        "requests": len(reqs), "split": len(rows),
        "ttft_p50_s": H.pct(run.ttft_s(), 50),
        "admit_wait_p50_s": H.pct([r["lateness_s"] + r["admit_wait_s"]
                                   for r in rows], 50),
        "to_first_p50_s": med["to_first_s"],
        "first_token_hold_p50_s": med["hold_s"],
        "lateness_p50_ms": 1e3 * med["lateness_s"],
        "closure_max_ms": 1e3 * max(abs(r["closure_s"]) for r in rows),
        "decode_step_ms": 1e3 * run.delta("decode_s") / steps
        if steps > 0 else None,
        "traced": traced,
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
