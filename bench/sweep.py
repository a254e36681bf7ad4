#!/usr/bin/env python3
"""Find a cell's knee: offer its traffic at several fixed rates, one after
the other, to one engine built once, and print what each rate achieved.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 0.5,1,1.5,2

One JSON line per rate: requests offered per second, output tokens per
second completed in the window, TTFT and TPOT tails, and how many requests
were still waiting for a slot when the window closed.  The knee is the
highest rate at which the output rate keeps up with the offered one and
the queue at the close stays short; the cells' fixed rates in their mix
files were set from such a sweep (see PERF.md).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import jax
    from bench.lib import harness as H
    cell = H.Cell.load(ROOT, args.workload)
    peak = H.check_devices(jax.devices(), cell.chips)
    H.cache_dir(ROOT)
    eng = cell.engine(args.seed, jax.devices())
    H.log(f"set-up {time.perf_counter() - T_START:.3f} s")
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, arrival=dict(cell.mix["arrival"],
                                          rate_per_s=rate))
        reqs = cell.requests(args.seed + i, args.seconds, mix)
        run = H.serve_window(cell, eng, reqs, args.seconds, peak, False)
        tpot = run.tpot_s()
        print(json.dumps({
            "rate_per_s": rate, "requests": len(reqs),
            "out_tok_s": run.window_tokens / args.seconds,
            "offered_tok_s": sum(r.req.max_new for r in reqs) / args.seconds,
            "ttft_p50_s": H.pct(run.ttft_s(), 50),
            "ttft_p90_s": H.pct(run.ttft_s(), 90),
            "tpot_p90_ms": H.pct(tpot, 90) * 1e3 if tpot else None,
            "waiting_at_close": sum(1 for r in reqs
                                    if not r.admit_t <= args.seconds),
            "unfinished": sum(1 for r in reqs if r.tokens is None),
            "decode_step_ms": 1e3 * run.delta("decode_s")
            / max(run.delta("decode_steps"), 1),
            "prefill_share": run.delta("prefill_s") / max(
                run.delta("prefill_s") + run.delta("decode_s"), 1e-9),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
