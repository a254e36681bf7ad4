#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from.

    python bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 [--control]

For each seed, in one process: the weights and traffic of that seed, a
window of ``seconds`` at the cell's own load with a full drain, and the
sample of finished requests that a run compares.  It prints one JSON line
per seed with the program's widest gap against the float32 reference
(the lower reading) and, with ``--control``, the widest gap of the
control: the reference computed with fp8 projections on the same prompts
and served tokens (the upper reading).  The limit in the configuration
file lies between the largest lower reading and the smallest upper one;
PERF.md records both.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    import jax
    from bench.lib import harness as H
    cell = H.Cell.load(ROOT, args.workload)
    peak = H.check_devices(jax.devices(), cell.chips)
    H.cache_dir(ROOT)
    n = int(cell.spec["check"]["requests"])
    for seed in (int(s) for s in args.seeds.split(",")):
        reqs = cell.requests(seed, args.seconds)
        eng = cell.engine(seed, jax.devices())
        H.serve_window(cell, eng, reqs, args.seconds, peak, False)
        del eng
        gc.collect()
        done = [r for r in reqs if r.tokens is not None]
        pick = H.sample(done, seed, cell.chips, n,
                        int(cell.spec["engine"]["chunk_prefill"]))
        out = {"seed": seed, "finished": len(done), "sampled": len(pick),
               "served_tokens": sum(len(r.tokens) for r in pick),
               "program": H.widest_gap(ROOT, cell.spec, seed, pick)}
        if args.control:
            out["control"] = H.widest_gap(ROOT, cell.spec, seed, pick, "fp8")
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
