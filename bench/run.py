#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, their configurations, traffic mixes and metrics are named in
``BENCHMARK.json``.  With ``--trace 0`` the last line of standard output
is a JSON object with the cell's end-to-end metrics; with ``--trace 1``
with its per-layer metrics, read from a profiler trace of a few seconds of
the window.  Either way the run ends by comparing a sample of the served
requests with the plain float32 reference (``correct``), and prints each
number compared beside its limit as its last lines on standard error and
as the last key of the result.

Where JAX finds no TPU, fewer chips than the cell asks for, or a device
kind missing from the peak table, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.lib import harness as H
    from bench.lib import spec as S
    cell = S.cell(S.benchmark(ROOT), args.workload)
    import jax
    try:
        devices = jax.devices()
        peak = H.check_devices(devices, int(cell["chips"]))
    except Exception as e:                    # no TPU, too few, unknown kind
        H.log(f"refused: {e}")
        return 2
    H.cache_dir(ROOT)
    result = H.run_cell(ROOT, args.workload, args.seed, args.seconds,
                        bool(args.trace), devices, peak, T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
