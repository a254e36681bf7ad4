"""End-to-end training driver.

Examples:
  # ~100M-param LM for a few hundred steps on CPU (examples/train_lm.py
  # wraps this with a ready-made config):
  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --smoke \
      --steps 200 --global-batch 8 --seq-len 256 --ckpt-dir /tmp/ckpt

  # production shapes lower through the same builder the dry-run uses.
"""
from __future__ import annotations

import argparse

from repro.config import get_config, reduced_config
from repro.data import DataConfig
from repro.launch.compiles import use_persistent_cache
from repro.launch.elastic import FailureInjector
from repro.train.train_loop import TrainConfig, train


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    print(f"[train] compile cache: {use_persistent_cache()}")
    cfg = reduced_config(args.arch) if args.smoke else get_config(args.arch)
    data_cfg = DataConfig(seq_len=args.seq_len, global_batch=args.global_batch,
                          vocab_size=cfg.vocab_size, seed=args.seed)
    tcfg = TrainConfig(steps=args.steps, lr=args.lr, ckpt_dir=args.ckpt_dir,
                       ckpt_every=args.ckpt_every, log_every=args.log_every,
                       seed=args.seed)

    injector = FailureInjector(None)

    def cb(step, metrics):
        injector.maybe_fail(step)

    state = train(cfg, data_cfg, tcfg, metrics_cb=cb)
    print(f"[train] done at step {state.step}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
