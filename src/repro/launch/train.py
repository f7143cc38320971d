"""Training launcher.

On this CPU container it drives reduced (smoke) configs end-to-end through
the production Trainer — microbatching, checkpointing, failure injection,
straggler telemetry. On a real pod the same driver runs the full configs:
pass --full to lower the assigned architecture at its production size
(requires TPU devices; the 512-way compile-only path is launch/dryrun.py).

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --steps 200 \\
      --ckpt-dir /tmp/ckpt --fail-at 80
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.configs import (ARCH_IDS, OptimizerConfig, TrainConfig, get_config,
                           get_reduced)
from repro.models.transformer import Impl
from repro.runtime import FailureInjector, Trainer
from repro.utils import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=list(ARCH_IDS))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--micro", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--fail-at", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", action="store_true",
                    help="full production config (TPU pods; CPU smoke uses "
                         "the reduced twin)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch) if args.full else get_reduced(args.arch)
    print(f"arch={cfg.name} params≈{cfg.param_count()/1e6:.1f}M "
          f"({'full' if args.full else 'reduced smoke'})")

    tcfg = TrainConfig(
        microbatch_size=args.micro, dtype="float32" if not args.full else "bfloat16",
        optimizer=OptimizerConfig(lr=args.lr, warmup_steps=max(2, args.steps // 20),
                                  total_steps=args.steps, weight_decay=0.01),
        log_every=max(1, args.steps // 20),
        checkpoint_every=max(10, args.steps // 5), seed=args.seed)

    injector = FailureInjector({args.fail_at: ["host1"]} if args.fail_at else {})
    trainer = Trainer(cfg, tcfg, global_batch=args.batch, seq_len=args.seq,
                      checkpoint_dir=args.ckpt_dir,
                      impl=Impl(attention="chunked", q_chunk=64, kv_chunk=64,
                                remat=False),
                      workers=[f"host{i}" for i in range(4)], injector=injector)
    report = trainer.run(args.steps)

    first = np.mean(report.losses[:5])
    last = np.mean(report.losses[-5:])
    print(f"\nloss {first:.4f} → {last:.4f} | steps {report.steps_run} | "
          f"restarts {report.restarts} | stragglers {report.stragglers} | "
          f"guard trips {report.guard_trips}")
    for e in report.events:
        print("event:", e)


if __name__ == "__main__":
    main()
