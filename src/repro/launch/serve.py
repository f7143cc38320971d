"""Serving launcher: continuous-batching decode over a fixed slot grid.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b --requests 12
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.models import init_params
from repro.models.transformer import Impl
from repro.runtime import Request, ServingEngine
from repro.utils import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=list(ARCH_IDS))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=96)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--greedy", action="store_true", default=True)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch) if args.full else get_reduced(args.arch)
    if cfg.swa_window is not None and args.max_seq > cfg.swa_window:
        args.max_seq = cfg.swa_window
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_batch=args.max_batch,
                        max_seq=args.max_seq,
                        impl=Impl(attention="naive", ssd="chunked", remat=False))

    t0 = time.perf_counter()
    for i in range(args.requests):
        prompt = [(13 * i + j) % cfg.vocab_size for j in range(3 + i % 4)]
        eng.submit(Request(rid=i, prompt=prompt, max_new=args.max_new))
    done = eng.run_until_drained()
    wall = time.perf_counter() - t0

    total = sum(len(r.generated) for r in done)
    for r in sorted(done, key=lambda r: r.rid)[:8]:
        print(f"req {r.rid:2d}: prompt={len(r.prompt)} new={len(r.generated)} "
              f"latency={(r.finished_at - r.submitted_at)*1e3:7.1f} ms")
    print(f"\n{len(done)} requests | {total} tokens | {eng.ticks} ticks | "
          f"{wall:.2f}s | {total/wall:.1f} tok/s")


if __name__ == "__main__":
    main()
