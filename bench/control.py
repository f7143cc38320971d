#!/usr/bin/env python3
"""Readings for a cell's correctness limit: the program's widest gap on
many seeds, and the control's (the reference in bfloat16 in the program's
place) on the same prompts and tokens.

    python3 bench/control.py --workload <cell> --seeds 1,2,...,12 --seconds 15

One process, one run of the harness per seed, each with a short window at
the cell's own load. Prints one JSON line per seed, then the lower reading
(largest program gap), the upper reading (smallest control gap) and the
limit the configuration holds. Needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run as bench_run
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(bench_run.CACHE_DIR)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import manifest
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    cell = manifest.find_cell(args.workload)
    devices, peak = bench_run.chips_for(jax, cell.chips, peaks)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        res = bench_run.run(cell, seed, args.seconds, False, devices, peak,
                            t_start=time.perf_counter(), control=True)
        row = {"seed": seed, "correct": res["correct"],
               "max_gap": res["checks"]["max_gap"]["value"],
               "control_gap": res.get("control_gap"),
               "attempted": res["attempted"], "failed": res["failed"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    prog = [r["max_gap"] for r in rows if r["max_gap"] is not None]
    ctl = [r["control_gap"] for r in rows if r["control_gap"] is not None]
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "lower": max(prog) if prog else None,
                      "upper": min(ctl) if ctl else None,
                      "limit": cell.config["correct"]["max_gap"]}), flush=True)


if __name__ == "__main__":
    main()
