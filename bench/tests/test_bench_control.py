"""The control of each cell's correctness check, at a size the CPU holds:
the plain reference computed in bfloat16, put in the program's place,
must come out not correct on every seed (its widest gap over the
configuration's limit), while the program itself comes out correct.

The tiny cells serve longer answers than ``tiny.py`` gives them, and the
check samples up to 64 requests, so that each run compares some hundreds
of served tokens, as a run on the chip does."""
import time

import jax
import pytest

from bench import manifest
from bench import run as bench_run
from bench.tests.tiny import tiny_cell

CELLS = [w["name"] for w in manifest.load_manifest()["workloads"]
         if w["chips"] == 1]
SEEDS = (1, 2**31 + 5, 2**33 + 1)


@pytest.mark.parametrize("cell_name", CELLS)
def test_control_is_not_correct(cell_name):
    cell = tiny_cell(cell_name)
    cell.config["correct"]["sample_requests"] = 64
    cell.traffic["output_tokens"] = {
        "published_median": 32 / cell.traffic["length_scale"], "sigma": 0.3,
        "min": 16, "max": 40}
    limit = cell.config["correct"]["max_gap"]
    for seed in SEEDS:
        res = bench_run.run(cell, seed, 4.0, False, jax.devices()[:1], None,
                            t_start=time.perf_counter(), control=True)
        assert res["correct"] is True, (seed, res["checks"])
        assert res["control_gap"] > limit, (seed, res["control_gap"])
