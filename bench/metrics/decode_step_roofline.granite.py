"""Bytes the hybrid step with held experts needs (bench/counts_hybrid_moe.py) at 819 GB/s, as a share of its device time (%)."""
import numpy as np

from bench import counts_hybrid_moe, readings


def read(run):
    dev = readings.step_device_s(run)
    ticks = readings.window_ticks(run, run.trace_host_window) \
        if run.trace_host_window else []
    if dev is None or not ticks or run.peaks is None:
        return None
    nbytes = np.mean([counts_hybrid_moe.step_counts(run.model, t.positions)[1]
                      for t in ticks])
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / dev
