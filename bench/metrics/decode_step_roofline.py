"""Bytes the decode step needs at 819 GB/s, as a share of its device time (%)."""
from bench import readings


def read(run):
    return readings.step_hbm_roofline(run)
