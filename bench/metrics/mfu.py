"""Model FLOPs of the tokens the window's ticks processed over the window times the chips' bf16 peak (%)."""
from bench import readings


def read(run):
    return readings.mfu(run)
