"""Model FLOPs of the tokens the window's ticks processed (bench/counts_hybrid_moe.py) over the window times the chips' bf16 peak (%)."""
from bench import counts_hybrid_moe, readings


def read(run):
    ticks = readings.window_ticks(run)
    if not ticks or run.peaks is None:
        return None
    flops = sum(counts_hybrid_moe.step_counts(run.model, t.positions)[0]
                for t in ticks)
    return 100.0 * flops / (run.seconds * run.peaks["bf16_flops_per_s"]
                            * run.chips)
