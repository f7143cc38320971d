"""Slot given to first token, from the engine's own stamps on each request, 90th percentile over the requests whose first token came in the window: the ticks that feed the prompt (s)."""
import numpy as np


def read(run):
    vals = []
    for a in run.probe.admitted.values():
        r = a.request
        first = getattr(r, "first_token_at", 0.0)   # a program without the stamps has none
        if first and run.window[0] <= first < run.window[1]:
            vals.append(first - r.admitted_at)
    return float(np.percentile(vals, 90)) if vals else None
