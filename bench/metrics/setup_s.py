"""Process start to the window's start: weights, engine, compile or cache load, warm-up and pre-roll (s)."""
from bench import readings


def read(run):
    return run.setup_s
