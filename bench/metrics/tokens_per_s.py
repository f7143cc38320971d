"""Output tokens generated inside the window, per token, for requests whose response was received and verified (tokens/s)."""
from bench import readings


def read(run):
    return readings.tokens_per_s(run)
