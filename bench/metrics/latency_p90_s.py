"""90th percentile of latency, scheduled send to verified response, of the requests due in the window (s)."""
from bench import readings


def read(run):
    return readings.latency_percentile(run, 90)
