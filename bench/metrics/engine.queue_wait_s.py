"""Submit to the tick that gives the request a slot, 90th percentile (s)."""
from bench import readings


def read(run):
    return readings.queue_wait_percentile(run, 90)
