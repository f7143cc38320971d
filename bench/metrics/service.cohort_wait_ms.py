"""Engine retiring a request to the service handler returning it, median: the wait for the rest of a coalesced cohort (ms)."""
from bench import readings


def read(run):
    return readings.cohort_wait_ms(run)
