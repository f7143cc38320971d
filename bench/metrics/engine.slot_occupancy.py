"""Share of the slots holding a request, mean over the window's ticks and the replicas (%)."""
from bench import readings


def read(run):
    return readings.slot_occupancy(run)
