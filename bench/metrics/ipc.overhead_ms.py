"""Client call time minus the service handler call that served it, median: seal, MAC, ring, coalescing and gateway both ways (ms)."""
from bench import readings


def read(run):
    return readings.ipc_overhead_ms(run)
