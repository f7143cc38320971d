"""Device time of one execution of the jitted decode step, from the profiler trace (ms)."""
from bench import readings


def read(run):
    return None if (s := readings.step_device_s(run)) is None else 1e3 * s
