"""Host time of one ServingEngine.tick, mean over the window and the engines; it ends in the argmax copy, so it holds the device step (ms)."""
from bench import readings


def read(run):
    return readings.tick_ms(run)
