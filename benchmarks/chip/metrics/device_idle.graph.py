"""Share of the traced window of a graph cell in which no operation
ran on the device (profiler trace; chips averaged), in %."""
from benchmarks.chip.readers import device_idle


def read(ctx):
    return device_idle(ctx)
