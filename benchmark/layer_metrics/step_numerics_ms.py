"""Device 0's time a traced step in the numerics plane's bundle (``numerics``),
by the program's ``obs/profile.py:step_phases()``; a fusion counts where its
root does. The device half of what ROADMAP S3 asks about."""

from benchmark import timeline

NAME = "step_numerics_ms"
UNIT = "ms"
LAYER = "Step loop"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return timeline.phase_ms(run, "numerics")
