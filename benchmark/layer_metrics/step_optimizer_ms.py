"""Device 0's time a traced step in the optimizer's update (``optimizer``), by
the program's ``obs/profile.py:step_phases()``; a fusion counts where its root
does."""

from benchmark import timeline

NAME = "step_optimizer_ms"
UNIT = "ms"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return timeline.phase_ms(run, "optimizer")
