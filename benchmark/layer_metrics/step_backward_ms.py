"""Device 0's time a traced step in the backward pass, recomputation and the
half-batch gradient mean in it (``transpose(jvp(forward))``, ``grad_mean``), by
the program's ``obs/profile.py:step_phases()``; a fusion counts where its root
does."""

from benchmark import timeline

NAME = "step_backward_ms"
UNIT = "ms"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return timeline.phase_ms(run, "backward")
