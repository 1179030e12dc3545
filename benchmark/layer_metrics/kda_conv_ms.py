"""Device 0's time a traced step under ``kda_conv`` (the three causal depthwise
convolutions of 4 taps with their SiLU, on q, k and v; forward, recomputation
and backward alike), by the program's ``obs/profile.py:step_scopes()``."""

from benchmark import kda_timeline

NAME = "kda_conv_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return kda_timeline.scope_ms(run, "kda_conv")
