"""Device 0's time a traced step under ``moe_shared`` (the shared expert every token passes through beside the routed ones; forward, recomputation
and backward alike), by the program's ``obs/profile.py:step_scopes()``."""

from benchmark import afmoe_timeline

NAME = "moe_shared_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return afmoe_timeline.scope_ms(run, "moe_shared")
