"""Device 0's time a traced step under ``moe_combine`` (the un-sort, the routing
weights and the sum over a token's experts; forward, recomputation and backward
alike), by the program's ``obs/profile.py:step_scopes()``."""

from benchmark import moe_timeline

NAME = "moe_combine_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return moe_timeline.scope_ms(run, "moe_combine")
