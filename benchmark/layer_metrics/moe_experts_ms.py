"""Device 0's time a traced step under ``moe_experts`` (the gather into expert
order and the three grouped matmuls; forward, recomputation and backward
alike), by the program's ``obs/profile.py:step_scopes()``."""

from benchmark import moe_timeline

NAME = "moe_experts_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return moe_timeline.scope_ms(run, "moe_experts")
