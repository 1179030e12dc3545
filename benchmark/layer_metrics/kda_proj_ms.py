"""Device 0's time a traced step under ``kda_proj`` (the KDA mixer's projections:
q, k, v, the decay's, the gate's, beta's and the out projection; forward,
recomputation and backward alike), by the program's
``obs/profile.py:step_scopes()``."""

from benchmark import kda_timeline

NAME = "kda_proj_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return kda_timeline.scope_ms(run, "kda_proj")
