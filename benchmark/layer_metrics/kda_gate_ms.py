"""Device 0's time a traced step under ``kda_gate`` (the per-head RMSNorm of the
rule's output and its sigmoid gate; forward, recomputation and backward alike),
by the program's ``obs/profile.py:step_scopes()``."""

from benchmark import kda_timeline

NAME = "kda_gate_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return kda_timeline.scope_ms(run, "kda_gate")
