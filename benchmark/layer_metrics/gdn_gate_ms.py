"""Device 0's time a traced step under ``gdn_gate`` (the per-head RMSNorm of the rule's output and its SiLU gate; forward,
recomputation and backward alike), by the program's
``obs/profile.py:step_scopes()``."""

from benchmark import gdn_timeline

NAME = "gdn_gate_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return gdn_timeline.scope_ms(run, "gdn_gate")
