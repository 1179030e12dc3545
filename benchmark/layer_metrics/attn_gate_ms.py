"""Device 0's time a traced step under ``attn_gate`` (the RMSNorms over each head of q and k, and the sigmoid gate on the heads'
outputs with its projection; forward, recomputation
and backward alike), by the program's ``obs/profile.py:step_scopes()``."""

from benchmark import afmoe_timeline

NAME = "attn_gate_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return afmoe_timeline.scope_ms(run, "attn_gate")
