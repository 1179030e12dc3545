"""Device 0's time a traced step under ``ssm_conv`` (the causal depthwise convolution over x, B and C and its silu; forward, recomputation
and backward alike), by the program's ``obs/profile.py:step_scopes()``."""

from benchmark import ssm_timeline

NAME = "ssm_conv_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return ssm_timeline.scope_ms(run, "ssm_conv")
