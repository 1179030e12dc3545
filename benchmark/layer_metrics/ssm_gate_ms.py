"""Device 0's time a traced step under ``ssm_gate`` (the scan's output times silu(z) and the RMSNorm over all of d_inner; forward, recomputation
and backward alike), by the program's ``obs/profile.py:step_scopes()``."""

from benchmark import ssm_timeline

NAME = "ssm_gate_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return ssm_timeline.scope_ms(run, "ssm_gate")
