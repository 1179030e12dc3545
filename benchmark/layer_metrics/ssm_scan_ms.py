"""Device 0's time a traced step under ``ssm_scan`` (the chunked state-space scan: decay matrices, the four matmuls of a chunk, the carry from chunk to chunk; forward, recomputation
and backward alike), by the program's ``obs/profile.py:step_scopes()``."""

from benchmark import ssm_timeline

NAME = "ssm_scan_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return ssm_timeline.scope_ms(run, "ssm_scan")
