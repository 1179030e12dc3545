"""Device 0's time a traced step under ``gdn_scan`` (the gated delta rule: the L2 norms, beta, the log-decay, a chunk's system and its blockwise inverse, the products of a chunk, the carry from chunk to chunk; forward,
recomputation and backward alike), by the program's
``obs/profile.py:step_scopes()``."""

from benchmark import gdn_timeline

NAME = "gdn_scan_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return gdn_timeline.scope_ms(run, "gdn_scan")
