"""Device 0's time a traced step under ``kda_scan`` (the Kimi delta rule: the L2
norms, beta, the safe gate, a chunk's decayed keys and queries, its system and
blockwise inverse, the products of a chunk, the carry from chunk to chunk;
forward, recomputation and backward alike), by the program's
``obs/profile.py:step_scopes()``."""

from benchmark import kda_timeline

NAME = "kda_scan_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return kda_timeline.scope_ms(run, "kda_scan")
