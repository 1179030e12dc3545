"""Device milliseconds a step under ``dsa_index``: the indexer's projections, norm and rotation and the index scores' kernels (forward, recomputed, backward),
all sparse-attention layers."""

from benchmark import dsa_timeline

NAME = "dsa_index_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return dsa_timeline.scope_ms(run, "dsa_index")
