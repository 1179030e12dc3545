"""Device milliseconds a step under ``dsa_target``: the indexer's target: the heads' mean probabilities, the rows' KL, its gradient towards the scores,
all sparse-attention layers."""

from benchmark import dsa_timeline

NAME = "dsa_target_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return dsa_timeline.scope_ms(run, "dsa_target")
