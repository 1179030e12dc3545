"""Device milliseconds a step under ``attn_sparse``: the masked flash kernels (forward once under ``save_flash``, the fused backward),
all sparse-attention layers."""

from benchmark import dsa_timeline

NAME = "attn_sparse_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return dsa_timeline.scope_ms(run, "attn_sparse")
