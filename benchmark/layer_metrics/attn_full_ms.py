"""Device 0's time a traced step under ``attn_full`` (the attention call of the full-attention layers of a model that also has
windowed ones: the flash2 kernels over the whole causal triangle; forward, recomputation
and backward alike), by the program's ``obs/profile.py:step_scopes()``."""

from benchmark import afmoe_timeline

NAME = "attn_full_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return afmoe_timeline.scope_ms(run, "attn_full")
