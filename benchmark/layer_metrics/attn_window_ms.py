"""Device 0's time a traced step under ``attn_window`` (the attention call of the windowed layers: the flash2 kernels that walk
only the blocks inside the window, and the row sums their backward starts from; forward, recomputation
and backward alike), by the program's ``obs/profile.py:step_scopes()``."""

from benchmark import afmoe_timeline

NAME = "attn_window_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return afmoe_timeline.scope_ms(run, "attn_window")
