"""Device 0's time a traced step under ``gdn_conv`` (the three short convolutions as one: the ``causal_conv_fwd`` / ``causal_conv_bwd`` kernels over the in projection's leading columns; forward,
recomputation and backward alike), by the program's
``obs/profile.py:step_scopes()``."""

from benchmark import gdn_timeline

NAME = "gdn_conv_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return gdn_timeline.scope_ms(run, "gdn_conv")
