"""Device 0's time a traced step under ``sconv_conv`` (the two gates and the
depthwise causal taps between them, ``ops/causal_conv.py:gated_causal_conv``;
forward, recomputation and backward alike), by the program's
``obs/profile.py:step_scopes()``."""

from benchmark import lfm2_timeline

NAME = "sconv_conv_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return lfm2_timeline.scope_ms(run, "sconv_conv")
