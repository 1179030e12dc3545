"""Device 0's time a traced step under ``sconv_proj`` (the gated short
convolution's in projection ``[B_g | C_g | x~]`` and its out projection;
forward, recomputation and backward alike), by the program's
``obs/profile.py:step_scopes()``."""

from benchmark import lfm2_timeline

NAME = "sconv_proj_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return lfm2_timeline.scope_ms(run, "sconv_proj")
