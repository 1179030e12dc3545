"""Device 0's time a traced step in the custom calls under ``attn_mla`` (the
grid-pipelined flash kernels at keys of 192 and values of 128: the forward, its
run under remat where the policy does not save it, and the fused backward), by
the program's ``obs/profile.py:step_scopes()``."""

from benchmark import kda_timeline

NAME = "attn_mla_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return kda_timeline.scope_ms(run, "attn_mla", kda_timeline.KERNEL)
