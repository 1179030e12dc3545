"""Device milliseconds a step under ``dsa_select``: the selection: the bisection kernel, the mask's elementwise pass and the two gauges' reductions,
all sparse-attention layers."""

from benchmark import dsa_timeline

NAME = "dsa_select_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return dsa_timeline.scope_ms(run, "dsa_select")
