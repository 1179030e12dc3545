"""Least time the chip could take for the selection / the device time of the
custom call under ``dsa_select`` (the bisection kernel). The selection is bound
by HBM on paper: its work is the family's ``select_bytes``, one read of the
float32 scores of every causal pair; the 32 counting passes over the row block
in VMEM are the time."""

from benchmark import dsa_timeline

NAME = "dsa_select_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return dsa_timeline.kernel_roofline(run, "dsa_select", None, "select_bytes")
