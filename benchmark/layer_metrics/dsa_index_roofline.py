"""Least time the chip could take for the index scores' work / the device time of
the custom calls under ``dsa_index`` (the forward kernel, its recomputation and
the backward kernel). The work is the family's ``index_kernel_flops`` (forward
over every causal pair, backward over the selected pairs) and
``index_kernel_bytes`` (the float32 scores written once, ``dI`` read once); the
larger bound is taken. The recomputed forward and the backward's dense walk are
time, not work."""

from benchmark import dsa_timeline

NAME = "dsa_index_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return dsa_timeline.kernel_roofline(
        run, "dsa_index", "index_kernel_flops", "index_kernel_bytes"
    )
