"""Least time the chip could take for the work of attention over the selection /
the device time of the custom calls under ``attn_sparse``. The work is the
family's ``sparse_kernel_flops`` (over the SELECTED pairs only: two matrix
multiplications forward, five backward) and ``sparse_kernel_bytes``; the larger
bound is taken (compute, at head_dim 128). The kernels compute every causal tile
whole under the mask, so what the selection empties inside a tile is time and
not work, as the window's reader counts: at 23.4% of the causal pairs selected
this share cannot pass about a quarter of what the dense kernels reach."""

from benchmark import dsa_timeline

NAME = "attn_sparse_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return dsa_timeline.kernel_roofline(
        run, "attn_sparse", "sparse_kernel_flops", "sparse_kernel_bytes"
    )
