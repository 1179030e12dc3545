"""Least time the chip could take for the work of the windowed layers' flash kernels / the device
time of the custom calls under ``attn_window``. The work is the family's
``kind_kernel_flops`` (two matrix multiplications over the visible (query,
key) pairs forward, five backward, which recomputes the scores) and
``kind_kernel_bytes`` (q, k, v, dO and the results once each way, bfloat16);
the larger of the two bounds is taken: at head_dim 128 the kernels are bound by
compute. Tiles the mask empties in part are time, not work."""

from benchmark import afmoe_timeline

NAME = "attn_window_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return afmoe_timeline.kernel_roofline(run, "attn_window", windowed=True)
