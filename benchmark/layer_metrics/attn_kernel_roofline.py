"""Least time the chip could take for the flash kernels' work / their device
time. The work is the family's ``kernel_flops`` (causal forward 2*B*H*T^2*D,
backward 2.5x that); at head_dim 128 and T = 4096 the kernels are bound by
compute, not by HBM, so the least time is operations / peak bfloat16 rate."""

from benchmark import reduce_trace

NAME = "attn_kernel_roofline"
UNIT = "%"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    kernels = getattr(run.family, "TRACE_KERNELS", None)
    if not kernels or not run.trace or not run.trace["steps"] or run.peaks is None:
        return None
    seconds = reduce_trace.seconds_matching(run.trace, kernels)
    if not seconds:
        return None
    sequences = run.config["train"]["batch_per_chip"] * run.trace["steps"]
    flops = run.family.kernel_flops(run.config, sequences)
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / seconds
