"""Least time the chip could take for the grouped matmuls' work / their device
time. The work is the family's ``moe_kernel_flops`` and ``moe_kernel_bytes``
(gate, up, down: forward and both gradients, nine grouped matmuls a layer; what
remat computes twice is not counted as work, and is counted as time). At two
thousand rows a group the work is bound by compute (about 510 operations a byte
against the v5e's 240), but the larger of the two bounds is taken whichever it
is."""

from benchmark import reduce_trace

NAME = "moe_kernel_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    kernels = getattr(run.family, "MOE_TRACE_KERNELS", None)
    if not kernels or not run.trace or not run.trace["steps"] or run.peaks is None:
        return None
    seconds = reduce_trace.seconds_matching(run.trace, kernels)
    if not seconds:
        return None
    tokens = run.items_per_step // run.chips * run.trace["steps"]
    least = max(
        run.family.moe_kernel_flops(run.config, tokens) / run.peaks["bf16_flops_per_s"],
        run.family.moe_kernel_bytes(run.config, tokens) / run.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / seconds
