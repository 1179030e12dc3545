"""Device time in the family's kernels (``TRACE_KERNELS``: for the LM the
flash forward, dq and dkv kernels) / device time of the step programs (the
time an operation ran inside them), over the traced steps."""

from benchmark import reduce_trace

NAME = "attn_kernel_share"
UNIT = "%"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    kernels = getattr(run.family, "TRACE_KERNELS", None)
    if not kernels or not run.trace or not run.trace["step_busy_s_total"]:
        return None
    seconds = reduce_trace.seconds_matching(run.trace, kernels)
    if seconds is None:
        return None
    return 100.0 * seconds / run.trace["step_busy_s_total"]
