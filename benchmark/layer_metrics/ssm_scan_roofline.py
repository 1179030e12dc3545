"""Least time the chip could take for the state-space scans' work / the
device time under ``ssm_scan``. The work is the family's ``ssm_scan_flops``
(the chunked form's four matmuls, forward and backward, causal half inside a
chunk) and ``ssm_scan_bytes`` (x, dt, B, C and y once each way, and their
gradients); what remat computes twice is not counted as work, and is counted as
time. At the published widths the two bounds are near each other (about 220
operations a byte against the v5e's 240), so the larger is taken whichever it
is. The scan is plain XLA, not one kernel, so the time is the scope's."""

from benchmark import ssm_timeline

NAME = "ssm_scan_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    flops = getattr(run.family, "ssm_scan_flops", None)
    if flops is None or run.peaks is None:
        return None
    seconds = ssm_timeline.scope_seconds(run, ("ssm_scan",))
    if not seconds:
        return None
    tokens = run.items_per_step // run.chips * run.trace["steps"]
    least = max(
        flops(run.config, tokens) / run.peaks["bf16_flops_per_s"],
        run.family.ssm_scan_bytes(run.config, tokens) / run.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / seconds
