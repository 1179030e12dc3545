"""Device time under the Mamba-2 mixer's four scopes (``ssm_proj``,
``ssm_conv``, ``ssm_scan``, ``ssm_gate``: forward, recomputation and backward
alike) / device time of the step programs, over the traced steps."""

from benchmark import ssm_timeline

NAME = "ssm_share"
UNIT = "%"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    seconds = ssm_timeline.scope_seconds(run)
    if seconds is None or not run.trace["step_busy_s_total"]:
        return None
    return 100.0 * seconds / run.trace["step_busy_s_total"]
