"""Device time under the Kimi-delta-attention mixer's four scopes (``kda_proj``,
``kda_conv``, ``kda_scan``, ``kda_gate``: forward, recomputation and backward
alike) / device time of the step programs, over the traced steps."""

from benchmark import kda_timeline

NAME = "kda_share"
UNIT = "%"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    seconds = kda_timeline.scope_seconds(run)
    if seconds is None or not run.trace["step_busy_s_total"]:
        return None
    return 100.0 * seconds / run.trace["step_busy_s_total"]
