"""Device time under the gated-delta-rule mixer's four scopes (``gdn_proj``,
``gdn_conv``, ``gdn_scan``, ``gdn_gate``: forward, recomputation and backward
alike) / device time of the step programs, over the traced steps."""

from benchmark import gdn_timeline

NAME = "gdn_share"
UNIT = "%"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    seconds = gdn_timeline.scope_seconds(run)
    if seconds is None or not run.trace["step_busy_s_total"]:
        return None
    return 100.0 * seconds / run.trace["step_busy_s_total"]
