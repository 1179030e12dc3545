"""Device time under the expert layer's three scopes (``moe_route``,
``moe_experts``, ``moe_combine``: forward, recomputation and backward alike) /
device time of the step programs, over the traced steps."""

from benchmark import moe_timeline

NAME = "moe_share"
UNIT = "%"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    seconds = moe_timeline.scope_seconds(run)
    if seconds is None or not run.trace["step_busy_s_total"]:
        return None
    return 100.0 * seconds / run.trace["step_busy_s_total"]
