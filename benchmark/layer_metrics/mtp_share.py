"""Device time under the multi-token-prediction module's scopes (``mtp``: the
whole module, its block's attention and expert layer inside it; forward,
recomputation and backward alike) / device time of the step programs, over the
traced steps."""

from benchmark import mtp_timeline

NAME = "mtp_share"
UNIT = "%"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    seconds = mtp_timeline.scope_seconds(run)
    if seconds is None or not run.trace["step_busy_s_total"]:
        return None
    return 100.0 * seconds / run.trace["step_busy_s_total"]
