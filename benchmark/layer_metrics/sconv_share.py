"""Device time under the gated short convolution's two scopes (``sconv_proj``,
``sconv_conv``: forward, recomputation and backward alike) / device time of
the step programs, over the traced steps."""

from benchmark import lfm2_timeline

NAME = "sconv_share"
UNIT = "%"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    seconds = lfm2_timeline.scope_seconds(run)
    if seconds is None or not run.trace["step_busy_s_total"]:
        return None
    return 100.0 * seconds / run.trace["step_busy_s_total"]
