"""Device time under the sparse-attention layer's four scopes (``dsa_index``,
``dsa_select``, ``attn_sparse``, ``dsa_target``: forward, recomputation and
backward alike) / device time of the step programs, over the traced steps."""

from benchmark import dsa_timeline

NAME = "dsa_share"
UNIT = "%"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    seconds = dsa_timeline.scope_seconds(run)
    if seconds is None or not run.trace["step_busy_s_total"]:
        return None
    return 100.0 * seconds / run.trace["step_busy_s_total"]
