"""The step loop's self time a step: the window's ``train_step`` spans less
their children (``data_wait``, ``step_dispatch``, ``numerics_fetch``), over the
window's whole steps. What is left is the plane hooks (numerics buffer, memory
census, step telemetry, heartbeat, capture controller) and the loop's own
Python: the host half of what ROADMAP S3 asks about."""

from benchmark import timeline

NAME = "loop_self_ms"
UNIT = "ms"
LAYER = "Step loop"
MOVES = "throughput"
SOURCE = "program_span"


def read(run):
    steps = timeline.window_events(run, "train_step")
    if not steps or not run.window_steps:
        return None
    if not timeline.window_events(run, "step_dispatch"):
        return None  # a program without the child spans: nothing to take off
    children = sum(
        e["dur"]
        for name in ("data_wait", "step_dispatch", "numerics_fetch")
        for e in timeline.window_events(run, name)
    )
    return (sum(e["dur"] for e in steps) - children) / 1e3 / run.window_steps
