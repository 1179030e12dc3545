"""How far the seconds a step moved through the window: the window's
``step_retired`` marks in order (each the device's pace over the steps since
the mark before it), the median ``seconds_per_step`` of the later half over
that of the earlier half, less one, in percent. 0 for a window of one pace;
what a window's median step hides when its steps grow or shrink through it.
Fewer than two paced marks, or a program without them, gives nothing to read."""

import statistics

from benchmark import timeline

NAME = "step_time_drift"
UNIT = "%"
BETTER = "lower"
LAYER = "Step loop"
MOVES = "throughput"
SOURCE = "program_counter"


def read(run):
    marks = sorted(
        timeline.window_events(run, "step_retired", ph="i"), key=lambda e: e["ts"]
    )
    paced = [
        e["args"]["seconds_per_step"] for e in marks
        if "seconds_per_step" in e.get("args", {})
    ]
    if len(paced) < 2:
        return None
    half = len(paced) // 2
    earlier, later = statistics.median(paced[:half]), statistics.median(paced[half:])
    if earlier <= 0:
        return None
    return 100.0 * (later / earlier - 1.0)
