"""Median ``seconds_per_step`` of the window's ``step_retired`` marks: the
program's own step time, between two moments its loop knew a numbered step
had retired on the device (a numerics fetch, the epoch's sync), over the steps
between them. What ``edl_train_step_seconds`` observes. It should equal items
a step / ``throughput`` / chips, which the harness takes from outside."""

import statistics

from benchmark import timeline

NAME = "host_step_ms"
UNIT = "ms"
LAYER = "Step loop"
MOVES = "throughput"
SOURCE = "program_counter"


def read(run):
    marks = [
        e["args"]["seconds_per_step"]
        for e in timeline.window_events(run, "step_retired", ph="i")
        if "seconds_per_step" in e["args"]
    ]
    return 1e3 * statistics.median(marks) if marks else None
