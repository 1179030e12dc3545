"""Median ``step_dispatch`` span of the window: what one call of the jitted
step costs the host. Long when the runtime's queue is full."""

from benchmark import timeline

NAME = "dispatch_ms"
UNIT = "ms"
LAYER = "Step loop"
MOVES = "throughput"
SOURCE = "program_span"


def read(run):
    return timeline.median_span_ms(run, "step_dispatch")
