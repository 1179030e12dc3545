"""Median gap on device 0 between one step program's last operation and the
next one's first, over the traced steps."""

NAME = "step_gap_ms"
UNIT = "ms"
LAYER = "Step loop"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return run.trace["step_gap_ms_median"] if run.trace else None
