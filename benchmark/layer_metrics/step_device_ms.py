"""Device time of one step program: the time an operation ran on device 0
inside the program's event (not the event's length, which holds the stalls in
which a program waits for an input transfer), median over the traced steps."""

NAME = "step_device_ms"
UNIT = "ms"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return run.trace["step_busy_ms_median"] if run.trace else None
