"""Items a step / seconds a step / chips, on the host's clock. Seconds a
step is the harness's (``harness.py:Schedule.step_seconds``): in a window of
one epoch, the median over the stretches of whole steps the device paced,
hooks and planes and the feed's waits inside them, so that a few seconds in
which a neighbour on the host slows the feed do not move it; in a window of
whole epochs, window seconds over whole steps, boundaries and saves and all.
The per-layer ``step_ms`` keeps the mean: what stalls cost is the distance
between the two."""

NAME = "throughput"
UNIT = "items/s/chip"
BETTER = "higher"
SOURCE = "host_clock"


def read(run):
    if not run.step_s:
        return None
    return run.items_per_step / run.step_s / run.chips
