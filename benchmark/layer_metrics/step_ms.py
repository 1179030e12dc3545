"""Window seconds / whole steps of the window, on the host's clock: the mean
cadence, every stall in it. Against ``step_device_ms`` it shows what the
loop, its planes and the feed add to a step; against the median cadence that
``throughput`` takes (items a step / throughput / chips) it shows what stalls
cost."""

NAME = "step_ms"
UNIT = "ms"
LAYER = "Step loop"
MOVES = "throughput"
SOURCE = "host_clock"


def read(run):
    if not run.window_steps or run.window_s <= 0:
        return None
    return 1e3 * run.window_s / run.window_steps
