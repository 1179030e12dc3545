"""Process start -> the window opens: imports, TPU runtime start, host
batches, state build, the first step (compile or cache load), the warm-up
epoch and, in a mix that saves, the warm-up epoch's save."""

NAME = "setup_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    return run.clocks["t_open"] - run.clocks["t_start"]
