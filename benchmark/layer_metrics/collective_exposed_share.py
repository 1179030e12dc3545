"""Device 0: time in collective operations during which no other operation runs
/ device time of the step programs (the time an operation ran inside them).
Nothing to read on one chip, or where the step holds no collective."""

NAME = "collective_exposed_share"
UNIT = "%"
LAYER = "Parallel"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    if run.chips == 1 or not run.trace or not run.trace["collective_s"]:
        return None
    return 100.0 * run.trace["collective_exposed_s"] / run.trace["step_busy_s_total"]
