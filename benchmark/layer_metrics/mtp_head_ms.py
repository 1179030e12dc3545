"""Device 0's time a traced step under ``mtp_head`` (the module's last norm, the
head's second use and its cross-entropy; forward and
backward alike), by the program's ``obs/profile.py:step_scopes()``."""

from benchmark import mtp_timeline

NAME = "mtp_head_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return mtp_timeline.scope_ms(run, "mtp_head")
