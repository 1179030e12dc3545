"""Device 0's time a traced step under ``mtp_join`` (the module's two norms, the
concatenation and the joined projection ``[2 hidden, hidden]``; forward and
backward alike), by the program's ``obs/profile.py:step_scopes()``."""

from benchmark import mtp_timeline

NAME = "mtp_join_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return mtp_timeline.scope_ms(run, "mtp_join")
