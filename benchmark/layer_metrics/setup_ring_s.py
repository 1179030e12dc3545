"""The program's own reading of a start: ``process_boot``'s start (the OS's
start of the process) -> the end of the last span of epoch 0 on that thread, in
the run the other set-up readers read."""

from benchmark import setup_timeline

NAME = "setup_ring_s"
UNIT = "s"
LAYER = "Worker boot"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return setup_timeline.ring_s(run)
