"""Seconds of the program's ``jit_compile`` spans inside the first
``first_step``: the step's backend compile, which is key hashing and a
persistent-cache load on a hit and XLA on a miss (``cache_misses`` beside it
says which; the ring's ``cache_load`` span is the read alone)."""

from benchmark import startup_timeline

NAME = "step_compile_s"
UNIT = "s"
LAYER = "Compile / cache"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return startup_timeline.first_step_phase_s(run, "jit_compile")
