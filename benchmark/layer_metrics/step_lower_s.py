"""Seconds of the program's ``jit_lower`` spans inside the first ``first_step``:
lowering the step's jaxpr to StableHLO. Every Pallas kernel's body is lowered
to Mosaic here, at every start."""

from benchmark import startup_timeline

NAME = "step_lower_s"
UNIT = "s"
LAYER = "Compile / cache"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return startup_timeline.first_step_phase_s(run, "jit_lower")
