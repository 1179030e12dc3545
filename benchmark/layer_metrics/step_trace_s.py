"""Seconds of the program's ``jit_trace`` spans inside the first ``first_step``:
tracing the step's Python to a jaxpr. Every Pallas kernel's body is traced
here, at every start (the ring's ``kernel_trace`` spans count them)."""

from benchmark import startup_timeline

NAME = "step_trace_s"
UNIT = "s"
LAYER = "Compile / cache"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return startup_timeline.first_step_phase_s(run, "jit_trace")
