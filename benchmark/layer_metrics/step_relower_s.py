"""The program's ``step_relower`` span: after the first step the step is lowered
and compiled a second time, for the cost model, the memory plan and the phase
table. Set-up time outside ``first_step``; milliseconds where jax's in-process
caches hand the trace, the lowering and the executable back (PR 33: 4-66 ms)."""

from benchmark import startup_timeline

NAME = "step_relower_s"
UNIT = "s"
LAYER = "Compile / cache"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    span = startup_timeline.first_span(run, "step_relower")
    return None if span is None else span["dur"] / 1e6
