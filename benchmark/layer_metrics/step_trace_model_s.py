"""Seconds of the program's outermost ``model_trace`` spans inside the first
``first_step`` less the ``kernel_trace`` inside them: the model's own Python
under the step's trace. With the ``kernel_trace`` seconds it splits
``step_trace_s`` into the model, the kernels' bodies and jax's own work."""

from benchmark import setup_timeline

NAME = "step_trace_model_s"
UNIT = "s"
LAYER = "Compile / cache"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return setup_timeline.model_trace_s(run)
