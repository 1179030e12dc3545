"""The program's ``process_boot`` span: the OS's start of the process -> the
first statement of ``edl_tpu/__init__.py`` (the interpreter, ``site`` and what
the entry point imported before the program: in ``run.py``, ``import jax``)."""

from benchmark import startup_timeline

NAME = "boot_process_s"
UNIT = "s"
LAYER = "Worker boot"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    boot = startup_timeline.first_span(run, "process_boot")
    return None if boot is None else boot["dur"] / 1e6
