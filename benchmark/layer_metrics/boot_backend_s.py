"""Seconds of the program's ``backend_init`` spans before the first
``train_setup``, one a platform: the TPU runtime's start (and the CPU
client's), whoever asked for a device first."""

from benchmark import setup_timeline, startup_timeline

NAME = "boot_backend_s"
UNIT = "s"
LAYER = "Worker boot"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    boot = setup_timeline.before_train_setup(run)
    if boot is None or startup_timeline.first_span(run, "backend_init") is None:
        return None
    return setup_timeline.seconds_inside(run, boot, "backend_init")
