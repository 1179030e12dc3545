"""Seconds of the program's outermost ``package_import`` spans before the first
``train_setup``: the program's own packages and what they import (flax, optax,
orbax), each moment once."""

from benchmark import setup_timeline, startup_timeline

NAME = "boot_import_s"
UNIT = "s"
LAYER = "Worker boot"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    boot = setup_timeline.before_train_setup(run)
    if boot is None or startup_timeline.first_span(run, "package_import") is None:
        return None
    return setup_timeline.seconds_inside(run, boot, "package_import")
