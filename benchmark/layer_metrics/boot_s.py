"""Process start -> ``jax.devices()`` returns: interpreter, imports, TPU
runtime start."""

NAME = "boot_s"
UNIT = "s"
LAYER = "Worker boot"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(run):
    return run.clocks["t_devices"] - run.clocks["t_start"]
