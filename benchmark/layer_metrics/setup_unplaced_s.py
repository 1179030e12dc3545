"""Seconds of ``setup_ring_s`` that no program span on that thread covers: the
holes between the tiles (``benchmark/setup_timeline.py`` names each by its
neighbours)."""

from benchmark import setup_timeline

NAME = "setup_unplaced_s"
UNIT = "s"
LAYER = "Worker boot"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return setup_timeline.unplaced_s(run)
