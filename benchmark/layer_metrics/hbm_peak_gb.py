"""``memory_stats()`` peak_bytes_in_use at the end of ``fit``, largest over the
devices. It bounds the batch."""

NAME = "hbm_peak_gb"
UNIT = "GB"
LAYER = "Memory"
MOVES = "throughput"
SOURCE = "program_counter"


def read(run):
    return run.at_end["memory_peak_bytes"] / 1e9 or None
