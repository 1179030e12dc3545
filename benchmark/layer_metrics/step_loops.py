"""``edl_train_step_program_count{what="loops"}`` at the run's end: the
``while`` instructions of the compiled step as the program's census counted
them (a chunked rule's carry, forward and backward; the group search inside
each Megablox call), by part in the ring's ``step_program`` instant. A program
without the census gives nothing to read."""

NAME = "step_loops"
UNIT = "count"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "program_counter"
GAUGE = "edl_train_step_program_count"


def read(run):
    series = run.at_end["registry"].get(GAUGE)
    if not series:
        return None
    value = series.get('{what="loops"}')
    return None if value is None else float(value)
