"""``edl_train_step_program_count{what="kernel_calls"}`` at the run's end: the
Pallas custom calls in the compiled step as the program's census counted them,
forward, recomputed and backward alike (by kernel and pass in the ring's
``step_program`` instant). Static: both branches of a conditional count. A
launch less is a pass over HBM less. 0 on a CPU; a program without the census
gives nothing to read."""

NAME = "step_kernel_calls"
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
    value = series.get('{what="kernel_calls"}')
    return None if value is None else float(value)
