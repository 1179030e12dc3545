"""``edl_train_moe_held_load_max`` at the window's close: the rows of the
busiest expert THIS chip holds over the mean ``N * k / E`` of all the model's
experts (1.0 is perfect balance; the mean over the expert layers), as the
model sowed it in the last step the loop fetched. The held experts' grouped
matmuls are as long as their rows, so this is the factor by which the largest
group here outgrows a balanced one."""

NAME = "expert_held_load_max"
UNIT = "ratio"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "program_counter"


def read(run):
    series = run.at_close["registry"].get("edl_train_moe_held_load_max", {})
    return series.get("") or None
