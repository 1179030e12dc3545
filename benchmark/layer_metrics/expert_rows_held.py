"""``edl_train_moe_rows_held`` at the window's close: the share of the ``N * k``
(token, choice) pairs that fell on the experts THIS chip holds (``held / E``
when balanced: 0.0156 at 8 of 512; the mean over the expert layers), as the
model sowed it in the last step the loop fetched. The held experts' grouped
matmuls, the gathers before them and the combine after them are as long as
these rows, and a step whose rows outgrow twice the balanced share takes the
layer's whole ``N * k`` path instead of its buffer."""

NAME = "expert_rows_held"
UNIT = "ratio"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "program_counter"


def read(run):
    series = run.at_close["registry"].get("edl_train_moe_rows_held", {})
    return series.get("") or None
