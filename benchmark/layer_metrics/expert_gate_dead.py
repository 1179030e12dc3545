"""``edl_train_moe_gate_dead`` at the window's close: of the gate
pre-activations ``[rows, width]`` of the experts THIS chip holds, over the rows
that fell on them, the share a ReLU gate zeroes (the mean over the expert
layers), as the model sowed it in the last step the loop fetched. A health
gauge like ``kda_decay_mean``: about 0.5 where the gate's values are as drawn;
the columns it counts are computed all the same today, and are what a kernel
that follows the gate could skip. A SiLU-gated layer sows none, and a count
over the buffer's rows that are nobody's would read a third higher."""

NAME = "expert_gate_dead"
UNIT = "ratio"
BETTER = "higher"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "program_counter"


def read(run):
    series = run.at_close["registry"].get("edl_train_moe_gate_dead", {})
    return series.get("") or None
