"""``edl_train_kda_log_decay_min`` at the window's close: the most negative
log-decay ``g`` a key channel took in the last step the loop fetched (the mean
over the Kimi-delta-attention layers of each layer's minimum), as the model
sowed it. The safe gate holds it above its bound (-5 in Ling's cell); Kimi
Linear's own gate, ``-exp(A_log) softplus(.)``, holds it nowhere: under -5.5
the rule's form before PR 51 (a sub-block of 16 steps under one reference)
could not have run the step. A health gauge of the layer, not a lever; it
reads on a CPU."""

NAME = "kda_log_decay_min"
UNIT = "nats/step"
BETTER = "higher"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "program_counter"


def read(run):
    return run.at_close["registry"].get("edl_train_kda_log_decay_min", {}).get("")
