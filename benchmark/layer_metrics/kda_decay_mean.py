"""``edl_train_kda_decay_mean`` at the window's close: the mean of ``exp(g)`` over
tokens, heads and key channels (the mean over the KDA layers), as the model sowed
it in the last step the loop fetched: how fast the delta rule's state forgets.
The safe gate holds ``g`` in (-5, 0), so this lies in (0.0067, 1); a fresh layer
reads near 1 (the source's initial decays are slow) and the chunked rule's
exponents are then small; towards 0 a sub-block's factors near e^+-80."""

NAME = "kda_decay_mean"
UNIT = "ratio"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "program_counter"


def read(run):
    series = run.at_close["registry"].get("edl_train_kda_decay_mean", {})
    return series.get("") or None
