"""``edl_train_mtp_loss`` at the window's close: the multi-token-prediction
module's own cross-entropy (nats, unweighted: the objective adds
``loss_weight`` times it), as the model sowed it in the last step the loop
fetched. A health gauge like ``kda_decay_mean``, not a speed: on ids that
cannot be learnt it stays at ``ln vocab_size`` (9.871 over a slice of 19,360),
and a module wired to the wrong target, fed a wrong vocabulary or left out of
the step does not read that. A model without the module gives nothing to read."""

NAME = "mtp_loss"
UNIT = "nats"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "program_counter"


def read(run):
    series = run.at_close["registry"].get("edl_train_mtp_loss", {})
    return series.get("") or None
