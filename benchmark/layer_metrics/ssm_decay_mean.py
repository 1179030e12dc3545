"""``edl_train_ssm_decay_mean`` at the window's close: the mean of ``exp(dt * A)``
over steps and heads (the mean over the Mamba-2 layers), as the model sowed it in
the last step the loop fetched: how fast the state-space layers' state forgets,
the twin of ``gdn_decay_mean`` / ``kda_decay_mean``. ``dt`` is positive and ``A``
negative, so this lies in (0, 1); a fresh layer (steps log-uniform in [1e-3,
1e-1], ``A`` in -[1, 16]) reads near 0.8, and a head that reads near 0 keeps
nothing from one chunk to the next. A health gauge, not a lever: the chunked
scan's time does not depend on it."""

NAME = "ssm_decay_mean"
UNIT = "ratio"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "program_counter"


def read(run):
    series = run.at_close["registry"].get("edl_train_ssm_decay_mean", {})
    return series.get("") or None
