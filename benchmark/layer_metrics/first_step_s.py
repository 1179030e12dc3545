"""``edl_train_first_step_seconds``: trace + compile, or trace + cache load, of
the step."""

NAME = "first_step_s"
UNIT = "s"
LAYER = "Compile / cache"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(run):
    series = run.at_end["registry"].get("edl_train_first_step_seconds", {})
    return series.get("") or None
