"""``edl_train_moe_load_max`` at the window's close: the busiest expert's
token-to-expert assignments over the mean (1.0 is perfect balance; the mean
over the expert layers), as the model sowed it in the last step the loop
fetched. A dropless layer computes every assignment, so this is the factor by
which its largest group outgrows the others."""

NAME = "expert_load_max"
UNIT = "ratio"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "program_counter"


def read(run):
    series = run.at_close["registry"].get("edl_train_moe_load_max", {})
    return series.get("") or None
