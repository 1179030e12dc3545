"""``edl_train_step_program_count{what="plain_fallbacks"}`` at the run's end:
the call-site shapes of the stage that took the plain form of an op that has a
kernel form (``causal_conv_silu``, ``kda_rule``, the grouped matmul, attention,
sparse attention), as the program's census counted its ``path="plain"`` notes
when the first step had ended. Each is named, with its ``why``, in the ring's
``step_program`` instant. 0 on the chip where every dispatch met its
conditions; on a CPU every one of them. A program without the census gives
nothing to read."""

NAME = "step_plain_fallbacks"
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
    value = series.get('{what="plain_fallbacks"}')
    return None if value is None else float(value)
