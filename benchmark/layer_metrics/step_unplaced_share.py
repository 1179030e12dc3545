"""The matmul-class instructions of the compiled step that the program's table
of parts (``obs/profile.py:STEP_PARTS``) places under no module or scope that
owns a matmul (``other``; ``block``, a layer's module the table does not list;
``loss``, no name at all), over all of them (``edl_train_step_program_count``
``unplaced_matmuls`` over ``matmuls`` at the run's end), in percent: the
table's own coverage. Near 0; a new model's layer without a listed name shows
here (not a matmul right under the model: that reads as a tied head's). A
program without the census, or a step without a matmul, gives nothing to read."""

NAME = "step_unplaced_share"
UNIT = "%"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "program_counter"
GAUGE = "edl_train_step_program_count"


def read(run):
    series = run.at_end["registry"].get(GAUGE) or {}
    matmuls = series.get('{what="matmuls"}')
    unplaced = series.get('{what="unplaced_matmuls"}')
    if not matmuls or unplaced is None:
        return None
    return 100.0 * unplaced / matmuls
