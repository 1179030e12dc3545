"""Growth of the goodput ledger's ``data_wait`` lane over the window / window:
the share of the window the step loop stood in ``next(batch_iter)``."""

NAME = "data_wait_share"
UNIT = "%"
LAYER = "Input pipeline"
MOVES = "throughput"
SOURCE = "program_counter"


def read(run):
    grown = run.at_close["goodput"]["data_wait"] - run.at_open["goodput"]["data_wait"]
    return 100.0 * grown / run.window_s
