"""The program's ``train_setup`` span: ``create_state`` (op-by-op init),
placement on the mesh, restore, the stage barrier."""

NAME = "state_build_s"
UNIT = "s"
LAYER = "State build"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    for ev in run.tracer_events:
        if ev.get("name") == "train_setup":
            return ev["dur"] / 1e6
    return None
