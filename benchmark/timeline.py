"""What the readers of the program's own timeline share: the ring's spans
inside the window, and device seconds by the step program's named phases.

The window is the epochs ``run.window_epochs`` lists; a ring event belongs to
it by its ``epoch`` argument. A program without these spans (an older commit)
gives every reader nothing to read.

The four phase readers need a device trace, and ``benchmark/tests/test_rehearse.py``
keeps by hand the set of metrics a CPU may miss, so ``BENCHMARK.json`` does not
list them yet. ``python3 benchmark/timeline.py`` writes ``BENCHMARK.phases.json``
beside it, the same file with the four listed for every cell, for
``run.py --benchmark BENCHMARK.phases.json --trace 1`` on the chip.
"""

from __future__ import annotations

import importlib.util
import json
import os
import statistics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASE_READERS = ("step_forward_ms", "step_backward_ms", "step_optimizer_ms",
                 "step_numerics_ms")


def window_events(run, name, ph="X"):
    """The ring's events called ``name`` whose ``epoch`` is one of the
    window's, less those that ended in an exception (the ``data_wait`` that
    found the end of the feed)."""
    epochs = set(run.window_epochs)
    return [
        e for e in run.tracer_events
        if e.get("name") == name and e.get("ph") == ph
        and e.get("args", {}).get("epoch") in epochs
        and "error" not in e.get("args", {})
    ]


def median_span_ms(run, name):
    spans = window_events(run, name)
    if not spans:
        return None
    return statistics.median(e["dur"] for e in spans) / 1e3


def phase_ms(run, phase):
    """Device 0's milliseconds a traced step in the operations that the
    program's ``step_phases()`` puts in ``phase``: the trace names an event by
    its HLO instruction, the program says which scope the instruction came
    from. None without a device trace, or where the program has no table."""
    if not run.trace or not run.trace.get("steps"):
        return None
    try:
        from edl_tpu.obs.profile import step_phases
    except ImportError:  # a program from before the scopes
        return None
    table = step_phases()
    if not table:
        return None
    seconds = sum(
        s for name, s in run.trace["op_seconds"].items() if table.get(name) == phase
    )
    return 1e3 * seconds / run.trace["steps"]


def with_phases(bench):
    """``bench`` (``BENCHMARK.json`` as loaded) with an entry for each phase
    reader it does not list, taken from the reader's own constants."""
    listed = {m["name"] for m in bench["per_layer"]}
    entries = []
    for name in PHASE_READERS:
        if name in listed:
            continue
        path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
        spec = importlib.util.spec_from_file_location(name, path)
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        entries.append({"name": reader.NAME, "unit": reader.UNIT, "better": "lower",
                        "source": reader.SOURCE, "layer": reader.LAYER,
                        "moves": reader.MOVES})
    return dict(bench, per_layer=bench["per_layer"] + entries)


if __name__ == "__main__":
    import sys

    sys.path.insert(0, ROOT)  # the readers import ``benchmark.timeline``
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        extended = with_phases(json.load(f))
    with open(os.path.join(ROOT, "BENCHMARK.phases.json"), "w") as f:
        json.dump(extended, f, indent=1)
    print("BENCHMARK.phases.json")
