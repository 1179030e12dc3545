"""What the readers of the expert layer's device time share: device seconds
under the layer's named scopes (``moe_route`` / ``moe_experts`` /
``moe_combine``), joined from the trace's instruction names by the program's
``obs/profile.py:step_scopes()``. A program without that function (an older
commit), a model that enters none of the scopes, or a run without a device
trace gives every reader nothing to read.

The five device readers (``moe_share``, ``moe_kernel_roofline`` and the three
``moe_*_ms``) need a device trace, and ``benchmark/tests/test_rehearse.py``
keeps by hand the set of metrics a CPU may miss, so ``BENCHMARK.json`` does not
list them (as it does not list ``timeline.py``'s four phases; ROADMAP S9).
``python3 benchmark/moe_timeline.py`` writes ``BENCHMARK.moe.json`` beside it:
the same file with the phases and these five listed, the five for the cells of
the ``moe_lm`` family, for ``run.py --benchmark BENCHMARK.moe.json --trace 1``
on the chip.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # run as a script
    sys.path.insert(0, ROOT)

from benchmark import timeline  # noqa: E402

SCOPES = ("moe_route", "moe_experts", "moe_combine")
DEVICE_READERS = ("moe_share", "moe_kernel_roofline", "moe_route_ms",
                  "moe_experts_ms", "moe_combine_ms")


def scope_seconds(run, scopes=SCOPES):
    """Device 0's seconds over the traced steps in the operations the program
    puts under one of ``scopes`` (a fusion counts where its root does), or
    None."""
    if not run.trace or not run.trace.get("steps"):
        return None
    try:
        from edl_tpu.obs.profile import step_scopes
    except ImportError:  # a program from before the expert layer
        return None
    table = step_scopes(SCOPES)
    if not table:
        return None
    return sum(
        s for name, s in run.trace["op_seconds"].items() if table.get(name) in scopes
    )


def scope_ms(run, scope):
    seconds = scope_seconds(run, (scope,))
    return None if seconds is None else 1e3 * seconds / run.trace["steps"]


def with_moe(bench):
    """``bench`` with the phase readers (``timeline.with_phases``) and an entry
    for each of this file's device readers it does not list, for the cells
    whose configuration is of the ``moe_lm`` family."""
    bench = timeline.with_phases(bench)
    cells = []
    for cell in bench["workloads"]:
        entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
        with open(os.path.join(ROOT, entry["file"])) as f:
            if json.load(f).get("family") == "moe_lm":
                cells.append(cell["name"])
    listed = {m["name"] for m in bench["per_layer"]}
    entries = []
    for name in DEVICE_READERS:
        if name in listed:
            continue
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        entries.append({"name": module.NAME, "unit": module.UNIT,
                        "better": module.BETTER, "source": module.SOURCE,
                        "layer": module.LAYER, "moves": module.MOVES,
                        "workloads": cells})
    return dict(bench, per_layer=bench["per_layer"] + entries)


if __name__ == "__main__":
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        extended = with_moe(json.load(f))
    with open(os.path.join(ROOT, "BENCHMARK.moe.json"), "w") as f:
        json.dump(extended, f, indent=1)
    print("BENCHMARK.moe.json")
