"""What the readers of the gated-delta-rule mixer's device time share: device
seconds under the mixer's named scopes (``gdn_proj`` / ``gdn_conv`` /
``gdn_scan`` / ``gdn_gate``), joined from the trace's instruction names by the
program's ``obs/profile.py:step_scopes()``. A program without that function, a
model that enters none of the scopes (every commit before the mixer, every
cell of another family), or a run without a device trace gives every reader
nothing to read.

The six device readers (``gdn_share``, ``gdn_scan_roofline`` and the four
``gdn_*_ms``) need a device trace, and ``benchmark/tests/test_rehearse.py``
keeps by hand the set of metrics a CPU may miss, so ``BENCHMARK.json`` does not
list them (as it lists none of ``timeline.py``'s, ``moe_timeline.py``'s,
``ssm_timeline.py``'s or ``afmoe_timeline.py``'s; ROADMAP S8). ``python3
benchmark/gdn_timeline.py`` writes ``BENCHMARK.gdn.json`` beside it: the same
file with all of those listed (``afmoe_timeline.with_afmoe``) and these six for
the cells of the ``gdn_lm`` family, for ``run.py --benchmark BENCHMARK.gdn.json
--trace 1`` on the chip.

``cells_of`` and ``listed_for`` are the loop the four earlier files each wrote
out: which cells run a family, and a ``per_layer`` entry from a reader's own
constants (PERF.md section 7, D12).
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # run as a script
    sys.path.insert(0, ROOT)

from benchmark import afmoe_timeline  # noqa: E402

SCOPES = ("gdn_proj", "gdn_conv", "gdn_scan", "gdn_gate")
DEVICE_READERS = ("gdn_share", "gdn_scan_roofline", "gdn_scan_ms", "gdn_conv_ms",
                  "gdn_gate_ms", "gdn_proj_ms")


def scope_seconds(run, scopes=SCOPES):
    """Device 0's seconds over the traced steps in the operations the program
    puts under one of ``scopes`` (a fusion counts where its root does;
    forward, recomputation and backward alike), or None."""
    if not run.trace or not run.trace.get("steps"):
        return None
    try:
        from edl_tpu.obs.profile import step_scopes
    except ImportError:  # a program from before the scopes' join
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


def cells_of(bench, family):
    """The names of ``bench``'s cells whose configuration is of ``family``."""
    cells = []
    for cell in bench["workloads"]:
        entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
        with open(os.path.join(ROOT, entry["file"])) as f:
            if json.load(f).get("family") == family:
                cells.append(cell["name"])
    return cells


def listed_for(bench, readers, cells):
    """``bench`` with an entry, for ``cells``, for each of ``readers`` (files
    of ``layer_metrics/``) it does not list."""
    listed = {m["name"] for m in bench["per_layer"]}
    entries = []
    for name in readers:
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


def with_gdn(bench):
    """``bench`` with every earlier unlisted reader listed
    (``afmoe_timeline.with_afmoe``) and this file's six for the cells of the
    ``gdn_lm`` family."""
    return listed_for(
        afmoe_timeline.with_afmoe(bench), DEVICE_READERS, cells_of(bench, "gdn_lm")
    )


if __name__ == "__main__":
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        extended = with_gdn(json.load(f))
    with open(os.path.join(ROOT, "BENCHMARK.gdn.json"), "w") as f:
        json.dump(extended, f, indent=1)
    print("BENCHMARK.gdn.json")
