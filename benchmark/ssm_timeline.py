"""What the readers of the Mamba-2 mixer's device time share: device seconds
under the mixer's named scopes (``ssm_proj`` / ``ssm_conv`` / ``ssm_scan`` /
``ssm_gate``), joined from the trace's instruction names by the program's
``obs/profile.py:step_scopes()``. A program without that function, a model
that enters none of the scopes (every commit before the mixer, every cell of
another family), or a run without a device trace gives every reader nothing
to read.

The six device readers (``ssm_share``, ``ssm_scan_roofline`` and the four
``ssm_*_ms``) need a device trace, and ``benchmark/tests/test_rehearse.py``
keeps by hand the set of metrics a CPU may miss, so ``BENCHMARK.json`` does not
list them (as it lists neither ``timeline.py``'s phases nor
``moe_timeline.py``'s five; ROADMAP S8). ``python3 benchmark/ssm_timeline.py``
writes ``BENCHMARK.ssm.json`` beside it: the same file with the phases, the
expert layer's five and these six listed, the six for the cells of the
``ssm_lm`` family, for ``run.py --benchmark BENCHMARK.ssm.json --trace 1`` on
the chip.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # run as a script
    sys.path.insert(0, ROOT)

from benchmark import moe_timeline  # noqa: E402

SCOPES = ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate")
DEVICE_READERS = ("ssm_share", "ssm_scan_roofline", "ssm_scan_ms", "ssm_conv_ms",
                  "ssm_gate_ms", "ssm_proj_ms")


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


def with_ssm(bench):
    """``bench`` with the phase readers and the expert layer's
    (``moe_timeline.with_moe``) and an entry for each of this file's device
    readers it does not list, for the cells whose configuration is of the
    ``ssm_lm`` family."""
    bench = moe_timeline.with_moe(bench)
    cells = []
    for cell in bench["workloads"]:
        entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
        with open(os.path.join(ROOT, entry["file"])) as f:
            if json.load(f).get("family") == "ssm_lm":
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
        extended = with_ssm(json.load(f))
    with open(os.path.join(ROOT, "BENCHMARK.ssm.json"), "w") as f:
        json.dump(extended, f, indent=1)
    print("BENCHMARK.ssm.json")
