"""What the readers of the gated short convolution's device time share: device
seconds under the mixer's named scopes (``sconv_proj`` / ``sconv_conv``),
joined from the trace's instruction names by the program's
``obs/profile.py:step_scopes()``. A program without that function, a model that
enters neither scope (every commit before the mixer, every cell of another
family), or a run without a device trace gives every reader nothing to read.

The four device readers (``sconv_share``, ``sconv_conv_roofline`` and the two
``sconv_*_ms``) need a device trace, and ``benchmark/tests/test_rehearse.py``
keeps by hand the set of metrics a CPU may miss, so ``BENCHMARK.json`` does not
list them (as it lists none of the earlier ``*_timeline.py`` files'; ROADMAP
S11(3)). ``python3 benchmark/lfm2_timeline.py`` writes ``BENCHMARK.lfm2.json``
beside it: the same file with all of those listed (``gdn_timeline.with_gdn``),
these four and the expert layer's five for the cells of the ``lfm2_lm`` family,
for ``run.py --benchmark BENCHMARK.lfm2.json --trace 1`` on the chip.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # run as a script
    sys.path.insert(0, ROOT)

from benchmark import gdn_timeline, moe_timeline  # noqa: E402

SCOPES = ("sconv_proj", "sconv_conv")
DEVICE_READERS = ("sconv_share", "sconv_conv_roofline", "sconv_proj_ms",
                  "sconv_conv_ms")


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


def with_lfm2(bench):
    """``bench`` with every earlier unlisted reader listed
    (``gdn_timeline.with_gdn``), the expert layer's five listed for the cells
    of the ``lfm2_lm`` family too, and this file's four for those cells."""
    cells = gdn_timeline.cells_of(bench, "lfm2_lm")
    bench = gdn_timeline.with_gdn(bench)
    per_layer = [
        dict(m, workloads=m["workloads"] + [c for c in cells if c not in m["workloads"]])
        if m["name"] in moe_timeline.DEVICE_READERS else m
        for m in bench["per_layer"]
    ]
    return gdn_timeline.listed_for(dict(bench, per_layer=per_layer), DEVICE_READERS, cells)


if __name__ == "__main__":
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        extended = with_lfm2(json.load(f))
    with open(os.path.join(ROOT, "BENCHMARK.lfm2.json"), "w") as f:
        json.dump(extended, f, indent=1)
    print("BENCHMARK.lfm2.json")
