"""The device-trace readers of the ``block_diffusion_lm`` family's cells. The
family brings one new scope, ``attn_block_diffusion`` (the attention call of a
block-diffusion step alone: the ``flash2`` kernels under the third mask kind;
the per-head QK norms beside it sit under ``afmoe_timeline``'s ``attn_gate``),
and no reader of its own for it: the family's ``TRACE_KERNELS`` (``%attn_``)
match the kernels under that scope, so ``attn_kernel_share`` and
``attn_kernel_roofline`` read them as listed, and its expert layer sits under
``moe_timeline``'s five. ``scope_seconds`` below gives a scratch script the
device seconds under each of ``SCOPES`` by the program's
``obs/profile.py:step_scopes()`` (``kda_timeline.py``'s way). The shared readers
need a device trace, and ``benchmark/tests/test_rehearse.py`` keeps by hand the
set of metrics a CPU may miss, so ``BENCHMARK.json`` does not list them (ROADMAP
S8). ``python3 benchmark/sdar_timeline.py`` writes ``BENCHMARK.sdar.json``
beside it: the same file with every earlier unlisted reader listed
(``smallthinker_timeline.with_smallthinker``: the phases, ``timeline.py``'s four,
among them) and ``attn_gate_ms`` and the expert layer's five for the cells of
this family too, for ``run.py --benchmark BENCHMARK.sdar.json --trace 1`` on the
chip.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # run as a script
    sys.path.insert(0, ROOT)

from benchmark import gdn_timeline, moe_timeline, smallthinker_timeline  # noqa: E402

SCOPES = ("attn_block_diffusion", "attn_gate") + moe_timeline.SCOPES
SHARED_READERS = ("attn_gate_ms",) + moe_timeline.DEVICE_READERS


def scope_seconds(run, scopes=SCOPES):
    """``{scope: device 0's seconds over the traced steps}`` in the operations
    the program puts under each of ``scopes`` (a fusion counts where its root
    does), or None without a trace or a program that knows the scopes."""
    if not run.trace or not run.trace.get("steps"):
        return None
    try:
        from edl_tpu.obs.profile import step_scopes
    except ImportError:
        return None
    table = step_scopes(SCOPES)
    if not table:
        return None
    found = dict.fromkeys(scopes, 0.0)
    for name, seconds in run.trace["op_seconds"].items():
        if table.get(name) in found:
            found[table[name]] += seconds
    return found


def with_sdar(bench):
    """``bench`` with every earlier unlisted reader listed and the shared
    readers listed for the cells of the ``block_diffusion_lm`` family too."""
    cells = gdn_timeline.cells_of(bench, "block_diffusion_lm")
    bench = smallthinker_timeline.with_smallthinker(bench)
    per_layer = [
        dict(m, workloads=m["workloads"] + [c for c in cells if c not in m["workloads"]])
        if m["name"] in SHARED_READERS else m
        for m in bench["per_layer"]
    ]
    return dict(bench, per_layer=per_layer)


if __name__ == "__main__":
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        extended = with_sdar(json.load(f))
    with open(os.path.join(ROOT, "BENCHMARK.sdar.json"), "w") as f:
        json.dump(extended, f, indent=1)
    print("BENCHMARK.sdar.json")
