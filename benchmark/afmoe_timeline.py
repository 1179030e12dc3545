"""What the readers of the ``afmoe_lm`` family's device time share: device
seconds under the scopes a model of both kinds of attention layer enters —
``attn_window`` and ``attn_full`` (the attention call alone: the flash kernels,
and the row sums their backward starts from), ``attn_gate`` (the per-head QK
norms and the gate with its projection), ``moe_shared`` (the shared expert) —
joined from the trace's instruction names by the program's
``obs/profile.py:step_scopes()``. A program without that function, a model that
enters none of the scopes (every commit before them, every cell of another
family), or a run without a device trace gives every reader nothing to read.

The six device readers (``attn_window_ms``, ``attn_full_ms``, ``attn_gate_ms``,
``moe_shared_ms``, ``attn_window_roofline``, ``attn_full_roofline``) need a
device trace, and ``benchmark/tests/test_rehearse.py`` keeps by hand the set of
metrics a CPU may miss, so ``BENCHMARK.json`` does not list them (as it lists
none of ``timeline.py``'s, ``moe_timeline.py``'s or ``ssm_timeline.py``'s;
ROADMAP S8). ``python3 benchmark/afmoe_timeline.py`` writes
``BENCHMARK.afmoe.json`` beside it: the same file with all of those listed,
these six and the expert layer's five for the cells of the ``afmoe_lm`` family,
for ``run.py --benchmark BENCHMARK.afmoe.json --trace 1`` on the chip.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # run as a script
    sys.path.insert(0, ROOT)

from benchmark import moe_timeline, ssm_timeline  # noqa: E402

SCOPES = ("attn_window", "attn_full", "attn_gate", "moe_shared")
DEVICE_READERS = ("attn_window_ms", "attn_full_ms", "attn_gate_ms", "moe_shared_ms",
                  "attn_window_roofline", "attn_full_roofline")
KERNEL = " custom-call("  # in the HLO instruction of a Pallas kernel


def scope_seconds(run, scope, holding=None):
    """Device 0's seconds over the traced steps in the operations the program
    puts under ``scope`` (a fusion counts where its root does; forward,
    recomputation and backward alike), only those whose HLO instruction holds
    the string ``holding`` if one is given; or None."""
    if not run.trace or not run.trace.get("steps"):
        return None
    try:
        from edl_tpu.obs.profile import step_scopes
    except ImportError:  # a program from before the scopes' join
        return None
    table = step_scopes(SCOPES)
    if scope not in table.values():
        return None
    return sum(
        s for name, s in run.trace["op_seconds"].items()
        if table.get(name) == scope
        and (holding is None or holding in run.trace["op_text"][name])
    )


def scope_ms(run, scope):
    seconds = scope_seconds(run, scope)
    return None if seconds is None else 1e3 * seconds / run.trace["steps"]


def kernel_roofline(run, scope, windowed):
    """Least time the chip could take for the kernels' work (the family's
    ``kind_kernel_flops`` and ``kind_kernel_bytes`` for the windowed or the full
    layers, the larger of the two bounds) / the device time of the custom
    calls under ``scope``, in %."""
    flops = getattr(run.family, "kind_kernel_flops", None)
    if flops is None or run.peaks is None:
        return None
    seconds = scope_seconds(run, scope, KERNEL)
    if not seconds:
        return None
    sequences = run.config["train"]["batch_per_chip"] * run.trace["steps"]
    least = max(
        flops(run.config, sequences, windowed) / run.peaks["bf16_flops_per_s"],
        run.family.kind_kernel_bytes(run.config, sequences, windowed)
        / run.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / seconds


def with_afmoe(bench):
    """``bench`` with the phases, the expert layer's and the Mamba-2 mixer's
    readers (``ssm_timeline.with_ssm``), the expert layer's five listed for the
    cells of the ``afmoe_lm`` family too, and an entry for each of this file's
    device readers it does not list, for those cells."""
    bench = ssm_timeline.with_ssm(bench)
    cells = []
    for cell in bench["workloads"]:
        entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
        with open(os.path.join(ROOT, entry["file"])) as f:
            if json.load(f).get("family") == "afmoe_lm":
                cells.append(cell["name"])
    per_layer = [
        dict(m, workloads=m["workloads"] + [c for c in cells if c not in m["workloads"]])
        if m["name"] in moe_timeline.DEVICE_READERS else m
        for m in bench["per_layer"]
    ]
    listed = {m["name"] for m in per_layer}
    for name in DEVICE_READERS:
        if name in listed:
            continue
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        per_layer.append({"name": module.NAME, "unit": module.UNIT,
                          "better": module.BETTER, "source": module.SOURCE,
                          "layer": module.LAYER, "moves": module.MOVES,
                          "workloads": cells})
    return dict(bench, per_layer=per_layer)


if __name__ == "__main__":
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        extended = with_afmoe(json.load(f))
    with open(os.path.join(ROOT, "BENCHMARK.afmoe.json"), "w") as f:
        json.dump(extended, f, indent=1)
    print("BENCHMARK.afmoe.json")
