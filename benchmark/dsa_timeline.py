"""What the readers of a sparse-attention layer's device time share: device
seconds under the layer's named scopes (``dsa_index``: the indexer's
projections, norm, rotation, the index scores forward, recomputed and backward;
``dsa_select``: the bisection, the mask and its two gauges; ``attn_sparse``: the
masked flash kernels; ``dsa_target``: the heads' mean probabilities, the KL and
its gradient), joined from the trace's instruction names by the program's
``obs/profile.py:step_scopes()``. A program without that function, a model that
enters none of the scopes (every commit before the layer, every cell of another
family), or a run without a device trace gives every reader nothing to read.

The eight device readers (``dsa_share``, the four ``*_ms`` and the three
rooflines) need a device trace, and ``benchmark/tests/test_rehearse.py`` keeps
by hand the set of metrics a CPU may miss, so ``BENCHMARK.json`` does not list
them (as it lists none of the earlier ``*_timeline.py`` files'; ROADMAP S11(3));
``dsa_tile_live`` reads a gauge, reads on a CPU and is listed. ``python3
benchmark/dsa_timeline.py`` writes ``BENCHMARK.dsa.json`` beside it: the same
file with all of those listed (``lfm2_timeline.with_lfm2``), these eight and the
expert layer's five for the cells of the ``sparse_lm`` family, for ``run.py
--benchmark BENCHMARK.dsa.json --trace 1`` on the chip.

``cells_of`` / ``listed_for`` are ``gdn_timeline``'s. ``scope_seconds`` is the
earlier files' loop once more, with the kernels' filter (``holding``) in it:
each of theirs asks ``step_scopes`` for its own module's ``SCOPES`` whatever
the caller passes, so none can be handed another layer's scopes without an
edit to a file that is there (PERF.md section 7, D13: a ``benchmark`` issue's).
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # run as a script
    sys.path.insert(0, ROOT)

from benchmark import gdn_timeline, lfm2_timeline, moe_timeline  # noqa: E402
from benchmark.afmoe_timeline import KERNEL  # noqa: E402

SCOPES = ("dsa_index", "dsa_select", "attn_sparse", "dsa_target")
DEVICE_READERS = ("dsa_share", "dsa_index_ms", "dsa_select_ms", "attn_sparse_ms",
                  "dsa_target_ms", "attn_sparse_roofline", "dsa_index_roofline",
                  "dsa_select_roofline")


def scope_seconds(run, scopes=SCOPES, holding=None):
    """Device 0's seconds over the traced steps in the operations the program
    puts under one of ``scopes`` (a fusion counts where its root does; forward,
    recomputation and backward alike), only those whose HLO instruction holds
    the string ``holding`` if one is given; or None."""
    if not run.trace or not run.trace.get("steps"):
        return None
    try:
        from edl_tpu.obs.profile import step_scopes
    except ImportError:  # a program from before the scopes' join
        return None
    table = step_scopes(SCOPES)
    if not any(scope in scopes for scope in table.values()):
        return None
    return sum(
        s for name, s in run.trace["op_seconds"].items()
        if table.get(name) in scopes
        and (holding is None or holding in run.trace["op_text"][name])
    )


def scope_ms(run, scope):
    seconds = scope_seconds(run, (scope,))
    return None if seconds is None else 1e3 * seconds / run.trace["steps"]


def kernel_roofline(run, scope, flops_name, bytes_name):
    """Least time the chip could take for a kernel's work (the family's two
    counting functions of ``(config, sequences)``; either may be missing: a
    kernel bound by one side alone) / the device time of the custom calls under
    ``scope``, in %."""
    flops = getattr(run.family, flops_name, None) if flops_name else None
    moved = getattr(run.family, bytes_name, None) if bytes_name else None
    if (flops is None and moved is None) or run.peaks is None:
        return None
    seconds = scope_seconds(run, (scope,), KERNEL)
    if not seconds:
        return None
    sequences = run.config["train"]["batch_per_chip"] * run.trace["steps"]
    least = max(
        flops(run.config, sequences) / run.peaks["bf16_flops_per_s"] if flops else 0.0,
        moved(run.config, sequences) / run.peaks["hbm_bytes_per_s"] if moved else 0.0,
    )
    return 100.0 * least / seconds


def with_dsa(bench):
    """``bench`` with every earlier unlisted reader listed
    (``lfm2_timeline.with_lfm2``), the expert layer's five listed for the cells
    of the ``sparse_lm`` family too, and this file's eight for those cells."""
    cells = gdn_timeline.cells_of(bench, "sparse_lm")
    bench = lfm2_timeline.with_lfm2(bench)
    per_layer = [
        dict(m, workloads=m["workloads"] + [c for c in cells if c not in m["workloads"]])
        if m["name"] in moe_timeline.DEVICE_READERS else m
        for m in bench["per_layer"]
    ]
    return gdn_timeline.listed_for(dict(bench, per_layer=per_layer), DEVICE_READERS, cells)


if __name__ == "__main__":
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        extended = with_dsa(json.load(f))
    with open(os.path.join(ROOT, "BENCHMARK.dsa.json"), "w") as f:
        json.dump(extended, f, indent=1)
    print("BENCHMARK.dsa.json")
