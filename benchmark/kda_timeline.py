"""What the readers of a Kimi-delta-attention / latent-attention model's device
time share: device seconds under the KDA mixer's named scopes (``kda_proj``:
the six projections in and the one out; ``kda_conv``: the three short
convolutions; ``kda_scan``: the L2 norms, beta, the safe gate and the chunked
rule; ``kda_gate``: the output norm and gate) and the latent layer's
(``mla_proj``: the latent path; ``attn_mla``: the two-width flash kernels),
joined from the trace's instruction names by the program's
``obs/profile.py:step_scopes()``. A program without that function, a model that
enters none of the scopes (every commit before the layers, every cell of
another family), or a run without a device trace gives every reader nothing to
read.

The seven device readers (``kda_share``, the four ``kda_*_ms``,
``kda_scan_roofline`` and ``attn_mla_ms``; the latent layer's kernels' share of
their roofline is the accepted ``attn_kernel_roofline``, which
``BENCHMARK.json`` lists for the cell beside ``attn_kernel_share``) need a device
trace, and ``benchmark/tests/test_rehearse.py`` keeps by hand the set of metrics
a CPU may miss, so ``BENCHMARK.json`` does not list them (as it lists none of
the earlier ``*_timeline.py`` files'; ROADMAP S11(3)); ``kda_decay_mean`` and
``expert_groups_live`` read gauges, read on a CPU and are listed. ``python3
benchmark/kda_timeline.py`` writes ``BENCHMARK.kda.json`` beside it: the same
file with all of those listed (``dsa_timeline.with_dsa``), these seven and the
expert layer's five for the cells of the ``kda_lm`` family, for ``run.py
--benchmark BENCHMARK.kda.json --trace 1`` on the chip.

``scope_seconds`` is ``dsa_timeline``'s loop once more, and differs from it in
one thing: a loop's own event is left out. It cannot call that one, because each
earlier file asks ``step_scopes`` for its own module's ``SCOPES`` whatever the
caller passes, and none of them can be edited here: folding the seven into one
that takes the scopes is a ``benchmark`` issue's (PERF.md section 7, D13).
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # run as a script
    sys.path.insert(0, ROOT)

from benchmark import dsa_timeline, gdn_timeline, moe_timeline  # noqa: E402
from benchmark.afmoe_timeline import KERNEL  # noqa: E402

KDA_SCOPES = ("kda_proj", "kda_conv", "kda_scan", "kda_gate")
SCOPES = KDA_SCOPES + ("mla_proj", "attn_mla")
LOOP = " while("  # in the HLO instruction of a loop's event
DEVICE_READERS = ("kda_share", "kda_scan_ms", "kda_conv_ms", "kda_proj_ms",
                  "kda_gate_ms", "kda_scan_roofline", "attn_mla_ms")


def scope_seconds(run, scopes=KDA_SCOPES, holding=None):
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
    # a loop's event spans its body's, which are on the line themselves (the
    # carry from chunk to chunk is a ``while``): counted once, by the body
    return sum(
        s for name, s in run.trace["op_seconds"].items()
        if table.get(name) in scopes and LOOP not in run.trace["op_text"][name]
        and (holding is None or holding in run.trace["op_text"][name])
    )


def scope_ms(run, scope, holding=None):
    seconds = scope_seconds(run, (scope,), holding)
    return None if seconds is None else 1e3 * seconds / run.trace["steps"]


def roofline(run, seconds, flops_name, bytes_name, units):
    """Least time the chip could take for the work the family's two counting
    functions give for ``units`` (tokens or sequences) / ``seconds``, in %."""
    flops = getattr(run.family, flops_name, None)
    moved = getattr(run.family, bytes_name, None)
    if flops is None or moved is None or run.peaks is None or not seconds:
        return None
    least = max(
        flops(run.config, units) / run.peaks["bf16_flops_per_s"],
        moved(run.config, units) / run.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / seconds


def with_kda(bench):
    """``bench`` with every earlier unlisted reader listed
    (``dsa_timeline.with_dsa``), the expert layer's five listed for the cells
    of the ``kda_lm`` family too, and this file's seven for those cells."""
    cells = gdn_timeline.cells_of(bench, "kda_lm")
    bench = dsa_timeline.with_dsa(bench)
    per_layer = [
        dict(m, workloads=m["workloads"] + [c for c in cells if c not in m["workloads"]])
        if m["name"] in moe_timeline.DEVICE_READERS else m
        for m in bench["per_layer"]
    ]
    return gdn_timeline.listed_for(dict(bench, per_layer=per_layer), DEVICE_READERS, cells)


if __name__ == "__main__":
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        extended = with_kda(json.load(f))
    with open(os.path.join(ROOT, "BENCHMARK.kda.json"), "w") as f:
        json.dump(extended, f, indent=1)
    print("BENCHMARK.kda.json")
