"""What the reader of a latent expert layer's device time reads: device seconds
under ``moe_latent`` (both of the latent's projections, 4096 -> 1024 before the
dispatch and 1024 -> 4096 after the combine; forward, recomputation and backward
alike), joined from the trace's instruction names by the program's
``obs/profile.py:step_scopes()``. A program without that function, a model that
enters no such scope (every commit before the latent, every cell of another
family), or a run without a device trace gives the reader nothing to read.

``moe_latent_ms`` needs a device trace, and ``benchmark/tests/test_rehearse.py``
keeps by hand the set of metrics a CPU may miss, so ``BENCHMARK.json`` does not
list it (as it lists none of the earlier ``*_timeline.py`` files'; ROADMAP
S11(3)); ``ssm_decay_mean`` and ``expert_rows_held`` read gauges, read on a CPU
and are listed. ``python3 benchmark/latent_moe_timeline.py`` writes
``BENCHMARK.latent_moe.json`` beside it: the same file with every earlier
unlisted reader listed (``kda_timeline.with_kda``), this one for the cells of
the ``nemotron_h_lm`` family, and for those cells the readers that exist of what
the family shares: the expert layer's five (``moe_timeline``), ``moe_shared_ms``
and the state-space six (``ssm_timeline``), for ``run.py --benchmark
BENCHMARK.latent_moe.json --trace 1`` on the chip.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # run as a script
    sys.path.insert(0, ROOT)

from benchmark import gdn_timeline, kda_timeline, moe_timeline, ssm_timeline  # noqa: E402

SCOPES = ("moe_latent",)
DEVICE_READERS = ("moe_latent_ms",)
SHARED_READERS = (
    moe_timeline.DEVICE_READERS + ("moe_shared_ms",) + ssm_timeline.DEVICE_READERS
)


def scope_ms(run, scope="moe_latent"):
    """Device 0's milliseconds a traced step in the operations the program puts
    under ``scope`` (a fusion counts where its root does), or None."""
    if not run.trace or not run.trace.get("steps"):
        return None
    try:
        from edl_tpu.obs.profile import step_scopes
    except ImportError:  # a program from before the scopes' join
        return None
    table = step_scopes(SCOPES)
    if scope not in table.values():
        return None
    seconds = sum(
        s for name, s in run.trace["op_seconds"].items() if table.get(name) == scope
    )
    return 1e3 * seconds / run.trace["steps"]


def with_latent_moe(bench):
    """``bench`` with every earlier unlisted reader listed, the shared readers
    listed for the cells of the ``nemotron_h_lm`` family too, and this file's
    one for those cells."""
    cells = gdn_timeline.cells_of(bench, "nemotron_h_lm")
    bench = kda_timeline.with_kda(bench)
    per_layer = [
        dict(m, workloads=m["workloads"] + [c for c in cells if c not in m["workloads"]])
        if m["name"] in SHARED_READERS else m
        for m in bench["per_layer"]
    ]
    return gdn_timeline.listed_for(dict(bench, per_layer=per_layer), DEVICE_READERS, cells)


if __name__ == "__main__":
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        extended = with_latent_moe(json.load(f))
    with open(os.path.join(ROOT, "BENCHMARK.latent_moe.json"), "w") as f:
        json.dump(extended, f, indent=1)
    print("BENCHMARK.latent_moe.json")
