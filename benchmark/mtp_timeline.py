"""What the readers of a latent-attention model with a multi-token-prediction
module share, for the cells of the ``mla_mtp_lm`` family: device seconds under
the module's named scopes (``mtp``: the whole module, so that a trace tells its
block's attention and expert layer from the trunk's; inside it ``mtp_join``: the
two norms, the concatenation and the joined projection; ``mtp_head``: the last
norm, the head's second use and its cross-entropy), joined from the trace's
instruction names by the program's ``obs/profile.py:step_scopes()``. A program
without that function, a model that enters none of the scopes (every commit
before the module, every cell of another family), or a run without a device
trace gives every reader nothing to read.

The four device readers (``mtp_share``, ``mtp_join_ms``, ``mtp_head_ms`` and
``mla_proj_ms``, which reads ``kda_timeline``'s scopes as ``attn_mla_ms`` does)
need a device trace, and ``benchmark/tests/test_rehearse.py`` keeps by hand the
set of metrics a CPU may miss, so ``BENCHMARK.json`` does not list them (as it
lists none of the earlier ``*_timeline.py`` files'; ROADMAP S11(3));
``mtp_loss`` reads a gauge, reads on a CPU and is listed. ``python3
benchmark/mtp_timeline.py`` writes ``BENCHMARK.mtp.json`` beside it: the same
file with every earlier unlisted reader listed (``solar_timeline.with_solar``),
these four, ``attn_mla_ms``, ``moe_shared_ms`` and the expert layer's five for
the cells of the ``mla_mtp_lm`` family, for ``run.py --benchmark
BENCHMARK.mtp.json --trace 1`` on the chip.

``scope_seconds`` is no ninth copy of ``moe_timeline.py``'s loop (ROADMAP D13):
it is ``kda_timeline.scope_seconds`` asked for this file's scopes. Each earlier
loop asks ``step_scopes`` for its own module's ``SCOPES`` whatever the caller
passes, and none of them can be edited here, so the module's name is lent this
file's value for the call.
"""

from __future__ import annotations

import json
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # run as a script
    sys.path.insert(0, ROOT)

from benchmark import gdn_timeline, kda_timeline, moe_timeline, solar_timeline  # noqa: E402

# asked for alone, ``mtp`` takes everything the module runs: the block's own
# scopes (``mla_proj``, ``attn_mla``, ``moe_*``) are not among the asked ones
SCOPES = ("mtp", "mtp_join", "mtp_head")
DEVICE_READERS = ("mtp_share", "mtp_join_ms", "mtp_head_ms", "mla_proj_ms")
SHARED_READERS = ("attn_mla_ms", "moe_shared_ms") + moe_timeline.DEVICE_READERS


def scope_seconds(run, scopes=SCOPES):
    """Device 0's seconds over the traced steps in the operations the program
    puts under one of ``scopes`` (forward, recomputation and backward alike; a
    loop's own event left out), or None: ``kda_timeline``'s loop over this
    file's scopes."""
    with mock.patch.object(kda_timeline, "SCOPES", SCOPES):
        return kda_timeline.scope_seconds(run, scopes)


def scope_ms(run, scope):
    seconds = scope_seconds(run, (scope,))
    return None if seconds is None else 1e3 * seconds / run.trace["steps"]


def with_mtp(bench):
    """``bench`` with every earlier unlisted reader listed, the shared readers
    listed for the cells of the ``mla_mtp_lm`` family too, and this file's four
    for those cells."""
    cells = gdn_timeline.cells_of(bench, "mla_mtp_lm")
    bench = solar_timeline.with_solar(bench)
    per_layer = [
        dict(m, workloads=m["workloads"] + [c for c in cells if c not in m["workloads"]])
        if m["name"] in SHARED_READERS else m
        for m in bench["per_layer"]
    ]
    return gdn_timeline.listed_for(dict(bench, per_layer=per_layer), DEVICE_READERS, cells)


if __name__ == "__main__":
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        extended = with_mtp(json.load(f))
    with open(os.path.join(ROOT, "BENCHMARK.mtp.json"), "w") as f:
        json.dump(extended, f, indent=1)
    print("BENCHMARK.mtp.json")
