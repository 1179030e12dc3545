"""The device-trace readers of the ``smallthinker_lm`` family's cells. The
family brings no new scope: its attention calls sit under ``afmoe_timeline``'s
``attn_window`` / ``attn_full`` and its expert layer under ``moe_timeline``'s
five, so ``attn_window_ms``, ``attn_full_ms``, ``attn_window_roofline``,
``attn_full_roofline`` and the expert layer's five read its traced runs as they
are. They need a device trace, and ``benchmark/tests/test_rehearse.py`` keeps by
hand the set of metrics a CPU may miss, so ``BENCHMARK.json`` does not list them
(ROADMAP S8). ``python3 benchmark/smallthinker_timeline.py`` writes
``BENCHMARK.smallthinker.json`` beside it: the same file with every earlier
unlisted reader listed (``mtp_timeline.with_mtp``) and those nine for the cells
of this family too, for ``run.py --benchmark BENCHMARK.smallthinker.json
--trace 1`` on the chip. No loop of its own: ``gdn_timeline.cells_of`` finds the
cells, and the readers are the files they were.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # run as a script
    sys.path.insert(0, ROOT)

from benchmark import gdn_timeline, moe_timeline, mtp_timeline  # noqa: E402

SHARED_READERS = (
    "attn_window_ms", "attn_full_ms", "attn_window_roofline", "attn_full_roofline",
) + moe_timeline.DEVICE_READERS


def with_smallthinker(bench):
    """``bench`` with every earlier unlisted reader listed and the shared
    readers listed for the cells of the ``smallthinker_lm`` family too."""
    cells = gdn_timeline.cells_of(bench, "smallthinker_lm")
    bench = mtp_timeline.with_mtp(bench)
    per_layer = [
        dict(m, workloads=m["workloads"] + [c for c in cells if c not in m["workloads"]])
        if m["name"] in SHARED_READERS else m
        for m in bench["per_layer"]
    ]
    return dict(bench, per_layer=per_layer)


if __name__ == "__main__":
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        extended = with_smallthinker(json.load(f))
    with open(os.path.join(ROOT, "BENCHMARK.smallthinker.json"), "w") as f:
        json.dump(extended, f, indent=1)
    print("BENCHMARK.smallthinker.json")
