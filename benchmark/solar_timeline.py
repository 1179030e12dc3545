"""What the readers of a Kimi-delta-attention / gated grouped-query-attention
model's device time share, for the cells of the ``solar_lm`` family: nothing
new. The linear layers enter the KDA mixer's named scopes (``kda_proj``,
``kda_conv``, ``kda_scan``, ``kda_gate``: ``kda_timeline.py``'s readers), the
softmax layer ``attn_gate`` (``afmoe_timeline.py``'s ``attn_gate_ms``) and its
flash kernels the accepted ``attn_kernel_share`` / ``attn_kernel_roofline``,
the expert layers ``moe_timeline.py``'s five and ``moe_shared_ms``.

Those device readers need a trace, and ``benchmark/tests/test_rehearse.py``
keeps by hand the set of metrics a CPU may miss, so ``BENCHMARK.json`` does not
list them (as it lists none of the earlier ``*_timeline.py`` files'; ROADMAP
S11(3)); ``kda_log_decay_min`` and ``kda_decay_mean`` read gauges, read on a
CPU and are listed. ``python3 benchmark/solar_timeline.py`` writes
``BENCHMARK.solar.json`` beside it: the same file with every earlier unlisted
reader listed (``latent_moe_timeline.with_latent_moe``) and, for the cells of
the ``solar_lm`` family, the six of ``kda_timeline.py`` that read the linear
layers, ``attn_gate_ms``, ``moe_shared_ms`` and the expert layer's five, for
``run.py --benchmark BENCHMARK.solar.json --trace 1`` on the chip.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # run as a script
    sys.path.insert(0, ROOT)

from benchmark import gdn_timeline, latent_moe_timeline, moe_timeline  # noqa: E402

KDA_READERS = ("kda_share", "kda_scan_ms", "kda_conv_ms", "kda_proj_ms",
               "kda_gate_ms", "kda_scan_roofline")
SHARED_READERS = KDA_READERS + ("attn_gate_ms", "moe_shared_ms") + moe_timeline.DEVICE_READERS


def with_solar(bench):
    """``bench`` with every earlier unlisted reader listed and the shared
    readers listed for the cells of the ``solar_lm`` family too."""
    cells = gdn_timeline.cells_of(bench, "solar_lm")
    bench = latent_moe_timeline.with_latent_moe(bench)
    per_layer = [
        dict(m, workloads=m["workloads"] + [c for c in cells if c not in m["workloads"]])
        if m["name"] in SHARED_READERS else m
        for m in bench["per_layer"]
    ]
    return dict(bench, per_layer=per_layer)


if __name__ == "__main__":
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        extended = with_solar(json.load(f))
    with open(os.path.join(ROOT, "BENCHMARK.solar.json"), "w") as f:
        json.dump(extended, f, indent=1)
    print("BENCHMARK.solar.json")
