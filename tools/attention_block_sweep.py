"""On-chip block-size sweep for the Pallas attention kernels.

The first shipped ``_FLASH2_BLOCKS_*`` came from exactly this measurement
(r4, v5e): fixed (128, 512) blocks left 1.7-2.6x on the table. Re-run on
new hardware or a new jax release and update the constants in
``edl_tpu/ops/attention.py`` when the winners move. The forward's default
since PR 48 came from a sweep at the benchmark cells' own shapes, GQA, two
widths and the masked copy included, which this tool's MHA-only shapes do
not reach (bench_results/README.md, "the forward's blocks").

Prints one JSON row per (seq, bq, bk) with fwd and fwd+bwd ms; configs
that crash the compiler are recorded as rows with "error" (that is itself
signal).

Usage::

    python tools/attention_block_sweep.py [--seqs 1024 2048 4096]
        [--iters 10]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(0, _HERE)

# the ONE timing methodology (two-point N vs 2N with a serial dependency
# chain) lives in attention_bench; block winners must stay comparable
# with dispatch-calibration timings
from attention_bench import bench_one  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--head_dim", type=int, default=64)
    p.add_argument("--seqs", type=int, nargs="+", default=[1024, 2048, 4096])
    p.add_argument("--iters", type=int, default=10)
    p.add_argument(
        "--blocks_q", type=int, nargs="+", default=[128, 256, 512]
    )
    p.add_argument(
        "--blocks_k", type=int, nargs="+", default=[256, 512, 1024]
    )
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    import importlib

    A = importlib.import_module("edl_tpu.ops.attention")

    dev = jax.devices()[0]
    dtype = jnp.bfloat16 if dev.platform != "cpu" else jnp.float32
    b, h, d = args.batch, args.heads, args.head_dim
    rng = jax.random.PRNGKey(0)
    scale = d ** -0.5

    for seq in args.seqs:
        kq, kk, kv = jax.random.split(jax.random.fold_in(rng, seq), 3)
        q = jax.random.normal(kq, (b, h, seq, d), dtype)
        k = jax.random.normal(kk, (b, h, seq, d), dtype)
        v = jax.random.normal(kv, (b, h, seq, d), dtype)
        for bq in args.blocks_q:
            for bk in args.blocks_k:
                if bq > seq or bk > seq:
                    continue

                def fwd(a, bq=bq, bk=bk):
                    o, _ = A._flash2_forward(
                        a[0], a[1], a[2], True, scale, bq, bk,
                        A._interpret(),
                    )
                    return o

                def fwd_bwd(a, bq=bq, bk=bk):
                    # explicit fwd + backward kernels at the SAME blocks
                    # — how _FLASH2_BLOCKS_BWD was (and can again be)
                    # derived
                    qq, kk_, vv = a
                    o, lse = A._flash2_forward(
                        qq, kk_, vv, True, scale, bq, bk,
                        A._interpret(),
                    )
                    g = jnp.ones_like(o)
                    dq, dk, dv = A._flash2_backward(
                        qq, kk_, vv, o,
                        lse.reshape(b * h, qq.shape[2]), g, True,
                        scale, bq, bk, A._interpret(),
                    )
                    return dq + dk + dv

                row = {"seq": seq, "bq": bq, "bk": bk}
                try:
                    row["fwd_ms"] = round(
                        bench_one(fwd, (q, k, v), args.iters) * 1e3, 3
                    )
                    row["fwdbwd_ms"] = round(
                        bench_one(fwd_bwd, (q, k, v), args.iters) * 1e3, 3
                    )
                except Exception as exc:  # compiler crashes ARE data
                    row["error"] = str(exc)[:120]
                print(json.dumps(row))


if __name__ == "__main__":
    main()
