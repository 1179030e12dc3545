"""Flash-attention kernel benchmark: Pallas kernel vs jnp reference.

Times forward and forward+backward of ``edl_tpu.ops.attention`` on the
current default backend (CPU numbers exercise interpret mode and are NOT
kernel evidence).

Every timed region ends with a ``device_get`` of a scalar that depends on
all iterations.

Prints one JSON line per (impl, mode, seq) combination plus a summary
line with the speedup of the kernel over the reference at the longest
sequence.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench_one(fn, args, iters):
    """Per-iteration seconds via a two-point measurement: the iteration
    loop lives INSIDE one jit (fori_loop with a scalar dependency chain so
    iterations serialize and can't be elided), and timing N vs 2N
    iterations cancels the fixed dispatch+fetch cost, which at short
    sequences is of the kernel's own order."""
    import functools

    import jax
    import jax.numpy as jnp

    q = args[0]

    @functools.partial(jax.jit, static_argnums=(1,))
    def many(args, n):
        q0 = args[0]

        def body(i, carry):
            acc, qd = carry
            out = fn((qd,) + tuple(args[1:]))
            s = jnp.sum(out.astype(jnp.float32))
            # s feeds the next iteration's q: a true serial dependency
            return acc + s, q0 + (s * 1e-30).astype(q0.dtype)

        acc, _ = jax.lax.fori_loop(0, n, body, (jnp.float32(0), q0))
        return acc

    def timed(n):
        float(jax.device_get(many(args, n)))  # compile + sync
        t0 = time.perf_counter()
        float(jax.device_get(many(args, n)))
        return time.perf_counter() - t0

    t1 = timed(iters)
    t2 = timed(2 * iters)
    return max(t2 - t1, 1e-9) / iters


def build_dispatch_table(results, seqs, has_builtin, meta=None):
    """Pure winner-selection: recorded timings -> dispatch table.

    ``results`` maps ``(impl_name, mode, seq)`` -> seconds, with the
    impl names bench ``main()`` produces ("reference", "flash",
    "comp_<fwd>_<bwd>", optionally "builtin"). Factored out of main()
    so a CPU test can feed it a recorded measurement file and assert
    every row is the per-seq minimum — calibration output can never
    ship an inverted row again (the r2 artifact implied dense bwd beat
    flash bwd at 4096 while the shipped default said otherwise).
    """
    fwd_w, bwd_w, whole_w = [], [], []
    for seq in seqs:
        fwd_times = {
            "ref": results[("reference", "fwd", seq)],
            "flash": results[("flash", "fwd", seq)],
            "flash2": results[("comp_flash2_flash", "fwd", seq)],
        }
        comp_times = {
            ("ref", "ref"): results[("reference", "fwd_bwd", seq)],
            ("flash", "flash"): results[("flash", "fwd_bwd", seq)],
            ("ref", "flash"): results[("comp_ref_flash", "fwd_bwd", seq)],
            ("flash", "ref"): results[("comp_flash_ref", "fwd_bwd", seq)],
            ("flash2", "flash"):
                results[("comp_flash2_flash", "fwd_bwd", seq)],
            ("flash2", "ref"):
                results[("comp_flash2_ref", "fwd_bwd", seq)],
            ("flash2", "flash2"):
                results[("comp_flash2_flash2", "fwd_bwd", seq)],
            ("ref", "flash2"):
                results[("comp_ref_flash2", "fwd_bwd", seq)],
            ("flash", "flash2"):
                results[("comp_flash_flash2", "fwd_bwd", seq)],
        }
        # JOINT (fwd, bwd) winner on full fwd+bwd time, fwd-only as the
        # tiebreak: the table's single fwd row serves training AND
        # inference, and picking the fwd-only winner first then the best
        # bwd for it (the old greedy policy) shipped a measured ~21%
        # TRAINING slowdown at seq 1024 in the r4 recalibration (flash2
        # won fwd-only by 0.05 ms but its best composition lost by
        # 0.2 ms). Training is where the time goes; inference-heavy
        # callers have the KV-cache decode path and EDL_ATTN_DISPATCH.
        fwd_best, bwd_best = min(
            comp_times,
            key=lambda fb: (comp_times[fb], fwd_times[fb[0]]),
        )
        fwd_w.append((seq, fwd_best))
        bwd_w.append((seq, bwd_best))
        if has_builtin:
            # EVERY seq gets a whole-row verdict ("comp" = fall through
            # to the fwd/bwd composition): a sparse winners-only list
            # would let _rows_from_winners' unbounded last row route
            # unmeasured/losing lengths to the builtin kernel
            best_comp = comp_times[(fwd_best, bwd_best)]
            builtin_wins = (
                results[("builtin", "fwd", seq)] < fwd_times[fwd_best]
                and results[("builtin", "fwd_bwd", seq)] < best_comp
            )
            whole_w.append((seq, "builtin" if builtin_wins else "comp"))
    table = {
        "fwd": _rows_from_winners(fwd_w),
        "bwd": _rows_from_winners(bwd_w),
        "whole": _rows_from_winners(whole_w),
    }
    if meta:
        table["_measured"] = meta
    return table


def _rows_from_winners(winners):
    """[(seq, impl)...] -> threshold rows [[seq, impl], ..., [None, last]]
    (first match wins; last row unbounded)."""
    rows = []
    for seq, impl in sorted(winners):
        if rows and rows[-1][1] == impl:
            rows[-1][0] = seq
        else:
            rows.append([seq, impl])
    if rows:
        rows[-1][0] = None
    return rows


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--head_dim", type=int, default=64)
    p.add_argument("--seqs", type=int, nargs="+", default=None)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument(
        "--calibrate", default=None, metavar="OUT.json",
        help="also time fwd/bwd compositions and jax's builtin TPU kernel, "
        "then write a dispatch table (load via EDL_ATTN_DISPATCH)",
    )
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from edl_tpu.ops.attention import (
        _auto, attention, attention_reference, flash_attention,
    )

    dev = jax.devices()[0]
    on_tpu = dev.platform not in ("cpu",)
    seqs = args.seqs or ([1024, 2048, 4096] if on_tpu else [256])
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    b, h, d = args.batch, args.heads, args.head_dim

    def comp(fwd_impl, bwd_impl):
        def f(q, k, v, causal=True):
            return _auto(
                q, k, v, causal, q.shape[-1] ** -0.5, fwd_impl, bwd_impl
            )
        return f

    impls = {
        "flash": flash_attention,
        "reference": attention_reference,
        # the dispatching default every model routes through: its row must
        # come out >= 1.0x reference at every seq, fwd and fwd_bwd
        "auto": attention,
    }
    if on_tpu:
        try:
            from jax.experimental.pallas.ops.tpu.flash_attention import (
                flash_attention as _builtin,
            )

            impls["builtin"] = lambda q, k, v, causal=True: _builtin(
                q, k, v, causal=causal, sm_scale=q.shape[-1] ** -0.5
            )
        except ImportError:
            pass
    if args.calibrate:
        impls["comp_ref_flash"] = comp("ref", "flash")
        impls["comp_flash_ref"] = comp("flash", "ref")
        # grid-pipelined fwd AND bwd candidates (all share the residual
        # contract, so any forward pairs with any backward)
        impls["comp_flash2_flash"] = comp("flash2", "flash")
        impls["comp_flash2_ref"] = comp("flash2", "ref")
        impls["comp_flash2_flash2"] = comp("flash2", "flash2")
        impls["comp_ref_flash2"] = comp("ref", "flash2")
        impls["comp_flash_flash2"] = comp("flash", "flash2")

    results = {}
    for seq in seqs:
        rng = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (b, h, seq, d), dtype)
        k = jax.random.normal(kk, (b, h, seq, d), dtype)
        v = jax.random.normal(kv, (b, h, seq, d), dtype)
        # causal attention FLOPs: 2 matmuls, half the square
        flops_fwd = 2 * 2 * b * h * seq * seq * d / 2
        for name, impl in impls.items():
            def fwd(args, _impl=impl):
                return _impl(*args, causal=True)

            def fwd_bwd(args, _impl=impl):
                def loss(q, k, v):
                    return jnp.sum(
                        _impl(q, k, v, causal=True).astype(jnp.float32)
                    )

                g = jax.grad(loss, argnums=(0, 1, 2))(*args)
                return g[0] + g[1] + g[2]

            modes = (("fwd", fwd, 1.0), ("fwd_bwd", fwd_bwd, 3.5))
            if name.startswith("comp_") and name != "comp_flash2_flash":
                # a composition's forward IS its fwd_impl alone; only the
                # fwd_bwd number is new information — skip the redundant
                # on-chip timing. Exception: comp_flash2_flash carries the
                # only fwd measurement of the flash2 kernel.
                modes = (("fwd_bwd", fwd_bwd, 3.5),)
            for mode, f, mult in modes:
                dt = bench_one(f, (q, k, v), args.iters)
                rec = {
                    "metric": "attention_%s_%s" % (name, mode),
                    "seq": seq,
                    "ms": round(dt * 1e3, 3),
                    "tflops": round(flops_fwd * mult / dt / 1e12, 2),
                    "platform": "tpu" if on_tpu else "cpu",
                    "device": dev.device_kind,
                    "shape": [b, h, seq, d],
                }
                results[(name, mode, seq)] = dt
                print(json.dumps(rec))

    for seq in seqs:
        # the acceptance row: dispatch vs XLA dense, both modes
        print(json.dumps({
            "metric": "attention_dispatch_speedup",
            "seq": seq,
            "fwd": round(
                results[("reference", "fwd", seq)]
                / results[("auto", "fwd", seq)], 3,
            ),
            "fwd_bwd": round(
                results[("reference", "fwd_bwd", seq)]
                / results[("auto", "fwd_bwd", seq)], 3,
            ),
            "platform": "tpu" if on_tpu else "cpu",
        }))

    if args.calibrate:
        table = build_dispatch_table(
            results, seqs, "builtin" in impls,
            meta={
                "device": dev.device_kind,
                "shape": [b, h, d],
                "seqs": seqs,
            },
        )
        with open(args.calibrate, "w") as f:
            json.dump(table, f, indent=1)
        print(json.dumps({"metric": "attention_dispatch_table",
                          "path": args.calibrate, **{
                              k: table[k] for k in ("fwd", "bwd", "whole")}}))


if __name__ == "__main__":
    main()
