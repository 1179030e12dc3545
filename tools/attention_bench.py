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


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--head_dim", type=int, default=64)
    p.add_argument("--seqs", type=int, nargs="+", default=None)
    p.add_argument("--iters", type=int, default=20)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from edl_tpu.ops.attention import (
        attention, attention_reference, flash_attention,
    )

    dev = jax.devices()[0]
    on_tpu = dev.platform not in ("cpu",)
    seqs = args.seqs or ([1024, 2048, 4096] if on_tpu else [256])
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    b, h, d = args.batch, args.heads, args.head_dim

    impls = {
        "flash": flash_attention,
        "reference": attention_reference,
        # the default entry point every model routes through
        "auto": attention,
    }
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

            for mode, f, mult in (("fwd", fwd, 1.0), ("fwd_bwd", fwd_bwd, 3.5)):
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
        # the routed entry point against XLA's dense path, both modes
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


if __name__ == "__main__":
    main()
