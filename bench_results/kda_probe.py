"""The per-channel rule's three kernels alone, and the rule forward and
backward, on the chip, at the two cells' shapes (one sequence of 8192, heads of
128: Solar's 8 held, Ling's 16), ms a run; then the kernels against the plain
float32 form on log-decays to -30 a step and beta to 2, value and gradients.

    chiprun --chips 1 -- python3 bench_results/kda_probe.py

Writes ``chiprun_out/kda_probe.json``. ``kda_forms_tpu_r51.json`` beside this
file is what its first version wrote on a tree of PR 51 that still had the
sub-blocks' form beside the halving one (README.md has the numbers)."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from edl_tpu.ops import gated_delta as G  # noqa: E402
from edl_tpu.ops import kda_rule  # noqa: E402


def draw(seed, T, H, deep):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    d = 128
    unit = lambda m: m / jnp.linalg.norm(m, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (1, T, H, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (1, T, H, d)))
    v = jax.random.normal(ks[2], (1, T, H, d))
    g = -jax.random.uniform(ks[3], (1, T, H, d), minval=0.0, maxval=2.0)
    if deep:
        g = jnp.where(jax.random.uniform(ks[4], g.shape) < 0.25,
                      -jax.random.uniform(ks[5], g.shape, minval=5, maxval=30), g)
    beta = (2 if deep else 1) * jax.nn.sigmoid(2 * jax.random.normal(ks[6], (1, T, H)))
    bf = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
    return bf(q), bf(k), bf(v), g, beta


def timed(fn, *a, n=20):
    jax.block_until_ready(fn(*a))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def with_grads(fn, w):
    def f(*a):
        out, vjp = jax.vjp(fn, *a)
        return (out, *vjp(w.astype(out.dtype)))
    return jax.jit(f)


def main():
    res, T = {}, 8192
    rule = lambda *a: kda_rule(*a, chunk=64)  # noqa: E731
    for H in (8, 16):
        q, k, v, g, beta = draw(1, T, H, False)
        flat = lambda a: a.reshape(1, T, -1)  # noqa: E731
        inv = jax.jit(lambda k, g, b: G._inverse_call(k, g, b, False))
        ops = jax.jit(lambda *a: G._operands_call(*a, False))
        bwd = jax.jit(lambda *a: G._backward_call(*a, False))
        inverse = inv(flat(k), flat(g), beta)
        operands = ops(flat(q), flat(k), flat(v), flat(g), beta, inverse)
        res["H%d" % H] = dict(
            inverse_ms=timed(inv, flat(k), flat(g), beta),
            operands_ms=timed(ops, flat(q), flat(k), flat(v), flat(g), beta, inverse),
            backward_ms=timed(bwd, flat(q), flat(k), flat(v), flat(g), beta, inverse, *operands),
            rule_fwd_bwd_ms=timed(with_grads(rule, v), q, k, v, g, beta, n=10),
        )
        print("H%d" % H, res["H%d" % H], flush=True)

    q, k, v, g, beta = draw(2, 2048, 8, True)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    w = jax.random.normal(jax.random.PRNGKey(5), v.shape)
    got = with_grads(rule, w)(q, k, v, g, beta)
    with jax.default_matmul_precision("highest"):
        want = with_grads(rule, w)(f32(q), f32(k), f32(v), g, beta)
    res["deep"] = {
        n: dict(err=float(jnp.max(jnp.abs(f32(a) - b))), scale=float(jnp.max(jnp.abs(b))),
                finite=bool(jnp.all(jnp.isfinite(f32(a)))))
        for n, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want)
    }
    print(json.dumps(res["deep"], indent=1))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kda_probe.json", "w") as f:
        json.dump(res, f, indent=1)


if __name__ == "__main__":
    main()
