"""PR 62's chip probe: the delta rules' carry and output stage as the walk's
two kernels (``ops/gated_delta.py``: ``delta_carry``, ``delta_carry_back``)
alone, and ``_carried_outputs``' value and seven gradients by the kernels
beside the plain form (``carried_states``' two ``lax.scan`` and the two
products), at the three cells' shapes: one sequence of 8192 in 128 chunks of
64; Ling 16 heads of 128 / 128 with a decay a key channel and a row's heads
side by side, Solar 8 such heads, OLMo-hybrid 15 heads of 96 / 192 with a
decay a head and a tile a head.

    chiprun --chips 1 -- python3 bench_results/delta_carry_probe.py [heads by number ...]

A program is timed as ``bench_results/ssd_probe.py`` times one: a jit that
runs it 21 times less a jit that runs it once, over 20, one operand scaled
apart each time and the outputs behind an ``optimization_barrier``. Prints one
JSON line a program and writes them to ``chiprun_out/delta_carry_probe.jsonl``.
Arguments set ``_CARRY_HEADS`` in turn, the most heads a step takes by number
(``0 16``: every cell in rounds of 8 through the loop, and every head at a
static place).
"""

import json
import os
import statistics
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from edl_tpu.ops import gated_delta as G  # noqa: E402

# heads, d_k, d_v, a decay a key channel, a tile a head
SHAPES = {"ling": (16, 128, 128, True, False), "solar": (8, 128, 128, True, False),
          "olmo_hybrid": (15, 96, 192, False, True)}
NC, SIZE, BATCH = 128, 64, 1


def operands(seed, h, d_k, d_v, channel, tiles):
    """``(state, w, u, k_out, whole, q_in, scores)`` as ``_carried_outputs``
    takes them, of the sizes a trained layer's are (rows of unit keys, a
    decay of 0.5 to 1 a chunk)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    bf16, f32 = jnp.bfloat16, jnp.float32
    rows = lambda key, shape, d: (jax.random.normal(key, shape) * d ** -0.5).astype(bf16)  # noqa: E731
    w = rows(keys[0], (NC, BATCH, h, SIZE, d_k), d_k)
    u = jax.random.normal(keys[1], (NC, BATCH, h, SIZE, d_v), f32)
    k_out = rows(keys[2], (NC, BATCH, SIZE, h, d_k), d_k)
    whole = jax.random.uniform(
        keys[3], (NC, BATCH, h, d_k) if channel else (NC, BATCH, h), f32, 0.5, 1.0
    )
    q_in = rows(keys[4], (BATCH, NC, SIZE, h, d_k), d_k)
    scores = jnp.tril(jax.random.normal(keys[5], (BATCH, NC, h, SIZE, SIZE)) * 0.1).astype(bf16)
    state = jax.random.normal(keys[6], (BATCH, h, d_k, d_v), f32)
    if tiles:
        k_out, q_in = jnp.swapaxes(k_out, 2, 3), jnp.swapaxes(q_in, 2, 3)
    else:
        k_out, q_in = k_out.reshape(NC, BATCH, SIZE, -1), q_in.reshape(BATCH, NC * SIZE, -1)
    return state, w, u, k_out, whole, q_in, scores


def wall(fn, *args):
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(8):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - start) * 1e3)
    return statistics.median(times)


def a_call(program, args, apart, more=20):
    """Milliseconds of one run of ``program`` on the device; operand
    ``apart`` is scaled a little each time, so no run is folded into another."""

    def times(n):
        def run(*ops):
            total = jnp.float32(0)
            for i in range(n):
                ops_i = list(ops)
                ops_i[apart] = (ops[apart] * (1.0 + 1e-3 * i)).astype(ops[apart].dtype)
                outs = jax.lax.optimization_barrier(tuple(program(*ops_i)))
                total += sum(o.ravel()[0].astype(jnp.float32) for o in outs)
            return total

        return jax.jit(run)

    return (wall(times(1 + more), *args) - wall(times(1), *args)) / more


def rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def outputs(*args):
    return G._carried_outputs(*args, NC * SIZE)


def value_and_grads(ct_o, ct_state, *args):
    out, pull = jax.vjp(outputs, *args)
    return (*out, *pull((ct_o, ct_state)))


def main():
    widths = [int(a) for a in sys.argv[1:]] or [G._CARRY_HEADS]
    lines = []

    def say(**line):
        lines.append(line)
        print(json.dumps(line), flush=True)

    for cell, (h, d_k, d_v, channel, tiles) in SHAPES.items():
        args = operands(62, h, d_k, d_v, channel, tiles)
        keys = jax.random.split(jax.random.PRNGKey(63))
        ct_o = jax.random.normal(keys[0], (BATCH, NC * SIZE, h, d_v)).astype(jnp.bfloat16)
        ct_state = jax.random.normal(keys[1], (BATCH, h, d_k, d_v)) * 0.1
        names = ("o", "final", "d_state", "d_w", "d_u", "d_k_out", "d_whole", "d_q_in",
                 "d_scores")
        with mock.patch.object(G, "_carry_refuses", lambda *a: "probe"):
            plain = jax.jit(lambda *a: value_and_grads(*a))  # noqa: PLW0108 — a fresh trace
            want = jax.block_until_ready(plain(ct_o, ct_state, *args))
            # the same operands widened, every product at the highest precision:
            # what both forms' roundings are measured against
            wide = [a.astype(jnp.float32) for a in (ct_o, ct_state, *args)]
            with jax.default_matmul_precision("highest"):
                exact = jax.block_until_ready(jax.jit(lambda *a: value_and_grads(*a))(*wide))
            say(cell=cell, program="plain against float32",
                rel={n: rel(g, w) for n, g, w in zip(names, want, exact)})
            say(cell=cell, program="plain value_and_grads",
                ms=a_call(lambda *a: value_and_grads(*a), (ct_o, ct_state, *args), 4))
            say(cell=cell, program="plain value", ms=a_call(lambda *a: outputs(*a), args, 2))
        for width in widths:
            with mock.patch.object(G, "_CARRY_HEADS", width):
                G._carry_call.clear_cache()
                G._carry_back_call.clear_cache()
                kernel = jax.jit(lambda *a: value_and_grads(*a))  # noqa: PLW0108
                got = jax.block_until_ready(kernel(ct_o, ct_state, *args))
                say(cell=cell, heads_by_number=width, program="kernels against plain",
                    rel={n: rel(g, w) for n, g, w in zip(names, got, want)})
                say(cell=cell, heads_by_number=width, program="kernels against float32",
                    rel={n: rel(g, w) for n, g, w in zip(names, got, exact)})
                say(cell=cell, heads_by_number=width, program="kernels value_and_grads",
                    ms=a_call(lambda *a: value_and_grads(*a), (ct_o, ct_state, *args), 4))
                say(cell=cell, heads_by_number=width, program="kernels value",
                    ms=a_call(lambda *a: outputs(*a), args, 2))
                # the two calls alone
                state, w, u, k_out, whole, q_in, scores = args
                if whole.ndim == 3:
                    whole = jnp.broadcast_to(whole[..., None], (*whole.shape, d_k))
                turned = jnp.swapaxes(state, 2, 3)
                forward = (w, u, k_out, whole, q_in, scores, turned)
                o, new, entering, _ = G._carry_call(*forward, False)
                say(cell=cell, heads_by_number=width, program="delta_carry",
                    ms=a_call(lambda *a: G._carry_call(*a, False), forward, 1))
                back = (w, k_out, whole, q_in, scores, entering, new, o, turned)
                say(cell=cell, heads_by_number=width, program="delta_carry_back",
                    ms=a_call(lambda *a: G._carry_back_call(*a, False), back, 7))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "delta_carry_probe.jsonl"), "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
