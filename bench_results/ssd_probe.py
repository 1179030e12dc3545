"""PR 50's chip probe: ``ops/ssd.py``'s two kernels alone, and ``ssd_scan``'s
value and gradients by the kernels beside the plain form, at the two cells'
shapes (one sequence of 8192, 64 heads of 64 over a state of 128; Granite:
chunks of 256 in one group, Nemotron: chunks of 128 in four groups).

    chiprun --chips 1 -- python3 bench_results/ssd_probe.py [heads a round ...]

A program is timed as the difference between one jitted function that runs it
21 times and one that runs it once (each call's ``A`` scaled apart, so none is
folded into another), over 20: the host's launch, about a millisecond here, is
in neither. Prints one JSON line a program and writes them to
``chiprun_out/ssd_probe.jsonl``. A kernel alone reads about 14% over the same
call in a traced step (PERF.md, PR 48). Arguments set ``ops/ssd.py``'s
``_HEADS_A_ROUND`` in turn (``2 4 8``: the sweep PR 50 chose 8 by).
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

from edl_tpu.ops import ssd  # noqa: E402

SHAPES = {"granite": (256, 1), "nemotron": (128, 4)}
T, H, P, N = 8192, 64, 64, 128
A = 2  # where ``a`` stands among a program's operands


def inputs(seed, groups):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    bf16 = jnp.bfloat16
    x = jax.random.normal(keys[0], (1, T, H, P), bf16)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (1, T, H)) - 3.0)
    a = -jnp.exp(jax.random.uniform(keys[2], (H,), minval=0.0, maxval=2.77))
    b = (jax.random.normal(keys[3], (1, T, groups, N)) * N ** -0.5).astype(bf16)
    c = jax.random.normal(keys[4], (1, T, groups, N), bf16)
    d = jnp.ones((H,))
    w = jax.random.normal(keys[5], (1, T, H, P), bf16)
    return (x, dt, a, b, c, d), w


def wall(fn, *args):
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(8):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - start) * 1e3)
    return statistics.median(times)


def a_call(program, operands, more=20):
    """Milliseconds of one run of ``program`` on the device."""

    def times(n):
        def run(*ops):
            total = jnp.float32(0)
            for i in range(n):
                ops_i = list(ops)
                ops_i[A] = ops[A] * (1.0 + 1e-6 * i)
                total += sum(o.ravel()[0].astype(jnp.float32) for o in program(*ops_i))
            return total

        return jax.jit(run)

    return (wall(times(1 + more), *operands) - wall(times(1), *operands)) / more


def rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def main(widths):
    lines = []

    def say(**line):
        line["device_kind"] = jax.devices()[0].device_kind
        lines.append(line)
        print(json.dumps(line), flush=True)

    for width in widths or [None]:
        if width:
            ssd._HEADS_A_ROUND = width
        ssd._forward_call.clear_cache()
        ssd._backward_call.clear_cache()
        for cell, (chunk, groups) in SHAPES.items():
            args, w = inputs(50, groups)
            x, dt, a, b, c, d = args
            xbc = jnp.concatenate([v.reshape(1, T, -1) for v in (x, b, c)], axis=-1)
            local = (xbc.swapaxes(1, 2), dt.swapaxes(1, 2), a.reshape(H, 1), d.reshape(H, 1))
            local = jax.block_until_ready(jax.jit(lambda *v: v)(*local))
            forward = lambda *v: ssd._forward_call(*v, chunk, P, N, False)  # noqa: E731
            backward = lambda *v: ssd._backward_call(*v, chunk, P, N, False)  # noqa: E731
            outs = jax.block_until_ready(jax.jit(forward)(*local))
            for name, fn, operands in (("ssd_forward", forward, local),
                                       ("ssd_backward", backward, (*local, *outs))):
                say(cell=cell, program=name, chunk=chunk, groups=groups, heads_a_round=width,
                    ms=round(a_call(fn, operands), 3))

            def value_and_grads(w, *v):
                out, vjp = jax.vjp(lambda *v: ssd.ssd_scan(*v, chunk=chunk), *v)
                return (out, *vjp(w))

            results = {}
            for path in ("kernel", "plain"):
                refuse = ssd._kernels_refuse if path == "kernel" else (lambda *a: "asked")
                with mock.patch.object(ssd, "_kernels_refuse", refuse):
                    fn = lambda w, x, a, *v: value_and_grads(w, x, v[0], a, *v[1:])  # noqa: E731
                    ms = a_call(fn, (w, x, a, dt, b, c, d), more=4)
                    results[path] = jax.jit(lambda w, *v: value_and_grads(w, *v))(w, *args)
                say(cell=cell, program="ssd_scan value and gradients", path=path, chunk=chunk,
                    groups=groups, heads_a_round=width, ms=round(ms, 3))
            names = ("y", "d_x", "d_dt", "d_a", "d_b", "d_c", "d_d")
            say(cell=cell, program="kernel against plain", heads_a_round=width, **{
                n: round(rel(g, p), 5)
                for n, g, p in zip(names, results["kernel"], results["plain"])
            })
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "ssd_probe.jsonl"), "w") as out:
        out.writelines(json.dumps(line) + "\n" for line in lines)


if __name__ == "__main__":
    main([int(a) for a in sys.argv[1:]])
