"""The chip probe of ``ops/ssd.py``'s two kernels alone, and of ``ssd_scan``'s
value and gradients by the kernels beside the plain form, at the two cells'
shapes (one sequence of 8192, 64 heads of 64 over a state of 128; Granite:
chunks of 256 in one group, Nemotron: chunks of 128 in four groups). PR 50's,
and since PR 64 of two trees in one call: ``--parent DIR`` names a checkout
whose ``edl_tpu/ops/ssd.py`` is loaded beside this tree's and timed first
(before PR 64: ``ssd_forward`` and ``ssd_backward`` hand the carry's operands
to a ``lax.scan``, timed alone too, as ``_carry_out``'s value and gradients).

    chiprun --chips 1 -- python3 bench_results/ssd_probe.py [--parent DIR] [heads a round ...]

A program is timed as the difference between one jitted function that runs it
21 times and one that runs it once (each call's ``A`` scaled apart, so none is
folded into another), over 20: the host's launch, about a millisecond here, is
in neither. Prints one JSON line a program and writes them to
``chiprun_out/ssd_probe.jsonl``. A kernel alone reads about 14% over the same
call in a traced step (PERF.md, PR 48). Arguments set ``ops/ssd.py``'s
``_HEADS_A_ROUND`` in turn (``2 4 8``: the sweep PR 50 chose 8 by).
"""

import importlib.util
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


def kernel_programs(module, local, chunk):
    """``(name, program, operands)`` of ``module``'s kernels alone: the fused
    pair (PR 64: the carry inside) or the chunk-local pair and the loops that
    carried their outputs."""
    state = jnp.zeros((1, H, P, N), jnp.float32)
    forward = lambda *v: module._forward_call(*v, chunk, P, N, False)  # noqa: E731
    backward = lambda *v: module._backward_call(*v, chunk, P, N, False)  # noqa: E731
    if hasattr(module, "_scan_kernels"):
        y, entering, final = jax.block_until_ready(jax.jit(forward)(*local, state))
        return [("ssd_forward", forward, (*local, state)),
                ("ssd_backward", backward, (*local, y, entering, final))]
    y, own, whole, grown = jax.block_until_ready(jax.jit(forward)(*local))
    nc, g = T // chunk, (local[0].shape[1] - H * P) // (2 * N)
    r = H // g
    c = local[0][:, H * P + g * N:].reshape(1, g, N, nc, chunk).transpose(3, 0, 1, 2, 4)
    carried = (whole.reshape(nc, 1, g, r), own.reshape(nc, 1, g, r, P, N),
               y.reshape(nc, 1, g, r, P, chunk), grown.reshape(nc, 1, g, r, chunk), c)
    w = jnp.ones((1, g, r, P, T), jnp.bfloat16)

    def loops(whole, own, y, grown, c):
        run = lambda *v: module._carry_out(state.reshape(1, g, r, P, N), *v, jnp.bfloat16)[0]  # noqa: E731
        out, vjp = jax.vjp(run, whole, own, y, grown, c)
        return (out, *vjp(w))

    return [("ssd_forward", forward, local),
            ("ssd_backward", backward, (*local, y, own, whole, grown)),
            ("carry loops value and gradients", loops, jax.block_until_ready(carried))]


def main(widths, parent):
    lines = []

    def say(**line):
        line["device_kind"] = jax.devices()[0].device_kind
        lines.append(line)
        print(json.dumps(line), flush=True)

    trees = {"tree": ssd}
    if parent:
        spec = importlib.util.spec_from_file_location(
            "parent_ssd", os.path.join(parent, "edl_tpu", "ops", "ssd.py")
        )
        trees = {"parent": importlib.util.module_from_spec(spec), **trees}
        spec.loader.exec_module(trees["parent"])
    for width in widths or [None]:
        for tree, module in trees.items():
            if width:
                module._HEADS_A_ROUND = width
            module._forward_call.clear_cache()
            module._backward_call.clear_cache()
            for cell, (chunk, groups) in SHAPES.items():
                args, w = inputs(50, groups)
                x, dt, a, b, c, d = args
                xbc = jnp.concatenate([v.reshape(1, T, -1) for v in (x, b, c)], axis=-1)
                local = (xbc.swapaxes(1, 2), dt.swapaxes(1, 2), a.reshape(H, 1), d.reshape(H, 1))
                local = jax.block_until_ready(jax.jit(lambda *v: v)(*local))
                said = dict(tree=tree, cell=cell, chunk=chunk, groups=groups, heads_a_round=width)
                for name, fn, operands in kernel_programs(module, local, chunk):
                    say(program=name, ms=round(a_call(fn, operands), 3), **said)

                def value_and_grads(w, *v):
                    out, vjp = jax.vjp(lambda *v: module.ssd_scan(*v, chunk=chunk), *v)
                    return (out, *vjp(w))

                results = {}
                for path in ("kernel", "plain"):
                    refuse = module._kernels_refuse if path == "kernel" else (lambda *a: "asked")
                    with mock.patch.object(module, "_kernels_refuse", refuse):
                        fn = lambda w, x, a, *v: value_and_grads(w, x, v[0], a, *v[1:])  # noqa: E731
                        ms = a_call(fn, (w, x, a, dt, b, c, d), more=4)
                        results[path] = jax.jit(lambda w, *v: value_and_grads(w, *v))(w, *args)
                    say(program="ssd_scan value and gradients", path=path, ms=round(ms, 3), **said)
                names = ("y", "d_x", "d_dt", "d_a", "d_b", "d_c", "d_d")
                say(program="kernel against plain", **said, **{
                    n: round(rel(g, p), 5)
                    for n, g, p in zip(names, results["kernel"], results["plain"])
                })
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "ssd_probe.jsonl"), "w") as out:
        out.writelines(json.dumps(line) + "\n" for line in lines)


if __name__ == "__main__":
    argv = sys.argv[1:]
    parent = argv.pop(argv.index("--parent") + 1) if "--parent" in argv else None
    main([int(a) for a in argv if a != "--parent"], parent)
