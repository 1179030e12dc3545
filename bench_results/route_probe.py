"""PR 65's chip probe: a token's chosen scores read out of ``[N, E]`` and their
gradient sent back, alone, at the six bias-routed cells' ``(N, E, k)``, in every
form the issue names:

- ``gather`` / ``scatter``: the parent's ``jnp.take_along_axis(scores, idx, -1)``
  and its transpose (``jax.linear_transpose``: a scatter-add into zeros);
- ``reduce``: one reduce of the broadcast compare, forward over ``e``
  (``sum_e where(idx[n, j] == e, scores[n, e], 0)``), backward over ``j``;
- ``columns``: ``k`` compares accumulated over the row, a choice at a time;
- ``pallas``: the same sums in a row-tiled Pallas kernel (``--tiles`` rows a grid
  step), the compare against a lane iota;
- ``tree``: ``models/moe.py:_picked`` and its ``jax.vjp``, where the tree has it
  (the parent has not: the line is left out).

    chiprun --chips 1 -- python3 bench_results/route_probe.py [cell ...]
    JAX_PLATFORMS=cpu python3 bench_results/route_probe.py --compile [cell ...]

The indices are a ``top_k``'s (distinct a row) of scores plus a bias; every form
is compared with the gather (with the scatter), bit for bit. A form is timed as
(a loop of 21 calls - a loop of 1) / 20 of one compiled program, the next call's
indices made to depend on this call's output and the whole result held behind
a barrier (``segment_sum_probe.py``'s way). Prints one JSON line a form and
writes them to ``chiprun_out/route_probe.jsonl``. A probe ranks; it does not
size (PERF.md, PR 48): inside a step XLA fuses a form with its neighbours and
chooses the layouts.
"""

import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

# (tokens N, experts E the router scores, choices k)
CELLS = {
    "nemotron": (8192, 512, 22),
    "ling": (8192, 512, 8),
    "solar": (8192, 320, 8),
    "trinity": (8192, 128, 8),
    "lfm2_glm": (8192, 64, 4),
}
ROW_TILES = (256, 1024)


def gather(scores, idx):
    return jnp.take_along_axis(scores, idx, axis=-1)


def scatter(grad, idx, e):
    like = jax.ShapeDtypeStruct((idx.shape[0], e), grad.dtype)
    return jax.linear_transpose(lambda s: gather(s, idx), like)(grad)[0]


def reduce_forward(scores, idx):
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, 1, scores.shape[-1]), 2)
    return jnp.sum(jnp.where(idx[:, :, None] == lanes, scores[:, None, :], 0), axis=-1)


def reduce_backward(grad, idx, e):
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, 1, e), 2)
    return jnp.sum(jnp.where(idx[:, :, None] == lanes, grad[:, :, None], 0), axis=1)


def columns_forward(scores, idx):
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, scores.shape[-1]), 1)
    return jnp.stack([
        jnp.sum(jnp.where(idx[:, j:j + 1] == lanes, scores, 0), axis=-1)
        for j in range(idx.shape[-1])
    ], axis=-1)


def columns_backward(grad, idx, e):
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, e), 1)
    total = jnp.zeros((idx.shape[0], e), grad.dtype)
    for j in range(idx.shape[-1]):
        total = total + jnp.where(idx[:, j:j + 1] == lanes, grad[:, j:j + 1], 0)
    return total


def _forward_kernel(scores_ref, idx_ref, out_ref):
    scores, idx = scores_ref[...], idx_ref[...]
    lanes = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    place = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 1)
    out = jnp.zeros(idx.shape, scores.dtype)
    for j in range(idx.shape[-1]):
        one = jnp.sum(jnp.where(idx[:, j:j + 1] == lanes, scores, 0), axis=-1, keepdims=True)
        out = jnp.where(place == j, one, out)
    out_ref[...] = out


def _backward_kernel(grad_ref, idx_ref, out_ref):
    grad, idx = grad_ref[...], idx_ref[...]
    lanes = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    total = jnp.zeros(out_ref.shape, grad.dtype)
    for j in range(idx.shape[-1]):
        total = total + jnp.where(idx[:, j:j + 1] == lanes, grad[:, j:j + 1], 0)
    out_ref[...] = total


def pallas_forward(rows, interpret=False):
    def run(scores, idx):
        n, e = scores.shape
        k = idx.shape[-1]
        return pl.pallas_call(
            _forward_kernel, grid=(n // rows,),
            in_specs=[pl.BlockSpec((rows, e), lambda i: (i, 0)),
                      pl.BlockSpec((rows, k), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((rows, k), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n, k), scores.dtype), interpret=interpret,
        )(scores, idx)

    return run


def pallas_backward(rows, interpret=False):
    def run(grad, idx, e):
        n, k = idx.shape
        return pl.pallas_call(
            _backward_kernel, grid=(n // rows,),
            in_specs=[pl.BlockSpec((rows, k), lambda i: (i, 0)),
                      pl.BlockSpec((rows, k), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((rows, e), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n, e), grad.dtype), interpret=interpret,
        )(grad, idx)

    return run


def the_trees():
    """``(forward, backward)`` of the tree's own ``_picked``, or None."""
    from edl_tpu.models import moe

    picked = getattr(moe, "_picked", None)
    if picked is None:
        return None

    def backward(grad, idx, e):
        scores = jnp.zeros((idx.shape[0], e), grad.dtype)
        return jax.vjp(lambda s: picked(s, idx), scores)[1](grad)[0]

    return picked, backward


def forms(interpret=False):
    """``(form, how, forward, backward)``; the first is what the others are
    held to."""
    yield "gather", {}, gather, scatter
    yield "reduce", {}, reduce_forward, reduce_backward
    yield "columns", {}, columns_forward, columns_backward
    for rows in ROW_TILES:
        yield ("pallas", dict(rows=rows), pallas_forward(rows, interpret),
               pallas_backward(rows, interpret))
    tree = the_trees()
    if tree:
        yield ("tree", {}) + tree


def looped(fn):
    """``fn(values, idx)`` in a loop of ``count``: the indices follow the last
    call's output (a zero the compiler cannot see), so no call is lifted out."""

    @jax.jit
    def run(count, values, idx):
        def body(_, carry):
            shift, total = carry
            out = jax.lax.optimization_barrier(fn(values, idx ^ shift))
            first = out.ravel()[0]
            return shift + (first != first).astype(jnp.int32), total + first

        return jax.lax.fori_loop(0, count, body, (jnp.int32(0), jnp.float32(0)))[1]

    return run


def wall(fn, *args):
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(5):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - start) * 1e3)
    return statistics.median(times)


def operands(cell, seed=0):
    """Sigmoid scores, the ``top_k`` of scores plus a bias, a gradient."""
    n, e, k = CELLS[cell]
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    scores = jax.nn.sigmoid(jax.random.normal(keys[0], (n, e), jnp.float32))
    bias = 0.1 * jax.random.normal(keys[1], (e,), jnp.float32)
    idx = jax.lax.top_k(scores + bias, k)[1]
    return scores, idx, jax.random.normal(keys[2], (n, k), jnp.float32)


def main(argv):
    compile_only = "--compile" in argv
    cells = [a for a in argv if not a.startswith("--")] or list(CELLS)
    sharding = None
    if compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
        device = "described v5e (compiled, not run)"
    else:
        device = jax.devices()[0].device_kind
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    name = "route_compile.jsonl" if compile_only else "route_probe.jsonl"
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as out:
        def say(**line):
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()

        for cell in cells:
            n, e, k = CELLS[cell]
            say(device=device, cell=cell, tokens=n, experts=e, top_k=k)
            if not compile_only:
                scores, idx, grad = operands(cell)
            want = {}
            for form, how, forward, backward in forms():
                directions = (
                    ("forward", forward, (n, e)),
                    ("backward", functools.partial(backward, e=e), (n, k)),
                )
                for direction, fn, shape in directions:
                    line = dict(cell=cell, form=form, direction=direction, **how)
                    run = looped(fn)
                    try:
                        if compile_only:
                            sds = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=sharding)  # noqa: E731
                            run.lower(
                                sds((), jnp.int32), sds(shape, jnp.float32),
                                sds((n, k), jnp.int32),
                            ).compile()
                            say(**line, compiled=True)
                            continue
                        values = scores if direction == "forward" else grad
                        got = np.asarray(jax.jit(fn)(values, idx))
                        line["equal"] = bool(
                            np.array_equal(got, want.setdefault(direction, got))
                        )
                        ms = (wall(run, 21, values, idx) - wall(run, 1, values, idx)) / 20
                        say(**line, ms=round(ms, 4))
                    except Exception as exc:  # noqa: BLE001 — a form the compiler refuses is a result
                        say(**line, error=repr(exc)[-300:])


if __name__ == "__main__":
    main(sys.argv[1:])
