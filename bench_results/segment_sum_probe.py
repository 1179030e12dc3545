"""PR 60's chip probe: the held share's combine alone (``models/moe.py:
_sum_unsorted`` on a buffer: the float32 sum by token of the ``m`` rows the held
experts wrote) at the expert cells' shapes, in every form the issue names:

- ``parent``: the form before PR 60, a gather of ``rows[inverse]`` for all
  ``N k`` pairs, seven eighths masked away, summed over ``k`` neighbours;
- ``op``: ``ops/grouped_matmul.py:rows_summed_by_segment`` as the tree has it;
- ``tgmm``: the same (one sort of ``m`` keys, one gather of ``m`` rows, Megablox's
  ``tgmm`` of the rows' one-hot place in a tile of tokens), over tiles of 128 /
  256 / 512 tokens, row tiles of 128 / 256 and the column tile ``_whole`` gives
  or the whole width; it passes its own tilings, so it reads the same whatever
  ``SEGMENT_TILE`` and ``_fit`` are;
- ``scatter``: ``jax.ops.segment_sum`` of the buffer as it lies (XLA's
  scatter-add on the TPU), and ``scatter_sorted``: the same after the sort and
  the gather, ``indices_are_sorted``;
- ``tgmm_by_tile``: the same with the rows of a tile of tokens left in the
  buffer's own order (sorted by (tile, row), not by token): the gather's
  sources are then runs of neighbouring rows;
- ``sort`` and ``gather``: the new form's two first passes alone, the gather by
  its index pattern (``token``, ``tile_row``, and ``expert``: the forward's
  ``tokens[order // k]``).

    chiprun --chips 1 -- python3 bench_results/segment_sum_probe.py [cell ...]
    JAX_PLATFORMS=cpu python3 bench_results/segment_sum_probe.py --compile [cell ...]

A cell is ``(tokens N, choices k, buffer rows m, live rows, width D)``; a pair
is live with probability ``live / (N k)``, the rows at and past ``live`` hold
NaN (Megablox writes none of them) and every form's result is compared with the
parent's. A form is timed as (a loop of 21 calls - a loop of 1) / 20 of one
compiled program, the next call's ``live`` made to depend on this call's
output (``gmm_tile_sweep.py``'s way). Prints one JSON line a variant and writes
them to ``chiprun_out/segment_sum_probe.jsonl``. A probe ranks; it does not size
(PERF.md, PR 48).
"""

import importlib
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

gm = importlib.import_module("edl_tpu.ops.grouped_matmul")  # the package exports the function

CELLS = {
    "smallthinker": (16384, 6, 24576, 12288, 2560),
    "nemotron": (8192, 22, 5632, 1500, 1024),
    "trinity": (8192, 8, 16384, 8192, 2048),
    "lfm2_glm": (8192, 4, 8192, 4096, 2048),
    "ling": (8192, 8, 2048, 1024, 2560),
    "solar": (8192, 8, 3280, 1640, 4096),
}
TOKEN_TILES = (128, 256, 512)
ROW_TILES = (128, 256)
VMEM = 16 * 2**20


def route(cell, seed=0):
    """``order[:m]``, ``inverse`` and ``live`` of a step of the cell: the live
    pairs sort to the front (by one of eight held experts), the others behind."""
    n, k, m, live, _ = CELLS[cell]
    rng = np.random.default_rng(seed)
    flat = np.where(rng.random(n * k) < live / (n * k), rng.integers(0, 8, n * k), 8)
    order = np.argsort(flat, kind="stable")
    inverse = np.argsort(order, kind="stable")
    live = min(int((flat < 8).sum()), m)
    return (jnp.asarray(order[:m], jnp.int32), jnp.asarray(inverse, jnp.int32),
            jnp.int32(live))


def parent(rows, first_rows, inverse, live, k):
    n = inverse.shape[0] // k
    back = jnp.where(
        (inverse < live)[:, None], rows[jnp.minimum(inverse, rows.shape[0] - 1)], 0
    )
    return jnp.sum(back.reshape(n, k, rows.shape[-1]), axis=1, dtype=jnp.float32)


def tokens_of(first_rows, live, k, n):
    return jnp.where(jnp.arange(first_rows.shape[0]) < live, first_rows // k, n)


def sorted_by_token(token, tm, past, tile=0):
    """``(keys, by_token)``: the rows' tokens in order, padded to whole row
    tiles of ``tm`` with ``past``, and the row each place takes. With ``tile``
    the rows of a tile of tokens stay in the buffer's own order (a tile's rows
    are then a run of neighbours for each held expert, not a token's ``k``
    rows from ``k`` places): the order is by (tile, row), the keys still the
    rows' tokens."""
    m = token.shape[0]
    keys = jnp.concatenate([token, jnp.full((-m % tm,), past, jnp.int32)])
    rows = jnp.minimum(jnp.arange(keys.shape[0], dtype=jnp.int32), m - 1)
    if not tile:
        return jax.lax.sort((keys, rows), num_keys=1)
    _, by_token, keys = jax.lax.sort((keys // tile, rows, keys), num_keys=2)
    return keys, by_token


def by_tgmm(tile, tiling, by_tile=False):
    def run(rows, first_rows, inverse, live, k):
        n = inverse.shape[0] // k
        groups = -(-n // tile)
        token = tokens_of(first_rows, live, k, n)
        keys, by_token = sorted_by_token(
            jnp.where(token >= n, groups * tile, token), tiling[0], groups * tile,
            tile if by_tile else 0,
        )
        sizes = jnp.sum(jax.nn.one_hot(keys // tile, groups, dtype=jnp.int32), axis=0)
        place = (keys[None, :] % tile == jnp.arange(tile)[:, None]).astype(rows.dtype)
        out = gm._megablox().tgmm(
            place, rows[by_token], sizes, preferred_element_type=jnp.float32, tiling=tiling
        )
        return out.reshape(groups * tile, -1)[:n]

    return run


def the_op(rows, first_rows, inverse, live, k):
    n = inverse.shape[0] // k
    return gm.rows_summed_by_segment(rows, tokens_of(first_rows, live, k, n), n)


def scatter(rows, first_rows, inverse, live, k):
    n = inverse.shape[0] // k
    return jax.ops.segment_sum(
        rows.astype(jnp.float32), tokens_of(first_rows, live, k, n), n
    )


def scatter_sorted(rows, first_rows, inverse, live, k):
    n = inverse.shape[0] // k
    keys, by_token = sorted_by_token(tokens_of(first_rows, live, k, n), 1, n)
    return jax.ops.segment_sum(
        rows[by_token].astype(jnp.float32), keys, n, indices_are_sorted=True
    )


def sort_alone(rows, first_rows, inverse, live, k):
    n = inverse.shape[0] // k
    return sorted_by_token(tokens_of(first_rows, live, k, n), 1, n)[1].astype(jnp.float32)


def gather_alone(pattern):
    """The gather of the buffer's ``m`` rows alone, by the index pattern: ``token``
    (the rows in token order: a token's rows lie in ``k`` experts' places),
    ``tile_row`` (by tile of 128 tokens, inside it as the buffer lies) and
    ``expert`` (``_rows_sorted``'s forward, ``tokens[order // k]``: ascending
    inside an expert). Each follows ``live``, so the loop cannot lift it out."""
    def run(rows, first_rows, inverse, live, k):
        n = inverse.shape[0] // k
        if pattern == "expert":
            return rows[(first_rows // k + live) % rows.shape[0]]
        token = tokens_of(first_rows, live, k, n)
        return rows[sorted_by_token(token, 1, n, 128 if pattern == "tile_row" else 0)[1]]

    return run


def variants(cell):
    n, k, m, live, d = CELLS[cell]
    yield "parent", {}, parent
    yield "op", {}, the_op
    for tile in TOKEN_TILES:
        for tm in ROW_TILES:
            for tn in dict.fromkeys((gm._whole(1024, d), d)):
                # two buffers of the rows' and the one-hot's blocks and of the
                # float32 output's, its accumulator, the masks' float32 copies
                held = 2 * 2 * tm * (tn + tile) + 3 * 4 * tile * tn + 2 * 4 * tm * tn
                if held <= VMEM:
                    yield "tgmm", dict(tile=tile, tiling=[tm, tile, tn]), by_tgmm(
                        tile, (tm, tile, tn)
                    )
    for tile in TOKEN_TILES[:2]:
        yield "tgmm_by_tile", dict(tile=tile, tiling=[128, tile, d]), by_tgmm(
            tile, (128, tile, d), by_tile=True
        )
    yield "scatter", {}, scatter
    yield "scatter_sorted", {}, scatter_sorted
    yield "sort", {}, sort_alone
    for pattern in ("token", "tile_row", "expert"):
        yield "gather", dict(pattern=pattern), gather_alone(pattern)


def looped(fn, k):
    @jax.jit
    def run(count, rows, first_rows, inverse, live):
        def body(_, carry):
            live, total = carry
            # the whole result behind a barrier: XLA would cut a fusion to the one
            # element that is read
            out = jax.lax.optimization_barrier(fn(rows, first_rows, inverse, live, k))
            first = out.ravel()[0].astype(jnp.float32)
            return live + (first != first).astype(jnp.int32), total + first

        return jax.lax.fori_loop(0, count, body, (live, jnp.float32(0)))[1]

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
    name = "segment_sum_compile.jsonl" if compile_only else "segment_sum_probe.jsonl"
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as out:
        def say(**line):
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()

        for cell in cells:
            n, k, m, _, d = CELLS[cell]
            first_rows, inverse, live = route(cell)
            say(device=device, cell=cell, tokens=n, top_k=k, pairs=n * k, rows=m,
                live=int(live), width=d)
            want = None
            for form, how, fn in variants(cell):
                line = dict(cell=cell, form=form, **how)
                run = looped(fn, k)
                try:
                    if compile_only:
                        sds = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=sharding)  # noqa: E731
                        with _as_tpu():
                            run.lower(
                                sds((), jnp.int32), sds((m, d), jnp.bfloat16),
                                sds((m,), jnp.int32), sds((n * k,), jnp.int32),
                                sds((), jnp.int32),
                            ).compile()
                        say(**line, compiled=True)
                        continue
                    rows = jax.random.normal(jax.random.PRNGKey(0), (m, d), jnp.bfloat16)
                    rows = jnp.where((jnp.arange(m) >= live)[:, None], jnp.nan, rows)
                    if form not in ("sort", "gather"):
                        got = np.asarray(jax.jit(fn, static_argnums=4)(
                            rows, first_rows, inverse, live, k))
                        want = got if want is None else want
                        line["max_abs_diff"] = float(np.abs(got - want).max())
                    ms = (wall(run, 21, rows, first_rows, inverse, live)
                          - wall(run, 1, rows, first_rows, inverse, live)) / 20
                    say(**line, ms=round(ms, 4))
                except Exception as exc:  # noqa: BLE001 — a tiling the compiler refuses is a result
                    say(**line, error=repr(exc)[-300:])


def _as_tpu():
    """``--compile``: the op chooses its form from the backend, which is the
    CPU here."""
    from unittest import mock

    return mock.patch.object(jax, "default_backend", lambda: "tpu")


if __name__ == "__main__":
    main(sys.argv[1:])
