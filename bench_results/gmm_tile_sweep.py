"""PR 57's chip probe: Megablox's three kernels alone (``gmm`` for the value,
``gmm`` over the transposed bank for ``d lhs``, ``tgmm`` for ``d rhs``) at the
held-share shapes of the expert cells, over the row tiles 128 / 256 / 384 / 512
(128 / 256 / 512 at groups of 2048 rows and more) and, for the two ``gmm``
forms, over the contracting dimension whole or split at 1024 with the two
widest column tiles that fit beside it. It passes its own tilings to the
kernels, so it reads the same whatever ``ops/grouped_matmul.py:_fit`` chooses;
``bench_results/README.md`` has PR 57's table.

    chiprun --chips 1 -- python3 bench_results/gmm_tile_sweep.py [cell ...]
    JAX_PLATFORMS=cpu python3 bench_results/gmm_tile_sweep.py --compile [cell ...]

``--compile`` times nothing: it compiles every variant for a described v5e
(no chip) and says which the compiler refuses (a working set over the scoped
VMEM a kernel has by default).

A cell's shape is ``G`` groups sharing ``live`` rows of a buffer of ``m``
(twice the balanced share, as ``models/moe.py`` sizes it), the groups' sizes
drawn as a router draws them (a Dirichlet share each, the last group left
with eight rows), over the banks ``[G, d, f]`` (gate / up) and ``[G, f, d]``
(down). A kernel is timed as (a ``while`` loop of 41 calls - a loop of 1) / 40
of one compiled program, the next call's group sizes made to depend on this
call's output, so the host's launch (a millisecond here) is in neither and no
call is hoisted; each call brings its group search, as in a step. Prints one
JSON line a variant and writes them to ``chiprun_out/gmm_tile_sweep.jsonl``.
A probe ranks; it does not size (PERF.md, PR 48).
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

# cell: (groups, buffer rows m, live rows, model width d, expert width f); Solar's
# buffer is 3280 rows, which ``grouped_matmul`` pads to whole row tiles: 3584 at
# the parent's 512 (taken here), 3328 at 256
CELLS = {
    "lfm2_glm": (8, 8192, 4096, 2048, 1536),
    "solar": (8, 3584, 1776, 4096, 1280),
    "olmoe": (64, 131072, 131072, 2048, 1024),
    "trinity": (16, 16384, 8192, 2048, 1024),
    "ling": (8, 2048, 1024, 2560, 768),
    "nemotron": (8, 5632, 1200, 1024, 2688),
    "whole_nk": (8, 65536, 9600, 2048, 1536),
}
ROW_TILES = (128, 256, 384, 512)
SPLIT = 1024  # the parent's contracting and column tile
VMEM = 16 * 2**20  # the scoped VMEM a kernel gets with no ``vmem_limit_bytes``
SECONDS = float(os.environ.get("GMM_SWEEP_SECONDS", "1300"))  # stop starting variants


def sizes_of(groups, live, seed=0):
    rng = np.random.default_rng(seed)
    share = rng.dirichlet(np.full(groups - 1, 8.0))
    sizes = np.append(rng.multinomial(live - 8, share), 8)
    return jnp.asarray(sizes, jnp.int32)


def variants(cell):
    """``(kernel, bank, tiling)`` for every variant of a cell, the parent's own
    tiling first in each group."""
    groups, m, live, d, f = CELLS[cell]
    tms = ROW_TILES if m // groups < 2048 else (128, 256, 512)
    for bank, (k, n) in (("up", (d, f)), ("down", (f, d))):
        for kernel, (contract, columns) in (("gmm", (k, n)), ("gmm_dlhs", (n, k))):
            split = (gm._whole(SPLIT, contract), gm._whole(SPLIT, columns))
            tiles = [split]
            for tn in list(gm._column_tiles(SPLIT, columns))[:2]:
                whole = (contract, tn)
                if whole not in tiles and gm._working_set(128, *whole, 2) <= VMEM:
                    tiles.append(whole)
            for tm in tms:
                for tk, tn in tiles:
                    yield kernel, bank, (tm, tk, tn)
        for tm in tms:
            yield "tgmm", bank, (tm, gm._whole(SPLIT, k), gm._whole(SPLIT, n))


def program(kernel, tiling, groups):
    """``(lhs, rhs, sizes) -> out`` of one Megablox kernel at a tiling."""
    backend = gm._megablox()
    bf16 = jnp.bfloat16
    if kernel == "gmm":
        return lambda a, b, s: backend.gmm(a, b, s, bf16, tiling)
    if kernel == "gmm_dlhs":
        return lambda a, b, s: backend.gmm(a, b, s, bf16, tiling, transpose_rhs=True)
    return lambda a, b, s: backend.tgmm(
        a.swapaxes(0, 1), b, s, bf16, tiling, num_actual_groups=groups
    )


def shapes(kernel, bank, cell, tm):
    """The two operands' shapes of a kernel at a cell's bank, the rows padded to
    whole row tiles as ``grouped_matmul`` pads them."""
    groups, m, _, d, f = CELLS[cell]
    k, n = (d, f) if bank == "up" else (f, d)
    m = -(-m // tm) * tm
    if kernel == "gmm":
        return (m, k), (groups, k, n)
    if kernel == "gmm_dlhs":
        return (m, n), (groups, k, n)
    return (m, k), (m, n)


def looped(fn):
    @jax.jit
    def run(count, a, b, sizes):
        def body(_, carry):
            sizes, total = carry
            first = fn(a, b, sizes).ravel()[0].astype(jnp.float32)
            return sizes + (first != first).astype(jnp.int32), total + first

        return jax.lax.fori_loop(0, count, body, (sizes, jnp.float32(0)))[1]

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
    name = "gmm_tile_compile.jsonl" if compile_only else "gmm_tile_sweep.jsonl"
    started = time.perf_counter()
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as out:
        def say(**line):
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()

        for cell in cells:
            groups, m, live, d, f = CELLS[cell]
            sizes = sizes_of(groups, live)
            say(device=device, cell=cell, groups=groups, rows=m, live=live,
                sizes=[int(s) for s in sizes])
            keys = jax.random.split(jax.random.PRNGKey(0), 2)
            for kernel, bank, tiling in variants(cell):
                if time.perf_counter() - started > SECONDS:
                    say(cell=cell, stopped="out of time")
                    return
                line = dict(cell=cell, kernel=kernel, bank=bank, tiling=list(tiling),
                            vmem_mib=round(gm._working_set(*tiling, 2) / 2**20, 2))
                run = looped(program(kernel, tiling, groups))
                shape_a, shape_b = shapes(kernel, bank, cell, tiling[0])
                try:
                    if compile_only:
                        args = [jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)] + [
                            jax.ShapeDtypeStruct(s, t, sharding=sharding)
                            for s, t in ((shape_a, jnp.bfloat16), (shape_b, jnp.bfloat16),
                                         ((groups,), jnp.int32))
                        ]
                        run.lower(*args).compile()
                        say(**line, compiled=True)
                        continue
                    a = jax.random.normal(keys[0], shape_a, jnp.bfloat16)
                    b = jax.random.normal(keys[1], shape_b, jnp.bfloat16)
                    ms = (wall(run, 41, a, b, sizes) - wall(run, 1, a, b, sizes)) / 40
                    k, n = (d, f) if bank == "up" else (f, d)
                    say(**line, ms=round(ms, 4),
                        roofline=round(2.0 * live * k * n / (ms * 1e-3) / 197e12, 4))
                except Exception as exc:  # noqa: BLE001 — a tiling the compiler refuses is a result
                    say(**line, error=repr(exc)[-300:])


if __name__ == "__main__":
    main(sys.argv[1:])
