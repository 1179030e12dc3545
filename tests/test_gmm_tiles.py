"""How ``ops/grouped_matmul.py`` cuts Megablox's three tiles (PR 57): the rule
held to its contract at every expert cell's call shapes, and the kernels on
those tiles against ``ragged_dot`` in the interpreter."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import EXPERT_CELL_SHAPES as CELLS
from edl_tpu.ops import grouped_matmul

gm = importlib.import_module("edl_tpu.ops.grouped_matmul")

CALLS = [
    pytest.param(cell, branch, bank, kernel, id="-".join((cell, branch, bank, kernel)))
    for cell, shape in CELLS.items()
    for branch in (("buffer", "whole") if shape[1] < shape[2] else ("whole",))
    for bank in ("up", "down")
    for kernel in gm._KERNELS
]


def _lane_tiles(dim):
    return [t for t in range(128, dim + 1, 128) if dim % t == 0]


@pytest.mark.parametrize("cell,branch,bank,kernel", CALLS)
def test_the_rule_at_a_cells_call(cell, branch, bank, kernel):
    groups, buffer, whole, d, f = CELLS[cell]
    m = buffer if branch == "buffer" else whole
    k, n = (d, f) if bank == "up" else (f, d)
    tm, tk, tn = gm._fit(kernel, m, groups, k, n, 2)
    if kernel == "gmm_dlhs":  # contracts the bank's columns, writes its rows
        k, n = n, k
    assert tm % 128 == 0 and tm <= 512
    assert tk in _lane_tiles(k) and tn in _lane_tiles(n)
    if kernel == "tgmm":
        # its step's cost follows its rows: short until a group is 16 such tiles
        assert tm == (128 if m // groups < 2048 else 256) and max(tk, tn) <= 1024
        return
    assert tm == 256  # every cell's mean group is at least that
    assert gm._working_set(tm, tk, tn, 2) <= gm._VMEM_COUNTED < 16 * 2**20
    # K whole wherever the budget allows it beside some column tile, and then
    # beside the widest one that fits
    fitting = [t for t in _lane_tiles(n) if t <= 1024
               and gm._working_set(tm, k, t, 2) <= gm._VMEM_COUNTED]
    assert fitting and (tk, tn) == (k, max(fitting))


@pytest.mark.parametrize("kernel,m,groups,k,n,itemsize,want", [
    # the cells the change was claimed in, as the sweep chose
    ("gmm", 8192, 8, 2048, 1536, 2, (256, 2048, 768)),
    ("gmm_dlhs", 8192, 8, 2048, 1536, 2, (256, 1536, 1024)),
    ("tgmm", 8192, 8, 2048, 1536, 2, (128, 1024, 768)),
    ("gmm", 3280, 8, 4096, 1280, 2, (256, 4096, 256)),  # 640 columns do not fit beside 4096
    ("gmm_dlhs", 3280, 8, 4096, 1280, 2, (256, 1280, 1024)),
    ("tgmm", 3280, 8, 4096, 1280, 2, (128, 1024, 640)),
    ("gmm_dlhs", 5632, 8, 1024, 2688, 2, (256, 2688, 512)),  # 2688 = 21 lane tiles
    ("tgmm", 5632, 8, 1024, 2688, 2, (128, 1024, 896)),
    # the toys': the whole of a small problem, as before
    ("gmm", 128, 4, 64, 96, 4, (128, 64, 96)),
    ("gmm_dlhs", 128, 4, 64, 96, 4, (128, 96, 64)),
    ("tgmm", 128, 4, 64, 96, 4, (128, 64, 96)),
    ("gmm", 64, 8, 16, 32, 4, (64, 16, 32)),
    ("gmm", 100, 2, 16, 32, 4, (104, 16, 32)),  # whole sublanes
    # groups shorter than two lane tiles: one
    ("gmm", 1024, 6, 256, 1536, 4, (128, 256, 768)),
    # no whole number of lane tiles divides: the dimension whole where that
    # fits, else the ragged last tile, as before
    ("gmm", 4096, 8, 1100, 1300, 2, (256, 1100, 1024)),
    ("tgmm", 4096, 8, 1100, 1300, 2, (128, 1024, 1024)),
    # a K that no column tile fits beside: split, as before
    ("gmm", 8192, 8, 16384, 1024, 2, (256, 1024, 1024)),
    ("gmm", 8192, 8, 16384, 1024, 4, (256, 1024, 1024)),
    # float32 rows count twice: 4096 x 128 columns at most beside 256 rows
    ("gmm", 8192, 8, 4096, 1280, 4, (256, 4096, 128)),
])
def test_the_rule_at_a_shape(kernel, m, groups, k, n, itemsize, want):
    assert gm._fit(kernel, m, groups, k, n, itemsize) == want


def test_the_rule_reads_shapes_alone():
    """No argument, environment variable or name picks a tiling (``ops/`` reads
    no environment: ``tests/test_lint.py``): the same shapes, the same tiles."""
    import inspect

    assert list(inspect.signature(gm._fit).parameters) == [
        "kernel", "m", "groups", "k", "n", "itemsize"
    ]
    assert "tiling" not in inspect.signature(grouped_matmul).parameters
    assert gm.TILING == (256, 1024, 1024)


# -- the kernels on short-group tiles, in the interpreter ------------------------

M, SIZES = 1400, (100, 256, 0, 600, 200)  # sums to 1156; 1400 is 5.5 tiles of 256


@pytest.fixture(scope="module")
def short_groups():
    """``[value, d_lhs, d_rhs]`` by the kernels and by ``ragged_dot``, at a
    shape whose row tiles are 256 (``gmm``) and 128 (``tgmm``): groups shorter
    than, equal to and longer than the tile, an empty one, rows past the
    groups' sum, and an ``m`` no tile divides (padded by ``grouped_matmul``)."""
    k, n = 256, 384
    assert gm._tilings(M, len(SIZES), k, n, 4) == (
        (256, 256, 384), (256, 384, 256), (128, 256, 384)
    )
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    lhs = jax.random.normal(keys[0], (M, k), jnp.float32)
    rhs = jax.random.normal(keys[1], (len(SIZES), k, n), jnp.float32) * k ** -0.5
    dy = jax.random.normal(keys[2], (M, n), jnp.float32)
    sizes = jnp.asarray(SIZES, jnp.int32)
    live = (np.arange(M) < sum(SIZES))[:, None]

    def both(implementation):
        def fn(lhs, rhs):
            out = grouped_matmul(lhs, rhs, sizes, implementation, interpret=True)
            return jnp.where(live, out, 0.0)  # a kernel writes no row past the sum

        out, vjp = jax.vjp(fn, lhs, rhs)
        d_lhs, d_rhs = vjp(dy)
        return out, jnp.where(live, d_lhs, 0.0), d_rhs  # nor of its row gradient

    with jax.default_matmul_precision("highest"):
        return [jax.jit(lambda i=i: both(i))() for i in ("pallas", "ragged_dot")]


@pytest.mark.parametrize("what", ["value", "d_lhs", "d_rhs"])
def test_megablox_on_short_group_tiles_is_ragged_dot(short_groups, what):
    index = ("value", "d_lhs", "d_rhs").index(what)
    got, want = short_groups[0][index], short_groups[1][index]
    assert got.shape == want.shape and got.shape[0] in (M, len(SIZES))
    scale = float(np.abs(np.asarray(want)).max())  # float32 sums in another order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-5 * scale)
    if what == "d_rhs":
        assert not np.asarray(got[2]).any()  # the empty group's bank: zeros
