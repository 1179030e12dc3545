"""Grouped matrix multiplication over ragged groups of rows.

``grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G]) -> [M, N]``:
the rows of ``lhs`` are sorted by group, group ``g`` owns the next
``group_sizes[g]`` rows and multiplies them by ``rhs[g]``. The shapes are
static whatever the sizes are (a group may be empty, none need be a
multiple of a tile), which is what a dropless mixture-of-experts layer
needs: no capacity, no padding to the busiest expert.

Two implementations, one contract (value, ``d lhs``, ``d rhs``):

- ``"pallas"``: jax's own Megablox kernels
  (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` for the value
  and ``d lhs``, ``tgmm`` for ``d rhs``), tiled for the v5e. Taken on a
  TPU backend.
- ``"ragged_dot"``: ``jax.lax.ragged_dot`` and its own transposes. Taken
  everywhere else (on the CPU it is the only one that is not an
  interpreter).

Which one runs is decided from ``jax.default_backend()`` when the caller
names none; the argument is for tests and for the probe that times both
on the chip (``benchmark/tools/grouped_matmul_probe.py``). Each shape says
which it took in its ``gmm_tiles`` note: ``path`` ``kernel`` or ``plain``,
and on ``plain`` ``why`` (``backend``, or ``asked`` by the caller).

``rows_summed_by_segment(rows [M, D], segments [M], S) -> [S, D]`` is the
same kernel family put to a sum: ``out[s]`` is the float32 sum of the rows
whose segment is ``s``. On a TPU the rows are sorted by segment and the
segments taken ``SEGMENT_TILE`` at a time: a tile's rows are one ragged
group, and Megablox's ``tgmm`` of the rows' one-hot place in their tile
(exact in bfloat16) against the rows is the tile's ``[SEGMENT_TILE, D]``
sums. Everywhere else ``jax.ops.segment_sum``. A held share's expert layer
un-sorts its buffer with it (``models/moe.py:_sum_unsorted``), so that pass
follows the buffer's rows and not the ``N k`` pairs.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from edl_tpu.obs import trace as obs_trace

# How Megablox's three kernels are tiled, (rows, contracting, columns) each
# (``gmm`` for the value, ``gmm`` over the transposed bank for ``d lhs``,
# ``tgmm`` for ``d rhs``, whose "contracting" and "columns" are the two sides
# of a bank and whose contracted dimension is the rows). Fitted on the v5e at
# the expert cells' held-share shapes: ``bench_results/gmm_tile_sweep.py``,
# its table in bench_results/README.md (PR 57).
#
# - **The row tile.** A tile's rows belong to one group or are masked, and a
#   tile that holds a group's end is walked once for every group in it: a call
#   walks about ``live + (G - 1) * tm`` rows, so where a held expert gets a few
#   hundred rows a tile of 512 is mostly other groups' masked rows. ``gmm``
#   takes 256 at every length of group measured (it loads its ``[K, tn]`` slab
#   into the MXU whatever the rows are, and 128 rows do not cover that), 128
#   where a full call's mean group ``m // G`` is shorter than 256; ``tgmm``,
#   whose step costs in proportion to its rows (its masks and its transpose
#   are float32 passes over the row tiles), takes 128 until ``m // G`` reaches
#   2048 and 256 from there.
# - **The contracting tile is the whole K wherever that fits** (``gmm``'s grid
#   is (column tiles, row visits, K tiles), K innermost: with one K tile the
#   bank's ``[K, tn]`` block keeps its index across a group's consecutive row
#   tiles and is fetched once a group and column tile; with two it changes at
#   every grid step and crosses HBM again at every visit). The column tile is
#   cut, to a whole number of lane tiles that divides N, before K is split.
#   What fits: two buffers each of the ``[tm, K]`` rows, the ``[K, tn]`` slab
#   and the ``[tm, tn]`` output, and the float32 accumulator, within
#   ``_VMEM_COUNTED``: the 16 MiB a kernel is given with no
#   ``vmem_limit_bytes`` (Megablox passes none) less the 2 MiB Mosaic was
#   seen to add (0 to 1.8 MiB, by bisecting ``xla_tpu_scoped_vmem_limit_kib``
#   on a compile for a described v5e). ``tgmm`` contracts the rows and has
#   nothing to hold.
# - ``TILING`` is the most each tile is: a row tile, the contracting tile of a
#   ``gmm`` whose K cannot be whole (and of ``tgmm``'s bank), a column tile.
#   ``_whole`` takes, for K and for N alike, the largest whole number of lane
#   tiles (128) that divides the dimension and is at most the tile asked for,
#   so that no tile is ragged (at an expert width of 1536, 768; at 2688 = 21
#   lane tiles, 896). No cell has a K that must be split; where one is, and
#   groups are long, 512 rows were faster than 256 (PR 25, this sweep: 14% at
#   OLMoE's shape), which this rule does not take up.
TILING = (256, 1024, 1024)
_LANES = 128
_VMEM_COUNTED = (16 - 2) * 2**20
_KERNELS = ("gmm", "gmm_dlhs", "tgmm")
# Segments a group of ``rows_summed_by_segment``'s ``tgmm``: the one-hot's
# width, so a lane tile (256 and 512 read the same at the held-share cells'
# buffers on the v5e, and the whole width as one column tile 1-16% faster than
# ``_whole``'s: ``bench_results/segment_sum_probe.py``, PERF.md section 6, PR 60).
SEGMENT_TILE = 128

IMPLEMENTATIONS = ("pallas", "ragged_dot")


def default_implementation() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ragged_dot"


def _whole(most: int, dim: int) -> int:
    """The tile of a dimension ``dim`` of K or N: ``dim`` itself where it is
    at most ``most``, else the largest multiple of 128 at most ``most`` that
    divides it, else (none does) ``most`` and a ragged last tile, which
    Megablox masks."""
    if dim <= most:
        return dim
    for tile in range(most - most % _LANES, 0, -_LANES):
        if dim % tile == 0:
            return tile
    return most


def _row_tile(kernel: str, m: int, groups: int) -> int:
    """The row tile of a call of ``m`` rows in ``groups`` groups: whole lane
    tiles (the kernels' masks and ``tgmm``'s transposed product are cut in
    128s), or the whole of a small ``m`` in whole sublanes."""
    if m <= _LANES:
        return -(-m // 8) * 8
    rows = m // groups // (8 if kernel == "tgmm" else 1)
    return min(max(rows // _LANES, 1) * _LANES, TILING[0])


def _column_tiles(most: int, n: int):
    """The column tiles of N to try beside a whole K, widest first:
    ``_whole``'s, then every smaller whole number of lane tiles that divides
    N."""
    first = _whole(most, n)
    yield first
    for tile in range((first - 1) // _LANES * _LANES, 0, -_LANES):
        if n % tile == 0:
            yield tile


def _working_set(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """Bytes of VMEM a ``gmm`` step is counted to hold at a tiling."""
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def _fit(kernel: str, m: int, groups: int, k: int, n: int, itemsize: int):
    """The tiling of one of the three kernels for ``[m, k]`` rows in
    ``groups`` groups over banks ``[groups, k, n]``: arithmetic on the shapes
    alone. No tile is larger than its dimension, and those of K and N divide
    theirs where a whole number of lane tiles does (Megablox masks a ragged
    last tile of K and N, not one of M, so M is padded by the caller to whole
    row tiles)."""
    tm = _row_tile(kernel, m, groups)
    if kernel == "tgmm":
        return tm, _whole(TILING[1], k), _whole(TILING[2], n)
    if kernel == "gmm_dlhs":  # [m, n] x [n, k]: contracts the bank's columns
        k, n = n, k
    for tn in _column_tiles(TILING[2], n):
        if _working_set(tm, k, tn, itemsize) <= _VMEM_COUNTED:
            return tm, k, tn
    return tm, _whole(TILING[1], k), _whole(TILING[2], n)


def _fit_segments(m: int, groups: int, d: int, itemsize: int):
    """The tiling of ``rows_summed_by_segment``'s ``tgmm`` for ``[m, d]`` rows
    in ``groups`` tiles of ``SEGMENT_TILE`` segments: ``tgmm``'s row tile, the
    one-hot whole, and the widest column tile (the whole width first, so the
    one-hot is read once) whose counted working set fits: two buffers each of
    the rows' and the one-hot's blocks and of the float32 output's, its
    accumulator, and the float32 copies the kernel's masks make of both."""
    tm, tile = _row_tile("tgmm", m, groups), SEGMENT_TILE
    for tn in _column_tiles(d, d):
        held = (2 * itemsize + 4) * tm * (tn + tile) + 3 * 4 * tile * tn
        if held <= _VMEM_COUNTED:
            return tm, tile, tn
    return tm, tile, _whole(_LANES, d)


def _tilings(m: int, groups: int, k: int, n: int, itemsize: int):
    """The three kernels' tilings for one call, in ``_KERNELS``' order."""
    return tuple(_fit(kernel, m, groups, k, n, itemsize) for kernel in _KERNELS)


def _note_tiles(kernel: str, m: int, groups: int, k: int, n: int, tiling):
    """One ``gmm_tiles`` instant in the span ring for each shape a Megablox
    kernel is traced at in a stage (``note_once``), with the tiling it was
    given and what that was chosen from: the groups, a full call's mean rows a
    group, and the most grid steps along the rows the call can take (a row
    tile is visited once, and once more for each group that starts inside
    one)."""
    obs_trace.get_tracer().note_once(
        "gmm_tiles", kernel=kernel, rows=m, contracting=k, columns=n,
        tiling=list(tiling), path="kernel", groups=groups,
        rows_a_group=m // groups, visits_bound=-(-m // tiling[0]) + groups - 1,
    )
    return tiling


def _megablox():
    # the package's ``gmm`` attribute is its custom-vjp function, which
    # fixes one tiling for all three kernels; the module has the kernels
    import importlib

    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm"
    )


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _pallas(lhs, rhs, group_sizes, tilings, interpret):
    """``tilings``: the three kernels' (``_tilings``), of which the rows of
    ``lhs`` are whole tiles."""
    m, k = lhs.shape
    groups, _, n = rhs.shape
    with obs_trace.span("kernel_trace", kernel="gmm"):
        return _megablox().gmm(
            lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
            tiling=_note_tiles("gmm", m, groups, k, n, tilings[0]),
            interpret=interpret,
        )


def _pallas_fwd(lhs, rhs, group_sizes, tilings, interpret):
    return _pallas(lhs, rhs, group_sizes, tilings, interpret), (lhs, rhs, group_sizes)


def _pallas_bwd(tilings, interpret, residuals, grad):
    lhs, rhs, group_sizes = residuals
    m, k = lhs.shape
    groups, _, n = rhs.shape
    backend = _megablox()
    grad = grad.astype(lhs.dtype)
    with obs_trace.span("kernel_trace", kernel="gmm_dlhs"):
        d_lhs = backend.gmm(
            grad, rhs, group_sizes, preferred_element_type=lhs.dtype,
            tiling=_note_tiles("gmm_dlhs", m, groups, n, k, tilings[1]),
            transpose_rhs=True,
            interpret=interpret,
        )
    with obs_trace.span("kernel_trace", kernel="tgmm"):
        d_rhs = backend.tgmm(
            lhs.swapaxes(0, 1), grad, group_sizes,
            preferred_element_type=rhs.dtype,
            tiling=_note_tiles("tgmm", m, groups, k, n, tilings[2]),
            num_actual_groups=groups,
            interpret=interpret,
        )
    return d_lhs, d_rhs, None


_pallas.defvjp(_pallas_fwd, _pallas_bwd)


def grouped_matmul(
    lhs: jax.Array,
    rhs: jax.Array,
    group_sizes: jax.Array,
    implementation: Optional[str] = None,
    interpret: bool = False,
) -> jax.Array:
    """``out[r] = lhs[r] @ rhs[group of row r]`` in ``lhs.dtype``, with
    float32 accumulation. ``group_sizes`` (int32, ``[G]``) sums to ``M``;
    rows past the sum, if any, come back as zeros. Differentiable in
    ``lhs`` and ``rhs``. ``interpret`` runs the Pallas kernels in the
    interpreter (tests on the CPU)."""
    if lhs.ndim != 2 or rhs.ndim != 3 or lhs.shape[1] != rhs.shape[1]:
        raise ValueError(
            "grouped_matmul: lhs %s and rhs %s are not [M, K] and [G, K, N]"
            % (lhs.shape, rhs.shape)
        )
    why = "asked"  # the caller named the plain form itself
    if implementation is None:
        implementation, why = default_implementation(), "backend"
    group_sizes = group_sizes.astype(jnp.int32)
    rhs = rhs.astype(lhs.dtype)
    if implementation == "ragged_dot":
        obs_trace.get_tracer().note_once(
            "gmm_tiles", kernel="ragged_dot", rows=lhs.shape[0],
            contracting=lhs.shape[1], columns=rhs.shape[2], path="plain", why=why,
        )
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes, preferred_element_type=jnp.float32
        ).astype(lhs.dtype)
    if implementation != "pallas":
        raise ValueError(
            "grouped_matmul: implementation %r is none of %r"
            % (implementation, IMPLEMENTATIONS)
        )
    m, k = lhs.shape
    groups, _, n = rhs.shape
    tilings = _tilings(m, groups, k, n, lhs.dtype.itemsize)
    pad = -m % math.lcm(*(tiling[0] for tiling in tilings))
    if pad:  # rows that belong to no group, cut off again below
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _pallas(lhs, rhs, group_sizes, tilings, interpret)
    return out[:m] if pad else out


def rows_summed_by_segment(
    rows: jax.Array,
    segments: jax.Array,
    num_segments: int,
    dtype=jnp.float32,
    implementation: Optional[str] = None,
    interpret: bool = False,
) -> jax.Array:
    """``out[s] = sum of rows[r] over the r with segments[r] == s``:
    ``[num_segments, D]``, each row read in its own dtype, summed in float32
    and rounded once to ``dtype``. ``segments`` (int32,
    ``[M]``, in any order) may name no segment (``num_segments`` or more): such
    a row is nobody's and counts as zeros whatever it holds, and a segment no
    row names comes back as zeros. The work follows ``M``: one sort of ``M``
    keys, one gather of ``M`` rows and a ``tgmm`` over them on a TPU (bfloat16
    rows; the kernel has no derivative, and its callers are ``custom_vjp``
    rules), ``jax.ops.segment_sum`` everywhere else."""
    if rows.ndim != 2 or segments.shape != rows.shape[:1]:
        raise ValueError(
            "rows_summed_by_segment: rows %s and segments %s are not [M, D] and [M]"
            % (rows.shape, segments.shape)
        )
    m, d = rows.shape
    why = "asked"
    if implementation is None:
        implementation, why = default_implementation(), "backend"
        if implementation == "pallas" and rows.dtype != jnp.bfloat16:
            # the MXU would round wider rows to bfloat16 before it sums them
            implementation, why = "ragged_dot", "dtype"
    segments = segments.astype(jnp.int32)
    nobodys = (segments < 0) | (segments >= num_segments)
    if implementation == "ragged_dot":  # the plain form, as ``grouped_matmul`` names it
        obs_trace.get_tracer().note_once(
            "gmm_tiles", kernel="segment_sum", rows=m, contracting=num_segments,
            columns=d, path="plain", why=why,
        )
        return jax.ops.segment_sum(  # an index past the segments is dropped
            rows.astype(jnp.float32), jnp.where(nobodys, num_segments, segments),
            num_segments,
        ).astype(dtype)
    if implementation != "pallas":
        raise ValueError(
            "rows_summed_by_segment: implementation %r is none of %r"
            % (implementation, IMPLEMENTATIONS)
        )
    tile = SEGMENT_TILE
    groups = -(-num_segments // tile)
    tiling = _fit_segments(m, groups, d, rows.dtype.itemsize)
    with jax.named_scope("segment_sum"):
        # nobody's rows sort behind every group, and so do the rows that fill
        # the last row tile: entries of the gather's index, not a pad of its
        # result
        keys = jnp.concatenate([
            jnp.where(nobodys, groups * tile, segments),
            jnp.full((-m % tiling[0],), groups * tile, jnp.int32),
        ])
        keys, by_segment = jax.lax.sort(
            (keys, jnp.minimum(jnp.arange(keys.shape[0], dtype=jnp.int32), m - 1)),
            num_keys=1,
        )
        sizes = jnp.sum(jax.nn.one_hot(keys // tile, groups, dtype=jnp.int32), axis=0)
        place = (keys[None, :] % tile == jnp.arange(tile)[:, None]).astype(rows.dtype)
        with obs_trace.span("kernel_trace", kernel="segment_sum"):
            out = _megablox().tgmm(
                place, rows[by_segment], sizes, preferred_element_type=dtype,
                tiling=_note_tiles("segment_sum", keys.shape[0], groups, tile, d, tiling),
                interpret=interpret,
            )
    out = out.reshape(groups * tile, d)
    return out if groups * tile == num_segments else out[:num_segments]
