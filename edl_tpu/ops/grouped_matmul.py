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
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from edl_tpu.obs import trace as obs_trace

# (rows, contracting, columns) tiles of the three Megablox kernels (value, row
# gradient, weight gradient; for the last "rows" is the contracted dimension).
# Measured on the v5e at the OLMoE cell's shapes (PERF.md section 6, PR 25):
# the fastest of those that fit VMEM with all three kernels. The rows of a
# tile belong to one group or are masked, so the row tile bounds the waste at
# a group's edge (64 edges of at most 512 rows in 131,072 at the OLMoE cell's
# shape); the other two keep a whole [K, N] slab of one expert in VMEM across
# that expert's row tiles. Measured at widths of 1024 and 2048 (OLMoE's and
# Trinity's experts over a model of 2048), which 1024 divides; elsewhere
# ``_fit`` takes, for K and for N alike, the largest whole number of lane
# tiles (128) that divides the dimension and is at most this one's, so that no
# tile is ragged: at an expert width of 1536, 768 (1024 there is one tile and
# a masked half, a third of the kernel's work wasted; PERF.md section 6, PR 37,
# has the probe's numbers for 768 against 512). The row tile is of ALL the
# groups' rows together (``m``), not of one group's: a tile that spans a
# group's end is walked once for each group in it, so where groups are small
# beside 512 rows (``ling_3_0_flash_vl.steady``: 128 rows a held expert
# expected, four groups a tile) most of a tile's rows are masked for each of
# them; not tuned here (PERF.md section 7). The latent expert layer
# (``nemotron_3_super_120b_a12b.steady``) reads ``gmm_tiles`` (512, 1024, 896)
# over 1024 x 2688 and (512, 896, 1024) over 2688 x 1024 (2688 is 21 lane
# tiles: 896 = 7 of them is the largest whole divisor under 1024), on a buffer
# of 5632 rows at 352 rows a held expert expected: a row tile of 512 then holds
# the edge of one or two groups, and is walked once for each; open, not tuned.
TILING = (512, 1024, 1024)
_LANES = 128

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


def _fit(tiling: Tuple[int, int, int], m: int, k: int, n: int):
    """``tiling`` cut to the problem: no tile larger than its dimension, and
    those of K and N dividing theirs where a whole number of lane tiles does
    (Megablox masks a ragged last tile of K and N, not one of M, so M is
    padded by the caller to a whole number of row tiles)."""
    tm, tk, tn = tiling
    return min(tm, m), _whole(tk, k), _whole(tn, n)


def _note_tiles(kernel: str, m: int, k: int, n: int, tiling):
    """One ``gmm_tiles`` instant in the span ring for each shape a Megablox
    kernel is traced at in a stage (``note_once``), with the tiling it was
    given."""
    obs_trace.get_tracer().note_once(
        "gmm_tiles", kernel=kernel, rows=m, contracting=k, columns=n,
        tiling=list(tiling), path="kernel",
    )
    return tiling


def _megablox():
    # the package's ``gmm`` attribute is its custom-vjp function, which
    # fixes one tiling for all three kernels; the module has the kernels
    import importlib

    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm"
    )


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pallas(lhs, rhs, group_sizes, interpret):
    m, k = lhs.shape
    n = rhs.shape[2]
    with obs_trace.span("kernel_trace", kernel="gmm"):
        return _megablox().gmm(
            lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
            tiling=_note_tiles("gmm", m, k, n, _fit(TILING, m, k, n)),
            interpret=interpret,
        )


def _pallas_fwd(lhs, rhs, group_sizes, interpret):
    return _pallas(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def _pallas_bwd(interpret, residuals, grad):
    lhs, rhs, group_sizes = residuals
    m, k = lhs.shape
    n = rhs.shape[2]
    backend = _megablox()
    grad = grad.astype(lhs.dtype)
    with obs_trace.span("kernel_trace", kernel="gmm_dlhs"):
        d_lhs = backend.gmm(
            grad, rhs, group_sizes, preferred_element_type=lhs.dtype,
            tiling=_note_tiles("gmm_dlhs", m, n, k, _fit(TILING, m, n, k)),
            transpose_rhs=True,
            interpret=interpret,
        )
    with obs_trace.span("kernel_trace", kernel="tgmm"):
        d_rhs = backend.tgmm(
            lhs.swapaxes(0, 1), grad, group_sizes,
            preferred_element_type=rhs.dtype,
            tiling=_note_tiles("tgmm", m, k, n, _fit(TILING, m, k, n)),
            num_actual_groups=rhs.shape[0],
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
    m = lhs.shape[0]
    tm = min(TILING[0], -(-m // 8) * 8)  # a row tile is whole sublanes
    pad = -m % tm
    if pad:  # rows that belong to no group, cut off again below
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _pallas(lhs, rhs, group_sizes, interpret)
    return out[:m] if pad else out
