"""Depthwise causal convolution with its bias and SiLU, as one pass.

``causal_conv_silu(x, kernel, bias)`` is the short convolution in front of a
recurrent mixer's scan, for ``x`` ``[B, T, C]``, ``kernel`` ``[d_conv, C]`` and
``bias`` ``[C]``. The Mamba-2 mixer (``models/mamba.py``) calls it with a bias
on the channels of ``xBC`` in the middle of its in projection's output
(``offset`` = the width of ``z``); the gated-delta-rule mixer
(``models/gated_delta.py``) with ``bias=None`` (the kernels then add a vector of
zeros, whose gradient nobody asks for) on the LEADING channels ``[q | k | v]``
of its own (``offset=0``, so the channel block is what divides ``C`` alone)::

    pre_t = sum_k kernel[k] * x_{t - (d_conv - 1) + k} + bias     zeros before 0
    y_t   = pre_t * sigmoid(pre_t)                                in x's dtype

The taps, their sum, the bias and the SiLU are float32 whatever ``x``'s dtype
is, and the result is rounded once. Two implementations, one contract (value,
``d x``, ``d kernel``, ``d bias``):

- On a TPU backend a pair of Pallas kernels under one ``jax.custom_vjp``,
  ``causal_conv_fwd`` and ``causal_conv_bwd``. They work on the transposed
  activation, ``[B, C, T]`` with time along the lanes: XLA lays the mixer's
  activations out that way for the scan's matmuls, so the two ``swapaxes``
  around the kernels cost nothing, where kernels with the channels along the
  lanes had every neighbour transposing (PERF.md section 6, PR 30). Each kernel
  streams a ``[block of C, block of T]`` tile through VMEM once in ``x``'s
  dtype; the float32 copy, the shifted products, the pre-activation and
  (backward) its cotangent live sixteen channels at a time in a scratch of a
  few hundred KB and never reach HBM. The ``d_conv - 1`` steps a tile needs
  from its neighbour come as a halo: a second view of the same array, one lane
  tile (128 steps) wide, that ends where the tile starts (backward also one
  that starts where it ends), zeroed at the sequence's ends. The backward keeps
  ``(x, kernel, bias)`` only and recomputes the pre-activation; the two
  reductions leave the kernel as float32 partial sums, one a channel a tap a
  tile. ``offset`` reads the ``C`` channels in place out of a wider array
  (the in projection's ``[z | xBC | dt]``), so no slice is copied.
- Everywhere else, and for a shape the kernels do not take (``T`` not a
  multiple of 128, ``C`` or ``offset`` not a multiple of 16, more than seven
  taps), the plain form: :func:`causal_conv` and ``jax.nn.silu`` in float32
  with jax's own backward.

Which one runs is decided from ``jax.default_backend()`` and the shapes, as
``ops/grouped_matmul.py`` decides; ``interpret`` runs the kernels in the
Pallas interpreter (tests on the CPU). Each shape says which it took in a
``conv_shape`` note: ``path`` ``kernel`` or ``plain``, and on ``plain`` ``why``
(``backend``, or ``blocks`` for a shape the kernels do not take).

A third caller, the gated short-convolution mixer (``models/short_conv.py``),
takes :func:`gated_causal_conv`: the same depthwise causal taps between two
elementwise gates, ``C_g * conv(B_g * x)`` over the three thirds ``[B_g | C_g |
x]`` of its in projection's output, read in place. That form leaves out what
the one above is built around, the bias and the SiLU (there is no activation at
all), and is plain ``jax.numpy`` with jax's own backward on every backend: its
neighbours are matmuls over ``[B, T, C]`` with the channels along the lanes, so
XLA keeps that layout and makes the gates, the shifted products and their sum
one fusion forward (PERF.md section 6, PR 37, has the trace that decided it).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from edl_tpu.obs import trace as obs_trace

_HALO = 128             # steps of a halo view: one lane tile
_BLOCK_T = 8192         # steps of a tile, at most: the wider, the less a channel's
                        # fixed work (its taps spread over the lanes, its halo) weighs
_TILE = 1 << 20         # bytes of a tile, at most
_ROWS = 16              # channels the kernels work on at a time: a bfloat16 tile
_LANES = 2048           # steps of them the kernels hold in registers at a time
_SUMS = 8               # partial sums a channel: one a tap, one for the bias


def causal_conv(x, kernel, bias=None):
    """Depthwise causal convolution along T as ``d_conv`` shifted products,
    float32: ``y_t = sum_k kernel[k] * x_{t - (d_conv - 1) + k} (+ bias)``,
    zeros before the start. x ``[B, T, C]``; kernel ``[d_conv, C]``."""
    taps, t = kernel.shape[0], x.shape[1]
    x = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(kernel[k] * x[:, k:k + t] for k in range(taps))
    return y if bias is None else y + bias


def _plain(x, kernel, bias, offset):
    x = x[..., offset:offset + kernel.shape[1]]
    return jax.nn.silu(causal_conv(x, kernel, bias)).astype(x.dtype)


def gated_causal_conv(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """``C_g * causal_conv(B_g * x~, kernel)`` in ``x``'s dtype, ``[B, T, C]``,
    for ``x`` ``[B, T, 3 C]`` = ``[B_g | C_g | x~]`` and ``kernel`` ``[taps,
    C]``: no bias and no activation. The two gates, the taps and their sum are
    float32 and the result is rounded once. Differentiable in ``x`` and
    ``kernel``."""
    taps, c = kernel.shape
    if x.ndim != 3 or x.shape[2] != 3 * c:
        raise ValueError(
            "gated_causal_conv: x %s is not [B, T, 3 * %d]" % (x.shape, c)
        )
    # once a shape and stage; ``bytes`` is one forward pass's least traffic
    # (three reads and one write of ``[B, T, C]``). Plain XLA is the only
    # form there is, so no ``path``
    obs_trace.get_tracer().note_once(
        "sconv_shape", channels=c, taps=taps, steps=x.shape[1], batch=x.shape[0],
        implementation="plain",
        bytes=4 * x.shape[0] * x.shape[1] * c * jnp.dtype(x.dtype).itemsize,
    )
    f32 = jnp.float32
    b_gate, c_gate, inner = (
        x[..., i * c:(i + 1) * c].astype(f32) for i in range(3)
    )
    return (c_gate * causal_conv(b_gate * inner, kernel)).astype(x.dtype)


def _divisor(n: int, most: int, of: int) -> int:
    """The largest multiple of ``of`` that divides ``n`` and is at most
    ``most`` (``of`` divides ``n``)."""
    return max(d for d in range(of, min(most, n) + 1, of) if n % d == 0)


def _blocks(x, kernel, offset):
    """``(block_c, block_t)`` of the kernels for these operands, or None for
    a shape they do not take."""
    taps, c = kernel.shape
    t = x.shape[1]
    if jnp.dtype(x.dtype).itemsize not in (2, 4) or taps + 1 > _SUMS:
        return None
    if t % _HALO or c % _ROWS or offset % _ROWS:
        return None
    block_t = _divisor(t, _BLOCK_T, _HALO)
    most_c = max(_ROWS, _TILE // (block_t * jnp.dtype(x.dtype).itemsize))
    return _divisor(math.gcd(c, offset), most_c, _ROWS), block_t


def _walk(x_ref):
    """How a kernel walks its tile: sixteen channels at a time, ``lanes``
    steps of them at a time (lane tiles, a power of two of them);
    ``(lanes, chunks of channels)``."""
    lanes = _HALO
    while 2 * lanes <= _LANES and x_ref.shape[2] % (2 * lanes) == 0:
        lanes *= 2
    return lanes, x_ref.shape[1] // _ROWS


def _whether(flag):
    """A scalar condition as a ``[16, 128]`` mask."""
    return jnp.full((_ROWS, _HALO), flag, jnp.int32) > 0


def _taps_of(w_ref, b_ref, rows, lanes):
    """The chunk's taps and bias, each spread over ``lanes`` steps once."""
    spread = lambda ref, k: jnp.broadcast_to(ref[rows, k:k + 1], (rows.size, lanes))  # noqa: E731
    return [spread(w_ref, k) for k in range(w_ref.shape[1])], spread(b_ref, 0)


def _later(a, s):
    """``a`` ``[16, n]`` moved ``s`` steps later along the lanes (what falls
    off the end comes round to the start: callers cut a halo off there)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(a, s % a.shape[1], axis=1) if s % a.shape[1] else a


def _pre_activation(xs_ref, t, lanes, w, b):
    """Steps ``t .. t + lanes`` of ``sum_k w[k] * x[. - (taps - 1) + k] + b``
    for an ``xs_ref`` whose column 128 is step 0."""
    taps = len(w)
    a = xs_ref[:, t:t + _HALO + lanes]
    return sum(
        w[k][:, :lanes] * _later(a, taps - 1 - k)[:, _HALO:] for k in range(taps)
    ) + b[:, :lanes]


def causal_conv_fwd(x_ref, before_ref, w_ref, b_ref, y_ref, xs_ref):
    """One ``[block_c, block_t]`` tile of the value. ``xs_ref`` ``[16, 128 +
    block_t]`` float32 holds sixteen channels of the halo and the tile."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    block_t = x_ref.shape[2]
    lanes, chunks = _walk(x_ref)
    inside = _whether(pl.program_id(2))  # the halo lies in the sequence

    def chunk(r, carry):
        rows = pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS)
        xs_ref[:, :_HALO] = jnp.where(inside, before_ref[0, rows, :].astype(f32), 0.0)
        xs_ref[:, _HALO:] = x_ref[0, rows, :].astype(f32)
        w, b = _taps_of(w_ref, b_ref, rows, lanes)
        for t in range(0, block_t, lanes):
            pre = _pre_activation(xs_ref, t, lanes, w, b)
            y_ref[0, rows, t:t + lanes] = (pre / (1.0 + jnp.exp(-pre))).astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, chunks, chunk, 0)


def causal_conv_bwd(x_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref,
                    b_ref, dx_ref, sums_ref, xs_ref, dp_ref):
    """One tile of ``d x`` and, a channel, of the partial sums of ``d
    kernel`` (columns ``0 .. taps - 1`` of ``sums_ref``) and ``d bias``
    (column ``taps``).

    ``xs_ref`` ``[16, 128 + block_t + 128]`` float32: halo, tile, halo.
    ``dp_ref`` ``[16, block_t + 128]`` float32: the pre-activation's cotangent
    for the tile and the 128 steps after it (zeros past the end), so ``d x_t =
    sum_j kernel[taps - 1 - j] * dp[t + j]`` and ``d kernel[taps - 1 - j] =
    sum_t x_t * dp[t + j]`` over the tile's steps."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    taps, block_t = w_ref.shape[1], x_ref.shape[2]
    lanes, chunks = _walk(x_ref)
    inside = _whether(pl.program_id(2))
    more = _whether(pl.num_programs(2) - 1 - pl.program_id(2))

    def fold(v):  # [16, lanes] -> [16, 128], whole registers added
        while v.shape[1] > _HALO:
            half = v.shape[1] // 2
            v = v[:, :half] + v[:, half:]
        return v

    def chunk(r, carry):
        rows = pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS)
        xs_ref[:, :_HALO] = jnp.where(inside, before_ref[0, rows, :].astype(f32), 0.0)
        xs_ref[:, _HALO:_HALO + block_t] = x_ref[0, rows, :].astype(f32)
        xs_ref[:, _HALO + block_t:] = after_ref[0, rows, :].astype(f32)
        w, b = _taps_of(w_ref, b_ref, rows, lanes)

        def d_pre(t, n, dy):
            pre = _pre_activation(xs_ref, t, n, w, b)
            s = 1.0 / (1.0 + jnp.exp(-pre))
            return dy.astype(f32) * (s * (1.0 + pre * (1.0 - s)))

        for t in range(0, block_t, lanes):
            dp_ref[:, t:t + lanes] = d_pre(t, lanes, dy_ref[0, rows, t:t + lanes])
        dp_ref[:, block_t:] = jnp.where(
            more, d_pre(block_t, _HALO, dy_after_ref[0, rows, :]), 0.0
        )

        sums = [jnp.zeros((_ROWS, _HALO), f32) for _ in range(taps + 1)]
        for t in range(0, block_t, lanes):
            x = xs_ref[:, _HALO + t:_HALO + t + lanes]
            a = dp_ref[:, t:t + lanes + _HALO]
            dx = None
            for j in range(taps):
                dp = _later(a, -j)[:, :lanes]
                term = w[taps - 1 - j] * dp
                dx = term if dx is None else dx + term
                sums[taps - 1 - j] = sums[taps - 1 - j] + fold(x * dp)
                if j == 0:
                    sums[taps] = sums[taps] + fold(dp)
            dx_ref[0, rows, t:t + lanes] = dx.astype(dx_ref.dtype)
        for k in range(taps + 1):
            sums_ref[0, 0, rows, k:k + 1] = sums[k].sum(axis=1, keepdims=True)
        if taps + 1 < _SUMS:
            sums_ref[0, 0, rows, taps + 1:] = jnp.zeros((_ROWS, _SUMS - taps - 1), f32)
        return carry

    jax.lax.fori_loop(0, chunks, chunk, 0)


def _independent_tiles():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel")
    )


# jitted so that a step traces and lowers each kernel once, not once a layer
# and pass; a kernel's body is traced under the whole model's call stack, and
# what that costs grows with the body (PERF.md section 6, PR 30: 15 bodies
# unrolled four times finer added 14 s to every start of the step)
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _forward(xt, w, b, offset, blocks, interpret):
    """``y`` ``[B, C, T]`` for ``xt`` ``[B, channels, T]``, ``w`` ``[C, taps]``
    and ``b`` ``[C, 1]``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_c, block_t = blocks
    batch, _, t = xt.shape
    c, taps = w.shape
    first_c, per_t = offset // block_c, block_t // _HALO
    kernel = pl.pallas_call(
        causal_conv_fwd,
        name="causal_conv_fwd",
        out_shape=jax.ShapeDtypeStruct((batch, c, t), xt.dtype),
        grid=(batch, c // block_c, t // block_t),
        in_specs=[
            pl.BlockSpec((1, block_c, block_t), lambda b, j, i: (b, first_c + j, i)),
            pl.BlockSpec(
                (1, block_c, _HALO),
                lambda b, j, i: (b, first_c + j, jnp.maximum(i * per_t - 1, 0)),
            ),
            pl.BlockSpec((block_c, taps), lambda b, j, i: (j, 0)),
            pl.BlockSpec((block_c, 1), lambda b, j, i: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_t), lambda b, j, i: (b, j, i)),
        scratch_shapes=[pltpu.VMEM((_ROWS, _HALO + block_t), jnp.float32)],
        compiler_params=_independent_tiles(),
        interpret=interpret,
    )
    with obs_trace.span("kernel_trace", kernel="causal_conv_fwd"):
        return kernel(xt, xt, w, b)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _backward(xt, w, b, dy, offset, blocks, interpret):
    """``(d x [B, C, T], partial sums [C, 8])`` for ``dy`` ``[B, C, T]``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_c, block_t = blocks
    batch, _, t = xt.shape
    c, taps = w.shape
    first_c, per_t = offset // block_c, block_t // _HALO
    num_t = t // block_t
    before = lambda i: jnp.maximum(i * per_t - 1, 0)  # noqa: E731
    after = lambda i: jnp.minimum((i + 1) * per_t, t // _HALO - 1)  # noqa: E731
    kernel = pl.pallas_call(
        causal_conv_bwd,
        name="causal_conv_bwd",
        out_shape=[
            jax.ShapeDtypeStruct((batch, c, t), xt.dtype),
            jax.ShapeDtypeStruct((batch, num_t, c, _SUMS), jnp.float32),
        ],
        grid=(batch, c // block_c, num_t),
        in_specs=[
            pl.BlockSpec((1, block_c, block_t), lambda b, j, i: (b, first_c + j, i)),
            pl.BlockSpec((1, block_c, _HALO), lambda b, j, i: (b, first_c + j, before(i))),
            pl.BlockSpec((1, block_c, _HALO), lambda b, j, i: (b, first_c + j, after(i))),
            pl.BlockSpec((1, block_c, block_t), lambda b, j, i: (b, j, i)),
            pl.BlockSpec((1, block_c, _HALO), lambda b, j, i: (b, j, after(i))),
            pl.BlockSpec((block_c, taps), lambda b, j, i: (j, 0)),
            pl.BlockSpec((block_c, 1), lambda b, j, i: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_c, block_t), lambda b, j, i: (b, j, i)),
            pl.BlockSpec((1, 1, block_c, _SUMS), lambda b, j, i: (b, i, j, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((_ROWS, _HALO + block_t + _HALO), jnp.float32),
            pltpu.VMEM((_ROWS, block_t + _HALO), jnp.float32),
        ],
        compiler_params=_independent_tiles(),
        interpret=interpret,
    )
    with obs_trace.span("kernel_trace", kernel="causal_conv_bwd"):
        dx, sums = kernel(xt, xt, xt, dy, dy, w, b)
    return dx, sums.sum(axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _kernels(xt, w, b, offset, blocks, interpret):
    return _forward(xt, w, b, offset, blocks, interpret)


def _kernels_fwd(xt, w, b, offset, blocks, interpret):
    return _forward(xt, w, b, offset, blocks, interpret), (xt, w, b)


def _kernels_bwd(offset, blocks, interpret, residuals, dy):
    xt, w, b = residuals
    dx, sums = _backward(xt, w, b, dy, offset, blocks, interpret)
    c, taps = w.shape
    if xt.shape[1] != c:  # x is wider than the convolution: zeros beside it
        dx = jnp.pad(dx, ((0, 0), (offset, xt.shape[1] - offset - c), (0, 0)))
    return dx, sums[:, :taps], sums[:, taps:taps + 1]


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def causal_conv_silu(
    x: jax.Array,
    kernel: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    offset: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """``silu(causal_conv(x[..., offset:offset + C], kernel, bias))`` in
    ``x``'s dtype, ``[B, T, C]`` for ``kernel`` ``[d_conv, C]``; the arithmetic
    is float32. Differentiable in ``x``, ``kernel`` and ``bias``."""
    c = kernel.shape[1]
    if x.ndim != 3 or not 0 <= offset <= x.shape[2] - c:
        raise ValueError(
            "causal_conv_silu: x %s has no %d channels at %d"
            % (x.shape, c, offset)
        )
    blocks = _blocks(x, kernel, offset)
    kernels = interpret or jax.default_backend() == "tpu"
    # the first condition the dispatch did not meet, None where it met both
    why = "backend" if not kernels else "blocks" if blocks is None else None
    note = functools.partial(
        obs_trace.get_tracer().note_once, "conv_shape", channels=c,
        taps=kernel.shape[0], steps=x.shape[1], batch=x.shape[0], offset=offset,
        dtype=str(x.dtype),
    )
    if why:
        note(path="plain", why=why)
        return _plain(x, kernel, bias, offset)
    note(path="kernel")
    b = jnp.zeros((c,), jnp.float32) if bias is None else bias.astype(jnp.float32)
    yt = _kernels(
        x.swapaxes(1, 2), kernel.astype(jnp.float32).T, b.reshape(c, 1),
        offset, blocks, interpret,
    )
    return yt.swapaxes(1, 2)
