"""Chunked state-space scan: Mamba-2's recurrence in its dual form (SSD).

The recurrence, per head ``h`` with state ``S`` of ``[P, N]`` (Dao and Gu,
"Transformers are SSMs", arXiv:2405.21060, section 6 and listing 1)::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (x) B_t
    y_t = S_t C_t + D * x_t

is computed a chunk of ``chunk`` steps at a time. Inside a chunk the state
never materialises: ``Y_diag = (L o C B^T)(dt x)`` with ``L_ij = exp(sum of
dt_k A over j < k <= i)``, three matmuls. Each chunk's own final state is one
more matmul, the states are carried from chunk to chunk in order (``T /
chunk`` steps), and what a chunk inherits reaches its outputs through ``Y_off
= exp(cumsum) * C S_in``, which is added to ``Y_diag`` as the carry passes the
chunk.

Precision: the log-decays ``dt A``, their running sums, every ``exp`` of
them and the carried state are float32; the matmul operands (``C``, ``B``,
``L o C B^T``, ``dt x`` and the state a chunk reads) are in ``x``'s dtype with
float32 accumulation, and ``y`` is rounded once. No ``exp`` is of a positive
argument.

The scan has two forms under one contract, chosen from what the call can see
(:func:`_kernels_refuse`), as ``ops/gated_delta.py:kda_rule``'s is:

- On a TPU backend, for bfloat16 ``x``, ``B``, ``C``, a chunk of whole lane
  tiles (a multiple of 128) that divides ``T``, ``P`` a multiple of 16, ``N``
  of 128 and a multiple of 8 heads a group: two Pallas kernels under one
  ``jax.custom_vjp`` (:func:`_scan_kernels`), ``ssd_forward`` and
  ``ssd_backward``, a grid step a chunk, every head of the chunk in VMEM,
  **and the carry inside them**: the grid walks a batch row's chunks in order
  (``_chunk_call``'s ``walk``; last to first in the backward) with every
  head's float32 state in a VMEM scratch from one grid step to the next, so
  a chunk's ``Y_diag + D x``, its own state and ``exp`` of its decays never
  reach HBM. ``ssd_forward`` writes ``y`` (rounded once, in place), the state
  each chunk inherits (the backward's one residual beside the inputs) and the
  final state; ``ssd_backward`` reads ``dy`` as it is handed over and makes
  the tile's intermediates and ``C S_in`` again in VMEM, taking the two ``dM``
  reductions (row and column sums of ``d mixing o mixing``) on the tile; a
  block's recomputation runs ``ssd_forward`` again.
  They work on the transposed activations, ``[B, channels, T]`` with time
  along the lanes, which is how ``causal_conv_silu`` hands ``xBC`` over, how
  XLA keeps ``z`` and ``dt`` and how the mixer's gate reads ``y`` (PERF.md
  section 6, PRs 30 and 64): a head is ``P`` whole sublanes, a step's decay a
  lane, and the ``swapaxes`` around the kernels are bitcasts. ``x``, ``B``
  and ``C`` are read out of one array, one under another, and their gradients
  leave as one array of its shape: the mixer hands :func:`ssd_scan` three
  slices of what its convolution wrote, and XLA passes that array whole
  (``tests/test_tpu_compile.py`` holds it to that, and to no copy of ``y`` or
  ``dy`` between the kernels and the gate).
  The ``[L, L]`` decay matrix of a head lives a ``128 x 128`` block at a time
  in registers, only the blocks on and under the causal line; the group's
  scores ``C B^T`` are made once a group in a VMEM scratch, and the products
  whose one operand a group's heads share (their own states through ``B``,
  what they read of the inherited states through ``C``, and both ways back)
  are one product over the heads' rows.
- Everywhere else (the CPU, float32 operands, a ragged ``T``, other widths),
  the chunk-local stage in plain ``jax.numpy`` with jax's own backward
  (:func:`_local_plain`) and the carry as one ``lax.scan`` with a backward of
  its own (:func:`_carry_out`: a reverse scan that keeps the state each chunk
  inherited and nothing else; the loop rounds each chunk's outputs as it
  passes, and the result is laid out time last behind an
  ``optimization_barrier``, PERF.md section 6, PR 50). It is also what the
  kernels are tested against. ``interpret`` runs the kernels in the Pallas
  interpreter (tests on the CPU).

Each traced shape leaves one ``ssm_chunks`` instant: ``chunk``, ``chunks``,
``heads``, ``groups``, ``d_head``, ``d_state``, ``state_bytes``, ``path``
``kernel`` / ``plain``, ``carry`` ``kernel`` / ``loop`` (who carries the state:
the kernels, or ``_carry_out``'s ``lax.scan``) and, on ``plain``, ``why`` (the
first of ``backend``, ``dtype``, ``chunk``, ``steps``, ``heads``, ``width``,
``vmem`` that did not hold).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from edl_tpu.obs import trace as obs_trace
from edl_tpu.ops.gated_delta import (
    _chunk_call, _column_of, _iota, _over_heads, _running_sum,
    _times_transposed, _transposed_times,
)

_BLOCK = 128            # a lane tile: the [L, L] tile is walked in such blocks
# Heads a round of the kernels' loops, for the scheduler to interleave: 0.58 ms a
# forward call at Nemotron's shape for 0.97 at 2 (my probe, PR 50). A group's
# heads come in eights in both cells and in every published Mamba-2 layer.
_HEADS_A_ROUND = 8
_VMEM_MOST = 96 << 20   # what the kernels' blocks may hold of a core's 128 MiB


def _local_plain(x, dt, a, b, c, d, size):
    """The chunk-local stage in plain ``jax.numpy``, for ``x`` ``[B, T, H,
    P]``, ``dt`` ``[B, T, H]`` (float32), ``a``, ``d`` ``[H]`` (float32; ``d``
    may be None), ``b``, ``c`` ``[B, T, G, N]`` and a ``T`` of whole chunks of
    ``size``, chunks first, as the carry's loop takes them: ``Y_diag + D x``
    ``[n, B, H P, L]`` and ``exp`` of the running sum ``[n, B, H, L]`` (a
    chunk's steps last, as the kernels give them), every chunk's own state
    ``[n, B, H, P, N]`` and ``exp`` of its whole decay ``[n, B, H]``, all
    float32."""
    batch, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r, nc = h // g, t // size
    f32, dtype = jnp.float32, x.dtype
    dot = dict(preferred_element_type=f32)

    # everything below: b batch, c chunk, l / s step in a chunk, g group,
    # r head in its group, p head width, n state width
    dt = dt.reshape(batch, nc, size, g, r)
    x = x.reshape(batch, nc, size, g, r, p)
    b = b.reshape(batch, nc, size, g, n)
    c = c.reshape(batch, nc, size, g, n)
    x32 = x.astype(f32)
    dtx = x32 * dt[..., None]
    # running sum of the log-decay inside each chunk, heads before steps
    decay = jnp.cumsum(dt * a.reshape(g, r), axis=2)
    decay = jnp.moveaxis(decay, 2, -1)                           # [b c g r l]

    # inside a chunk
    scores = jnp.einsum("bclgn,bcsgn->bcgls", c, b, **dot)
    causal = jnp.tril(jnp.ones((size, size), bool))
    between = jnp.exp(jnp.where(
        causal, decay[..., :, None] - decay[..., None, :], -jnp.inf
    ))                                                           # [b c g r l s]
    mixing = (between * scores[:, :, :, None]).astype(dtype)
    y = jnp.einsum("bcgrls,bcsgrp->bcgrpl", mixing, dtx.astype(dtype), **dot)
    if d is not None:
        y = y + d.reshape(g, r, 1, 1) * jnp.moveaxis(x32, 2, -1)

    # a chunk's own contribution to the state at its end
    to_end = jnp.exp(decay[..., -1:] - decay)                    # [b c g r l]
    weighted = (dtx * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(dtype)
    own = jnp.einsum("bclgn,bclgrp->bcgrpn", b, weighted, **dot)
    whole = jnp.exp(decay[..., -1])                              # [b c g r]
    chunks_first = lambda v, *dims: jnp.moveaxis(v, 1, 0).reshape(nc, batch, *dims)  # noqa: E731
    return (
        chunks_first(y, h * p, size), chunks_first(own, h, p, n),
        chunks_first(whole, h), chunks_first(jnp.exp(decay), h, size),
    )


# -- the scan as Pallas kernels ----------------------------------------------
#
# One grid step holds a chunk of every head in VMEM, time along the lanes:
# ``xBC`` ``[B, H P + 2 G N, T]`` (a head of ``x`` is ``P`` sublanes, a group's
# ``B`` or ``C`` ``N``) and ``dt`` ``[B, H, T]``. The tile of a head is held
# transposed, ``[s, l]``
# (the step that wrote along the sublanes, the step that reads along the
# lanes), so ``Y^T = (dt x)^T mixing^T`` and its two transposed products in the
# backward are plain ones, and a step's decay is a lane of a ``[1, L]`` row; the
# column ``decay_s`` comes out of the chunk's one ``[L, H]`` transpose. The
# grid is (batch, chunks) with a batch row's chunks one after another
# (``_chunk_call``'s ``walk``): every head's state ``[H, P, N]`` (float32) stays
# in a VMEM scratch from one grid step to the next, as ``delta_carry``'s does.


def _at(i):
    return slice(i * _BLOCK, (i + 1) * _BLOCK)


def _between(rows, cols, s, l):
    """Block ``(s, l)`` of a head's transposed decay tile, ``exp(decay_l -
    decay_s)`` where ``s <= l`` and zeros elsewhere: ``rows`` the head's decay
    a block of lanes (``[1, 128]`` each), ``cols`` a block of sublanes (``[128,
    1]`` each). A block under the diagonal (``s < l``) needs no mask; one above
    it is never asked for."""
    diff = rows[l] - cols[s]
    if s == l:
        diff = jnp.where(_iota(diff.shape, 0) <= _iota(diff.shape, 1), diff, -jnp.inf)
    return jnp.exp(diff)


def _decays_of(dt_ref, a_ref, d_ref, decay_ref, to_end_ref, cols_ref, skip_ref):
    """Every head's running sum of ``dt A`` over the chunk ``[H, L]`` (left in
    ``decay_ref``, a block of lanes at a time; its transpose in ``cols_ref``,
    ``exp(decay_L - decay)`` in ``to_end_ref``, ``D`` along a lane tile in
    ``skip_ref``); returns it and its last column ``[H, 1]``."""
    decay = _running_sum(dt_ref[0] * a_ref[...], axis=1)
    last = decay[:, decay.shape[1] - 1:]
    for i in range(decay_ref.shape[0]):  # a block of lanes apart: a row is read by head
        decay_ref[i] = decay[:, _at(i)]
    to_end_ref[...] = jnp.exp(last - decay)
    cols_ref[...] = decay.T
    skip_ref[...] = jnp.broadcast_to(d_ref[...], skip_ref.shape)
    return decay, last


def _blocks_of(decay_ref, cols_ref, h, blocks):
    """Head ``h``'s decay as :func:`_between` takes it: a ``[1, 128]`` row and
    a ``[128, 1]`` column for each block of the chunk's steps."""
    from jax.experimental import pallas as pl

    rows = [decay_ref[i, pl.ds(h, 1), :] for i in range(blocks)]
    return rows, [_column_of(cols_ref[_at(i), :], h) for i in range(blocks)]


def _rows(i, width, base=0):
    """Rows ``base + i * width`` to ``base + (i + 1) * width`` of a block: head
    ``i``'s of ``x`` (``width`` ``P``), group ``i``'s of ``B`` or ``C``
    (``width`` ``N``, ``base`` where they start in ``xBC``)."""
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(base + i * width, math.gcd(base, width)), width)


def _shape_of(xbc_ref, dt_ref, state_ref):
    """``(heads, P, N, groups, chunk's blocks)`` and where ``B`` and ``C``
    start among the rows of ``xBC``, from the kernels' blocks."""
    heads, size = dt_ref.shape[1:]
    p, n = state_ref.shape[3:]
    at_b = heads * p
    at_c = at_b + (xbc_ref.shape[1] - at_b) // 2
    return heads, p, n, (at_c - at_b) // n, size // _BLOCK, at_b, at_c


def _first_chunk(state_ref, from_ref):
    """At a batch row's first grid step, the walk's state from its operand."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[...] = from_ref[0]


def _last_chunk(to_ref, state_ref):
    """At a batch row's last grid step, the walk's state to its result."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _():
        to_ref[0] = state_ref[...]


def _carried(state_ref, first, whole_ref, moved):
    """``S <- exp(whole) S + moved`` for the heads from ``first`` on (a slab's)
    of the walk's state ``[H, P, N]``, float32: ``moved`` ``[heads P, N]`` one
    head under another, ``whole_ref`` ``[H, N]`` a head's factor along its
    row."""
    from jax.experimental import pallas as pl

    p = state_ref.shape[1]
    for j in range(moved.shape[0] // p):
        h = first + j
        state_ref[h] = whole_ref[pl.ds(h, 1), :] * state_ref[h] + moved[j * p:(j + 1) * p]


def ssd_forward_kernel(xbc_ref, dt_ref, a_ref, d_ref, initial_ref,
                       y_ref, entering_ref, final_ref,
                       state_ref, scores_ref, decay_ref, to_end_ref, cols_ref, skip_ref,
                       weighted_ref, inherited_ref, whole_ref):
    """One chunk of every head, the chunks in order: ``y = Y_diag + D x +
    exp(cumsum) C S_in`` rounded once to ``x``'s dtype (transposed as the
    inputs are, at the chunk's lanes), the state the chunk inherits as it is,
    and ``S <- exp(whole) S + own`` in ``state_ref`` (float32, from the chunk
    before; the initial state at the first, the final one out at the last),
    rounded once to the operands' dtype for ``C S_in``."""
    from jax.experimental import pallas as pl

    f32, dtype = jnp.float32, xbc_ref.dtype
    heads, p, n, groups, blocks, at_b, at_c = _shape_of(xbc_ref, dt_ref, entering_ref)
    r = heads // groups
    width = _HEADS_A_ROUND
    _first_chunk(state_ref, initial_ref)
    decay, last = _decays_of(
        dt_ref, a_ref, d_ref, decay_ref, to_end_ref, cols_ref, skip_ref
    )
    whole_ref[...] = jnp.broadcast_to(jnp.exp(last), whole_ref.shape)

    def group(g, carry):
        b_g, c_g = _rows(g, n, at_b), _rows(g, n, at_c)
        # the scores of the group's heads, [s, l]: once a group
        scores_ref[...] = _transposed_times(xbc_ref[0, b_g, :], xbc_ref[0, c_g, :])

        # what the group's heads read of the states they inherit, C S_in: C is
        # theirs together, so one product streams all their rows through it, a
        # slab of heads at a time
        def inherit(i, carry):
            first = g * r // width + i
            state = state_ref[pl.ds(first * width, width)]
            entering_ref[0, 0, pl.ds(first * width, width)] = state
            inherited_ref[_rows(first, width * p), :] = jnp.dot(
                state.reshape(width * p, n).astype(dtype), xbc_ref[0, c_g, :],
                preferred_element_type=f32,
            )
            return carry

        carry = jax.lax.fori_loop(0, r // width, inherit, carry)

        def head(pair, half, carry):
            h = g * r + width * pair + half
            rows, one = _rows(h, p), pl.ds(h, 1)
            x32 = xbc_ref[0, rows, :].astype(f32)
            dtx = x32 * dt_ref[0, one, :]
            dtxb = dtx.astype(dtype)
            rows_h, cols_h = _blocks_of(decay_ref, cols_ref, h, blocks)
            skip = skip_ref[one, :]
            for l in range(blocks):
                acc = skip * x32[:, _at(l)]
                for s in range(l + 1):
                    mixing = _between(rows_h, cols_h, s, l) * scores_ref[_at(s), _at(l)]
                    acc = acc + jnp.dot(
                        dtxb[:, _at(s)], mixing.astype(dtype), preferred_element_type=f32
                    )
                acc = acc + inherited_ref[rows, _at(l)] * jnp.exp(rows_h[l])
                y_ref[0, rows, _at(l)] = acc.astype(dtype)
            weighted_ref[rows, :] = (dtx * to_end_ref[one, :]).astype(dtype)
            return carry

        carry = _over_heads(r, head, carry, width)

        # the own states of the group's heads: B is theirs together, so one
        # product streams all their rows through it, a slab of heads at a time
        def slab(i, carry):
            first = g * r // width + i
            own = _times_transposed(weighted_ref[_rows(first, width * p), :], xbc_ref[0, b_g, :])
            _carried(state_ref, first * width, whole_ref, own)
            return carry

        return jax.lax.fori_loop(0, r // width, slab, carry)

    jax.lax.fori_loop(0, groups, group, 0)
    _last_chunk(final_ref, state_ref)


def ssd_backward_kernel(xbc_ref, dt_ref, a_ref, d_ref, dy_ref, entering_ref, dfinal_ref,
                        dxbc_ref, ddt_ref, da_ref, dd_ref, dinitial_ref,
                        dstate_ref, scores_ref, decay_ref, to_end_ref, cols_ref, skip_ref,
                        weighted_ref, inherited_ref, whole_ref,
                        dscores_ref, d_weighted_ref, d_inherited_ref, by_row_ref, by_col_ref,
                        d_end_ref, dt_part_ref, dd_part_ref, dwhole_ref):
    """One chunk of the walk back, every head, the chunks last to first: the
    cotangents of the chunk's inputs from ``dy`` and the cotangent ``dS'`` of
    the state the chunk left (``dstate_ref``, float32; the final state's at the
    first step), and ``dS = exp(whole) dS' + (dy o grown) C^T`` of the state it
    inherited left there (the initial state's out at the last). The tile's
    intermediates are made again in VMEM from the inputs, ``C S_in`` from the
    saved state the chunk inherited; ``dS'`` is the own state's cotangent,
    ``<dS', S_in>`` the whole decay's, ``sum_p(dy o C S_in)`` the running
    sum's, and the inherited term's ``dC = S_in^T (dy o grown)`` joins the
    scores'. ``dA`` and ``dD`` leave as the chunk's own sums ``[H, 1]``."""
    from jax.experimental import pallas as pl

    f32, dtype = jnp.float32, xbc_ref.dtype
    heads, p, n, groups, blocks, at_b, at_c = _shape_of(xbc_ref, dt_ref, entering_ref)
    size, r = dt_ref.shape[2], heads // groups
    width = _HEADS_A_ROUND
    _first_chunk(dstate_ref, dfinal_ref)
    decay, last = _decays_of(
        dt_ref, a_ref, d_ref, decay_ref, to_end_ref, cols_ref, skip_ref
    )
    whole_ref[...] = jnp.broadcast_to(jnp.exp(last), whole_ref.shape)
    down = lambda a: jnp.sum(a, axis=0, keepdims=True)      # noqa: E731 — [1, L]
    across = lambda a: jnp.sum(a, axis=1, keepdims=True)    # noqa: E731 — [L, 1]
    add = lambda acc, a: a if acc is None else acc + a      # noqa: E731
    held = lambda states: states.reshape(r * p, n).astype(dtype)  # noqa: E731 — a group's

    def group(g, carry):
        b_g, c_g = _rows(g, n, at_b), _rows(g, n, at_c)
        scores_ref[...] = _transposed_times(xbc_ref[0, b_g, :], xbc_ref[0, c_g, :])
        dscores_ref[...] = jnp.zeros(dscores_ref.shape, f32)

        # through the own states of the group's heads, B theirs together, and
        # what they read of the states they inherit, C theirs together: one
        # product each streams all their rows through it, a slab of heads at a
        # time
        def slab(i, carry):
            first = g * r // width + i
            rows = _rows(first, width * p)
            leaving = dstate_ref[pl.ds(first * width, width)]
            state = entering_ref[0, 0, pl.ds(first * width, width)]
            d_weighted_ref[rows, :] = jnp.dot(
                leaving.reshape(width * p, n).astype(dtype), xbc_ref[0, b_g, :],
                preferred_element_type=f32,
            )
            inherited_ref[rows, :] = jnp.dot(
                state.reshape(width * p, n).astype(dtype), xbc_ref[0, c_g, :],
                preferred_element_type=f32,
            )
            for j in range(width):  # <dS', S_in> a head, along its row
                dwhole_ref[pl.ds(first * width + j, 1), :] = down(leaving[j] * state[j])
            return carry

        jax.lax.fori_loop(0, r // width, slab, carry)

        def head(pair, half, carry):
            h = g * r + width * pair + half
            rows, one = _rows(h, p), pl.ds(h, 1)
            x32 = xbc_ref[0, rows, :].astype(f32)
            dt_h, to_end = dt_ref[0, one, :], to_end_ref[one, :]
            dtx = x32 * dt_h
            dtxb = dtx.astype(dtype)
            dyb = dy_ref[0, rows, :]
            dy = dyb.astype(f32)
            rows_h, cols_h = _blocks_of(decay_ref, cols_ref, h, blocks)

            # through Y^T = (dt x)^T mixing^T, a block of the tile at a time
            d_dtx, by_row, by_col = [None] * blocks, [None] * blocks, [None] * blocks
            for l in range(blocks):
                for s in range(l + 1):
                    between = _between(rows_h, cols_h, s, l)
                    mixing = between * scores_ref[_at(s), _at(l)]
                    d_mixing = _transposed_times(dtxb[:, _at(s)], dyb[:, _at(l)])
                    # the two dM reductions: the decay's gradient by row and by column
                    both = d_mixing * mixing
                    by_row[l] = add(by_row[l], down(both))
                    by_col[s] = add(by_col[s], across(both))
                    dscores_ref[_at(s), _at(l)] += d_mixing * between
                    d_dtx[s] = add(
                        d_dtx[s], _times_transposed(dyb[:, _at(l)], mixing.astype(dtype))
                    )
            d_dtx = jnp.concatenate(d_dtx, axis=1)

            # through the chunk's own state
            d_weighted = d_weighted_ref[rows, :]
            d_dtx = d_dtx + d_weighted * to_end
            d_end_ref[one, :] = down(d_weighted * dtx) * to_end
            weighted_ref[rows, :] = (dtx * to_end).astype(dtype)

            # through the inherited term, exp(cumsum) o C S_in
            grown = jnp.exp(jnp.concatenate(rows_h, axis=1))
            d_inherited_ref[rows, :] = (dy * grown).astype(dtype)

            skip = jnp.concatenate([skip_ref[one, :]] * blocks, axis=1)
            dxbc_ref[0, rows, :] = (d_dtx * dt_h + skip * dy).astype(dtype)
            dt_part_ref[one, :] = down(d_dtx * x32)
            dd_part_ref[one, :] = down(dy * x32)
            by_row_ref[one, :] = (
                jnp.concatenate(by_row, axis=1) + down(dy * inherited_ref[rows, :]) * grown
            )
            by_col = jnp.concatenate(by_col, axis=0)
            by_col_ref[...] = jnp.where(
                _iota(by_col_ref.shape, 1) == h, by_col, by_col_ref[...]
            )
            return carry

        carry = _over_heads(r, head, carry, width)

        # the group's B and C: through the scores, B through the own states of
        # its heads and C through what they inherit (one product over the
        # heads' rows each)
        b, c = xbc_ref[0, b_g, :], xbc_ref[0, c_g, :]
        of_g, rows_g = pl.ds(g * r, r), _rows(g, r * p)
        db = _transposed_times(held(dstate_ref[of_g]), weighted_ref[rows_g, :])
        dc = _transposed_times(held(entering_ref[0, 0, of_g]), d_inherited_ref[rows_g, :])
        db_s, dc_l = [None] * blocks, [None] * blocks
        for l in range(blocks):
            for s in range(l + 1):
                d_scores = dscores_ref[_at(s), _at(l)].astype(dtype)
                dc_l[l] = add(
                    dc_l[l], jnp.dot(b[:, _at(s)], d_scores, preferred_element_type=f32)
                )
                db_s[s] = add(db_s[s], _times_transposed(c[:, _at(l)], d_scores))
        dxbc_ref[0, b_g, :] = (db + jnp.concatenate(db_s, axis=1)).astype(dtype)
        dxbc_ref[0, c_g, :] = (dc + jnp.concatenate(dc_l, axis=1)).astype(dtype)

        # the state's cotangent on its way back, once nothing reads dS' any more
        def back(i, carry):
            first = g * r // width + i
            moved = _times_transposed(d_inherited_ref[_rows(first, width * p), :], c)
            _carried(dstate_ref, first * width, whole_ref, moved)
            return carry

        return jax.lax.fori_loop(0, r // width, back, carry)

    jax.lax.fori_loop(0, groups, group, 0)
    _last_chunk(dinitial_ref, dstate_ref)

    # every head's decay at once: rows less columns, the chunk's end, and back
    # through the running sum
    d_end = d_end_ref[...]
    d_last = across(d_end) + across(dwhole_ref[...]) * jnp.exp(last)
    d_decay = (
        by_row_ref[...] - d_end - by_col_ref[...].T
        + jnp.where(_iota(decay.shape, 1) == size - 1, d_last, 0.0)
    )
    d_log = _running_sum(d_decay, reverse=True, axis=1)
    ddt_ref[0] = d_log * a_ref[...] + dt_part_ref[...]
    da_ref[0, 0] = across(d_log * dt_ref[0])
    dd_ref[0, 0] = across(dd_part_ref[...])


_INPUTS = ("packed", "steps", "head", "head")                          # xbc dt a d


def _kinds(xbc, dt, size, p, n, back=False):
    """The kernels' kinds of operand (``ops/gated_delta.py:_chunk_call``): a
    kind's shape, dtype, block and the block's place at batch ``b``, grid step
    ``c`` (the chunks first to last or, ``back``, last to first); and the
    scratch both kernels share, the walk's state first."""
    batch, rows, steps = xbc.shape
    h, nc = dt.shape[1], steps // size
    f32, dtype = jnp.float32, xbc.dtype
    at = (lambda c: nc - 1 - c) if back else (lambda c: c)  # noqa: E731
    lanes = lambda b, c: (b, 0, at(c))      # noqa: E731 — a chunk's steps of [B, ., T]
    first = lambda b, c: (at(c), b)         # noqa: E731 — chunks first
    kinds = dict(
        # every row of xBC (x, B, C one under another), as the convolution left it
        packed=((batch, rows, steps), dtype, (1, rows, size), lanes),
        steps=((batch, h, steps), f32, (1, h, size), lanes),
        head=((h, 1), f32, (h, 1), lambda b, c: (0, 0)),
        # y and its cotangent, as x lies in xBC
        local=((batch, h * p, steps), dtype, (1, h * p, size), lanes),
        # the states: the ones the chunks inherit, a batch row's one
        entering=((nc, batch, h, p, n), f32, (1, 1, h, p, n), first),
        state=((batch, h, p, n), f32, (1, h, p, n), lambda b, c: (b,)),
        # a chunk's own sums a head (dA's and dD's parts)
        sums=((nc, batch, h, 1), f32, (1, 1, h, 1), first),
    )
    # the walk's state, the group's scores, every head's decay, exp(decay_L -
    # decay), decay^T, D, dt x decayed to the chunk's end, C S_in, exp of the
    # whole decay along a state's rows
    scratch = (((h, p, n), f32), ((size, size), f32), ((size // _BLOCK, h, _BLOCK), f32),
               ((h, size), f32), ((size, h), f32), ((h, _BLOCK), f32), ((h * p, size), dtype),
               ((h * p, size), f32), ((h, n), f32))
    return kinds, scratch


# jitted, as ``ops/causal_conv.py``'s: a step traces and lowers each body once
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _forward_call(xbc, dt, a, d, initial, size, p, n, interpret):
    """``(y, the states the chunks inherit, the final state)``."""
    kinds, scratch = _kinds(xbc, dt, size, p, n)
    grid = (xbc.shape[0], xbc.shape[2] // size)
    return _chunk_call(
        ssd_forward_kernel, grid, kinds, (*_INPUTS, "state"), ("local", "entering", "state"),
        (xbc, dt, a, d, initial), interpret, scratch, walk=True,
    )


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _backward_call(xbc, dt, a, d, dy, entering, d_final, size, p, n, interpret):
    """The cotangents of ``(xbc, dt, a, d, initial)``, ``a``'s and ``d``'s a
    chunk's own sums."""
    kinds, scratch = _kinds(xbc, dt, size, p, n, back=True)
    grid = (xbc.shape[0], xbc.shape[2] // size)
    f32, h = jnp.float32, dt.shape[1]
    by_head = ((h, size), f32)
    # d scores, d (dt x decayed to the end), dy o grown, the two dM reductions,
    # the decay's gradient at the end, dt's and D's parts, <dS', S_in>
    scratch = (
        *scratch, ((size, size), f32), ((h * p, size), f32), ((h * p, size), xbc.dtype),
        by_head, ((size, h), f32), by_head, by_head, by_head, ((h, n), f32),
    )
    return _chunk_call(
        ssd_backward_kernel, grid, kinds, (*_INPUTS, "local", "entering", "state"),
        ("packed", "steps", "sums", "sums", "state"),
        (xbc, dt, a, d, dy, entering, d_final), interpret, scratch, walk=True,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _scan_kernels(xbc, dt, a, d, initial, size, p, n, interpret):
    """The whole scan by the two kernels, time along the lanes: ``xbc`` ``[B,
    H P + 2 G N, T]`` (``x``, ``B`` and ``C`` one under another, as the
    convolution leaves them: nothing is sliced out), ``dt`` ``[B, H, T]``
    (float32), ``a``, ``d`` ``[H, 1]`` (float32), ``initial`` ``[B, H, P, N]``
    (float32); returns ``y`` ``[B, H P, T]`` in ``xbc``'s dtype and the final
    state, float32."""
    y, _, final = _forward_call(xbc, dt, a, d, initial, size, p, n, interpret)
    return y, final


def _scan_kernels_fwd(xbc, dt, a, d, initial, size, p, n, interpret):
    # the inputs and the states the chunks inherit: the backward makes the
    # tile again
    y, entering, final = _forward_call(xbc, dt, a, d, initial, size, p, n, interpret)
    return (y, final), (xbc, dt, a, d, entering)


def _scan_kernels_bwd(size, p, n, interpret, residuals, cotangents):
    *inputs, entering = residuals
    dy, d_final = cotangents
    dxbc, ddt, da, dd, d_initial = _backward_call(
        *inputs, dy, entering, d_final, size, p, n, interpret
    )
    return dxbc, ddt, da.sum((0, 1)), dd.sum((0, 1)), d_initial


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


# -- from chunk to chunk -----------------------------------------------------


def _inherit(state, c_n, dtype):
    """What a chunk's steps read of the state it inherits, ``C S`` ``[B, G, R,
    P, L]`` (float32): ``state`` ``[B, G, R, P, N]``, ``c_n`` ``[B, G, N, L]``."""
    return jnp.einsum(
        "bgnl,bgrpn->bgrpl", c_n, state.astype(dtype), preferred_element_type=jnp.float32
    )


# bound here: the family's wrong program (``benchmark/tests/test_ssm_lm.py``, a
# carried state kept in bfloat16) swaps this module's ``jax.lax.scan`` for one
# that rounds the forward's carry and knows no ``reverse``
_scan_back = functools.partial(jax.lax.scan, reverse=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _carry_out(state, whole, own, local, grown, c, dtype):
    """The state from chunk to chunk, and every chunk's outputs as the loop
    passes it: ``(y, final)`` for the initial ``state`` ``[B, G, R, P, N]``
    (float32) and, chunks first, ``whole`` ``[n, B, G, R]``, ``own`` ``[n, B, G,
    R, P, N]``, ``local`` ``[n, B, G, R, P, L]`` (``Y_diag + D x``), ``grown``
    ``[n, B, G, R, L]`` (all float32) and ``c`` ``[n, B, G, N, L]``. ``y`` ``[B,
    G, R, P, n L]`` in ``dtype``, time last: the loop rounds each chunk's
    outputs as it passes, so what is laid out for the mixer's gate afterwards
    is the result in ``dtype`` and no float32 ``[T, H P]`` array. One
    ``lax.scan`` of ``n`` steps, a matmul and an elementwise update each; its
    backward is one reverse scan that makes ``C S`` again from the state a
    chunk inherited, which is all the forward keeps."""
    return _carry_out_fwd(state, whole, own, local, grown, c, dtype)[0]


def _carry_out_fwd(state, whole, own, local, grown, c, dtype):
    def step(state, inputs):
        whole_n, own_n, local_n, grown_n, c_n = inputs
        y_n = local_n + _inherit(state, c_n, dtype) * grown_n[..., None, :]
        return whole_n[..., None, None] * state + own_n, (state, y_n.astype(dtype))

    final, (entering, y) = jax.lax.scan(step, state, (whole, own, local, grown, c))
    # the chunks' steps one after another, time last. The barrier holds the
    # reader's float32 conversion behind the layout change: XLA then moves
    # whole (sublanes, L) tiles once, in ``dtype`` (0.10 ms a call at Granite's
    # shape), where it made a transposing copy and a retiling of the float32
    # array (0.82 ms)
    y = jnp.moveaxis(y, 0, -2)
    y = jax.lax.optimization_barrier(y.reshape(*y.shape[:-2], -1))
    return (y, final), (entering, whole, grown, c)


def _carry_out_bwd(dtype, residuals, cotangents):
    entering, whole, grown, c = residuals
    dy, d_final = cotangents
    nc, size = grown.shape[0], grown.shape[-1]
    f32 = jnp.float32

    def step(d_after, inputs):
        index, state, whole_n, grown_n, c_n = inputs
        dy_n = jax.lax.dynamic_slice_in_dim(dy, index * size, size, axis=-1).astype(f32)
        inherited = _inherit(state, c_n, dtype)
        d_inherited = dy_n * grown_n[..., None, :]
        d_state = whole_n[..., None, None] * d_after + jnp.einsum(
            "bgrpl,bgnl->bgrpn", d_inherited, c_n, preferred_element_type=f32
        )
        d_c = jnp.einsum(
            "bgrpl,bgrpn->bgnl", d_inherited, state.astype(dtype), preferred_element_type=f32
        )
        return d_state, (
            jnp.sum(d_after * state, axis=(-1, -2)), d_after, dy_n,
            jnp.sum(dy_n * inherited, axis=-2), d_c.astype(c.dtype),
        )

    d_state, (d_whole, d_own, d_local, d_grown, d_c) = _scan_back(
        step, d_final, (jnp.arange(nc), entering, whole, grown, c)
    )
    return d_state, d_whole, d_own, d_local, d_grown, d_c


_carry_out.defvjp(_carry_out_fwd, _carry_out_bwd)


def _kernels_refuse(dtype, h, p, g, n, steps, chunk, interpret):
    """Why the scan of these operands is not the kernels', or None where it
    is: the first of a TPU backend or the interpreter
    (``backend``), bfloat16 operands (``dtype``), a chunk of whole lane tiles
    (``chunk``) that divides the length (``steps``), a group's heads in eights
    (``heads``), a head of whole bfloat16 sublane tiles over a state of whole
    lane tiles (``width``) and blocks a core's VMEM holds (``vmem``) that does
    not hold."""
    # the backward's, the larger. Of a chunk's [H P, L]: x, dY and dx in
    # ``dtype``, twice each (the pipeline's two buffers), and four scratch
    # arrays, two float32 and two in ``dtype``. Of a state's [H, P, N] float32:
    # the one the chunk inherits, the final one's cotangent and the initial
    # one's, twice each, and the walk's own scratch
    blocks = chunk * h * p * (2 * 3 * 2 + 2 * 4 + 2 * 2) + 4 * h * p * n * (2 * 3 + 1)
    conditions = (
        ("backend", interpret or jax.default_backend() == "tpu"),
        ("dtype", dtype == jnp.bfloat16),
        ("chunk", chunk % _BLOCK == 0),
        ("steps", steps % chunk == 0),
        ("heads", (h // g) % _HEADS_A_ROUND == 0),
        ("width", p % 16 == 0 and n % 128 == 0),
        ("vmem", blocks <= _VMEM_MOST),
    )
    return next((why for why, met in conditions if not met), None)


def ssd_scan(x, dt, a, b, c, d=None, *, chunk: int = 256, initial_state=None,
             return_final_state: bool = False, interpret: bool = False):
    """``y`` ``[B, T, H, P]`` in ``x``'s dtype (and the final state, float32
    ``[B, H, P, N]``, with ``return_final_state``).

    ``x`` ``[B, T, H, P]``; ``dt`` ``[B, T, H]``, the step sizes, already
    positive (after the softplus); ``a`` ``[H]``, negative; ``b``, ``c``
    ``[B, T, G, N]`` with ``G`` dividing ``H`` (a group's ``B`` and ``C`` are
    shared by its ``H / G`` heads); ``d`` ``[H]`` or None; ``initial_state``
    ``[B, H, P, N]`` or None for zeros. The result does not depend on
    ``chunk`` beyond rounding; a ``T`` that ``chunk`` does not divide is
    padded with steps of size 0, which leave the state as it is (and takes the
    plain form). ``interpret`` runs the kernels in the Pallas interpreter.
    """
    batch, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if h % g:
        raise ValueError("ssd_scan: %d heads in %d groups" % (h, g))
    r = h // g
    size = min(chunk, t)
    why_plain = _kernels_refuse(jnp.result_type(x, b, c), h, p, g, n, t, size, interpret)
    pad = -t % size
    if pad:
        x, dt, b, c = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b, c)
        )
    steps = t + pad
    nc = steps // size
    f32, dtype = jnp.float32, x.dtype
    # once a shape and stage: which form the scan took, who carries the state,
    # and why where it is the plain one
    note = functools.partial(
        obs_trace.get_tracer().note_once, "ssm_chunks", chunk=size, chunks=nc,
        heads=h, groups=g, d_head=p, d_state=n, state_bytes=4 * h * p * n,
    )
    dt, a = dt.astype(f32), a.astype(f32)
    if initial_state is None:
        state = jnp.zeros((batch, h, p, n), f32)
    else:
        state = initial_state.astype(f32)
    if why_plain is None:
        note(path="kernel", carry="kernel")
        # x, B and C one under another, time last. The mixer hands over three
        # slices of the one array its convolution wrote, and XLA passes that
        # array whole: no slice of it and no concatenation is made (nor of the
        # gradient, which leaves as one array of its shape)
        xbc = jnp.concatenate([v.reshape(batch, steps, -1) for v in (x, b, c)], axis=-1)
        skip = jnp.zeros((h,), f32) if d is None else d.astype(f32)
        y, state = _scan_kernels(
            xbc.swapaxes(1, 2), dt.swapaxes(1, 2), a.reshape(h, 1), skip.reshape(h, 1),
            state, size, p, n, interpret,
        )
    else:
        note(path="plain", why=why_plain, carry="loop")
        y, own, whole, grown = _local_plain(
            x, dt, a, b, c, None if d is None else d.astype(f32), size
        )
        # from chunk to chunk, the state in float32, and every chunk's outputs;
        # a chunk's C with its steps last, as its outputs are
        c = jnp.transpose(c.reshape(batch, nc, size, g, n), (1, 0, 3, 4, 2))
        y, state = _carry_out(
            state.reshape(batch, g, r, p, n), whole.reshape(nc, batch, g, r),
            own.reshape(nc, batch, g, r, p, n), y.reshape(nc, batch, g, r, p, size),
            grown.reshape(nc, batch, g, r, size), c, dtype,
        )
    y = y.reshape(batch, h * p, steps).swapaxes(1, 2)[:, :t].reshape(batch, t, h, p)
    if return_final_state:
        return y, state.reshape(batch, h, p, n)
    return y
