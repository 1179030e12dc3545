"""Attention over a learned selection of keys: an indexer scores every causal
pair, each query keeps its ``topk`` best keys, the main attention runs over
that selection, and the indexer is trained towards the main attention's own
probabilities (DeepSeek sparse attention, arXiv:2512.02556 section 2).

For one sequence of ``T`` tokens, with the main attention's ``q [H, T, D]``,
``k``, ``v`` ``[Hkv, T, D]`` and the indexer's ``index_q [J, T, Di]`` (J
heads), ``index_k [T, Di]`` (one key head) and ``index_w [T, J]`` (float32)::

    I[t, s] = sum_j index_w[t, j] * relu(index_q[j, t] . index_k[s])    s <= t
    S_t     = the min(topk, t + 1) keys s <= t of largest I[t, s]
              (a tie goes to the lower key index, ``lax.top_k``'s order)
    o[t]    = sum_{s in S_t} softmax_{s in S_t}(q_t . k_s * scale) v_s
    p[t, .] = mean over the H heads of those probabilities, detached
    L_I     = mean_t KL(p[t, S_t] || softmax_{s in S_t} I[t, s])

``sparse_attention`` returns ``(o, L_I, stats, detail)`` (``detail``: the
selection and the scores themselves, for a check that asks). ``o``'s gradient reaches
``q``, ``k`` and ``v`` and nothing of the indexer (a selection passes no
gradient); ``L_I``'s reaches ``index_q``, ``index_k`` and ``index_w`` and
nothing else (``p`` is detached).

Two implementations behind the one function. **The reference**
(:func:`sparse_attention_reference`): ``jax.numpy`` with a dense ``[H, T, T]``
softmax under the boolean selection, ``lax.top_k`` for the selection,
differentiated by autodiff; it runs anywhere and is what the kernels are held
to. **The kernels**, on the TPU (or in interpret mode), none of which holds
more than a tile of any ``[T, T]`` rectangle per head:

- ``_index_fwd_kernel``: ``I`` tile by tile, float32, the J heads' products
  summed in VMEM (a plain einsum holds ``[J, T, T]``); tiles past the diagonal
  are neither computed nor written;
- ``_select_kernel``: the k-th largest of each row's causal prefix, exactly,
  by bisection over the 32 bits of the scores' order-preserving integer keys
  on a row block held in VMEM (the scores are read once), then, only in a
  block where the threshold is tied, a bisection over the key index for the
  last tied key a row keeps, then, from the same block, the log-sum-exp of the
  scores a row keeps (its softmax's row sum in ``L_I``): three ``[T]`` vectors
  a call (``tau``, ``last``, ``lse``), which bear the ``checkpoint_name``
  ``dsa_select``, so the backward has all three as residuals and no plain-XLA
  pass reads the ``[T, T]`` scores for the loss;
- the selection as an ``int8`` mask ``[T, T]``, one elementwise pass of XLA
  over ``I`` and the two thresholds;
- ``_sparse_fwd_kernel`` / ``_sparse_bwd_kernel``: the grid-pipelined flash
  forward and its fused backward (``ops/attention.py``'s flash2, whose
  online-softmax update, VMEM rule and blocks they share) under a mask that is
  an **operand**, not a function of positions. The work is the dense causal
  one: what the selection empties inside a tile is time, not work;
- ``_target_kernel``: ``p``, the rows' KL and, where ``L_I`` is
  differentiated, ``dL_I/dI`` in the indexer's compute dtype **from the same
  walk**: the loss's custom-VJP forward makes ``dI`` once and keeps it as its
  causal tiles alone (``[n (n + 1) / 2 * block, block]`` for ``n`` square
  blocks a side: :func:`_packed_tile`), under the ``checkpoint_name``
  ``dsa_di``, so the backward runs no second call and a block's recomputation
  under ``save_flash`` none at all (277 MB a layer at 16,384 tokens in
  bfloat16); a call that nothing differentiates writes the rows alone. A grid
  step is a score tile for all the heads: they loop inside the kernel over
  strips of the tile's rows, their sum one value a strip, and the mask is read
  once a tile, at the close;
- ``_index_bwd_kernel``: the kept ``dL_I/dI`` through the relu to
  ``index_q``, ``index_k`` and ``index_w``, recomputing each head's products.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from edl_tpu.obs import trace as obs_trace
from edl_tpu.ops.attention import (
    NEG_INF,
    _FLASH2_BLOCKS_BWD,
    _FLASH2_BLOCKS_FWD,
    _bwd_delta,
    _dot_nn,
    _dot_nt,
    _dot_tn,
    _fit_block,
    _fold_dkv,
    _fused_bwd_vmem,
    _gqa_group,
    _interpret,
    _note_tiles,
    _softmax_update,
    _state_lanes,
    _vmem_capacity,
)

SELECT_NAME = "dsa_select"       # checkpoint_name of the select kernel's three rows
DI_NAME = "dsa_di"               # ... of dL_I/dI's causal tiles, made in the loss's forward
REMAT_NAMES = (SELECT_NAME, DI_NAME)
# the tile the index scores, the target and the indexer's backward are made in
# (a configuration's q / kv chunk of 512), and the rows a selection holds
_INDEX_BLOCKS = (512, 512)
_SELECT_ROWS = 128
_SELECT_CHUNK = 2048             # keys a pass of the count reads at a time
_TARGET_ROWS = 256               # rows of a tile the target sums the heads over at a time
_INT_MIN = -(2 ** 31)


# -- the plain reference ----------------------------------------------------


def index_scores_reference(index_q, index_k, index_w):
    """``I [T, T]`` (float32; every pair, the caller masks) from ``index_q
    [J, T, Di]``, ``index_k [T, Di]``, ``index_w [T, J]``: products in the
    operands' dtype accumulated in float32."""
    s = jnp.einsum(
        "jtd,sd->jts", index_q, index_k, preferred_element_type=jnp.float32
    )
    return jnp.einsum("tj,jts->ts", index_w.astype(jnp.float32), jnp.maximum(s, 0.0))


def select_reference(scores, topk: int):
    """The selection ``[T, T]`` (bool) from scores ``[T, T]``: per row the
    ``min(topk, t + 1)`` largest of its causal prefix, a tie to the lower
    index."""
    t = scores.shape[0]
    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    masked = jnp.where(cols <= rows, scores, -jnp.inf)
    k = min(topk, t)
    _, idx = jax.lax.top_k(masked, k)                         # [T, k]
    keep = jnp.arange(k)[None, :] < jnp.minimum(topk, rows + 1)
    return jnp.zeros((t, t), bool).at[rows, idx].max(keep)


def index_kl_reference(scores, mask, target):
    """``mean_t KL(target[t] || softmax over the selection of scores[t])``."""
    logq = jax.nn.log_softmax(jnp.where(mask, scores, NEG_INF), axis=-1)
    live = mask & (target > 0)
    term = jnp.where(live, target * (jnp.log(jnp.where(live, target, 1.0)) - logq), 0.0)
    return jnp.mean(jnp.sum(term, axis=-1))


def _masked_attention_reference(q, k, v, mask, scale):
    """``(o [H, T, D], probabilities [H, T, T] float32)`` of one sequence
    under the boolean selection."""
    group = q.shape[0] // k.shape[0]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q, k, preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(jnp.where(mask[None], s, NEG_INF), axis=-1)
    return jnp.einsum("hqk,hkd->hqd", probs.astype(v.dtype), v), probs


def _stats(mask, block_q: int, block_k: int):
    """``selected_share`` (selected over causal pairs) and ``tile_live`` (of
    the forward kernel's ``block_q x block_k`` tiles that touch the causal
    triangle, the share that holds a selected pair) of one selection."""
    t = mask.shape[0]
    picked = mask != 0
    bq, bk = _fit_block(block_q, t), _fit_block(block_k, t)
    tiles = jnp.any(picked.reshape(t // bq, bq, t // bk, bk), axis=(1, 3))
    under = (
        jnp.arange(t // bk)[None, :] * bk <= jnp.arange(t // bq)[:, None] * bq + bq - 1
    )
    return {
        "selected_share": jnp.sum(picked, dtype=jnp.float32) / (t * (t + 1) / 2),
        "tile_live": jnp.sum(tiles & under, dtype=jnp.float32) / jnp.sum(under),
    }


def _one_reference(q, k, v, index_q, index_k, index_w, topk, scale):
    scores = index_scores_reference(index_q, index_k, index_w)
    mask = select_reference(jax.lax.stop_gradient(scores), topk)
    out, probs = _masked_attention_reference(q, k, v, mask, scale)
    target = jax.lax.stop_gradient(jnp.mean(probs, axis=0))
    return (
        out, index_kl_reference(scores, mask, target),
        _stats(mask, *_FLASH2_BLOCKS_FWD), _detail(mask, scores),
    )


def sparse_attention_reference(q, k, v, index_q, index_k, index_w, topk, scale=None):
    """:func:`sparse_attention` in plain ``jax.numpy`` (batched: ``q [B, H,
    T, D]`` ...), dense ``[H, T, T]`` probabilities a sequence."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    outs = [
        _one_reference(q[b], k[b], v[b], index_q[b], index_k[b], index_w[b], topk, scale)
        for b in range(q.shape[0])
    ]
    return _stacked(outs)


def _detail(mask, scores):
    """What a check against a reference reads (dead code otherwise): the
    selection ``[T, T]`` (int8) and the scores it was made from (float32; on
    the kernels' path the tiles past the diagonal are unwritten)."""
    return {
        "selection": jax.lax.stop_gradient(mask.astype(jnp.int8)),
        "scores": jax.lax.stop_gradient(scores),
    }


def _stacked(outs):
    out = jnp.stack([o[0] for o in outs])
    kl = jnp.mean(jnp.stack([o[1] for o in outs]))
    stats = {
        name: jnp.mean(jnp.stack([o[2][name] for o in outs])) for name in outs[0][2]
    }
    detail = {
        name: jnp.stack([o[3][name] for o in outs]) for name in outs[0][3]
    }
    return out, kl, stats, detail


# -- index scores -----------------------------------------------------------


def _last_live(qi, block_q: int, block_k: int):
    """The last key block that the rows of q block ``qi`` see (causal)."""
    return jax.lax.div(qi * block_q + block_q - 1, block_k)


def _packed_tile(qi, ki):
    """The row block of causal tile ``(qi, ki)`` in an array that holds the
    tiles at and under the diagonal alone, row of tiles after row of tiles
    (square blocks): ``[n (n + 1) / 2 * block, block]`` where the rectangle is
    ``[n * block, n * block]``. A dead grid step (``ki > qi``) holds the row's
    last live tile, as ``held`` does in the rectangle."""
    return jax.lax.div(qi * (qi + 1), 2) + jax.lax.min(ki, qi)


def _packed_rows(t: int, block: int) -> int:
    """Rows of the packed array of :func:`_packed_tile` for ``[t, t]``."""
    n = t // block
    return n * (n + 1) // 2 * block


def _fold_lanes(x, lanes: int = 128, combine=jnp.add):
    """``[rows, n * lanes] -> [rows, lanes]``: the lane tiles summed (plain
    adds, or ``combine``; the one cross-lane step is left to the caller's
    last)."""
    if x.shape[1] <= lanes:
        return x
    part = x[:, :lanes]
    for t in range(1, x.shape[1] // lanes):
        part = combine(part, x[:, t * lanes:(t + 1) * lanes])
    return part


def _index_fwd_kernel(q_ref, k_ref, w_ref, o_ref, *, heads: int, block_q: int,
                      block_k: int):
    from jax.experimental import pallas as pl

    qi, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki <= _last_live(qi, block_q, block_k))
    def _tile():
        k = k_ref[...]
        w = w_ref[...]
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for j in range(heads):
            acc = acc + w[:, j:j + 1] * jnp.maximum(_dot_nt(q_ref[j], k), 0.0)
        o_ref[...] = acc


def _index_scores_kernels(index_q, index_k, index_w, block_q, block_k, interpret):
    """``I [T, T]`` float32; the tiles wholly past the diagonal are left
    unwritten (whatever the memory held: every reader masks by position)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    heads, t, di = index_q.shape

    def held(qi, ki):
        return jax.lax.min(ki, _last_live(qi, block_q, block_k))

    kernel = pl.pallas_call(
        functools.partial(
            _index_fwd_kernel, heads=heads, block_q=block_q, block_k=block_k
        ),
        out_shape=jax.ShapeDtypeStruct((t, t), jnp.float32),
        grid=(t // block_q, t // block_k),
        in_specs=[
            pl.BlockSpec((heads, block_q, di), lambda qi, ki: (0, qi, 0)),
            pl.BlockSpec((block_k, di), lambda qi, ki: (held(qi, ki), 0)),
            pl.BlockSpec((block_q, heads), lambda qi, ki: (qi, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_k), lambda qi, ki: (qi, held(qi, ki))),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )
    with obs_trace.span("kernel_trace", kernel="dsa_index_fwd"):
        return kernel(index_q, index_k, index_w.astype(jnp.float32))


def _index_bwd_kernel(d_ref, q_ref, k_ref, w_ref, dq_ref, dw_ref, dk_ref,
                      dq_scr, dw_scr, dk_scr, *, heads: int, block_q: int,
                      block_k: int, num_q: int, num_k: int):
    from jax.experimental import pallas as pl

    qi, ki = pl.program_id(0), pl.program_id(1)

    @pl.when((qi == 0) & (ki == 0))
    def _init_all():
        dk_scr[...] = jnp.zeros_like(dk_scr)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        dw_scr[...] = jnp.zeros_like(dw_scr)

    @pl.when(ki <= _last_live(qi, block_q, block_k))
    def _tile():
        k = k_ref[...]
        w = w_ref[...]
        d = d_ref[...].astype(jnp.float32)
        keys = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
        dk = jnp.zeros((block_k, k.shape[1]), jnp.float32)
        for j in range(heads):
            q = q_ref[j]
            s = _dot_nt(q, k)
            dw_scr[j] = dw_scr[j] + _fold_lanes(d * jnp.maximum(s, 0.0))
            ds = jnp.where(s > 0, d * w[:, j:j + 1], 0.0).astype(q.dtype)
            dq_scr[j] = dq_scr[j] + _dot_nn(ds, k)
            dk = dk + _dot_tn(ds, q)
        dk_scr[keys, :] = dk_scr[keys, :] + dk

    @pl.when(ki == num_k - 1)
    def _rows_out():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)
        dw_ref[...] = dw_scr[...]

    @pl.when((qi == num_q - 1) & (ki == num_k - 1))
    def _keys_out():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)


def _index_backward_kernels(d_scores, index_q, index_k, index_w, block_q, block_k,
                            interpret):
    """``(d index_q, d index_k, d index_w)`` from ``d_scores``, the causal
    tiles of ``dL_I/dI`` as :func:`_target_call` packs them (zero off the
    selection)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    heads, t, di = index_q.shape
    num_q, num_k = t // block_q, t // block_k
    lanes = min(128, block_k)

    def held(qi, ki):
        return jax.lax.min(ki, _last_live(qi, block_q, block_k))

    kernel = pl.pallas_call(
        functools.partial(
            _index_bwd_kernel, heads=heads, block_q=block_q, block_k=block_k,
            num_q=num_q, num_k=num_k,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((heads, t, di), index_q.dtype),
            jax.ShapeDtypeStruct((heads, t, lanes), jnp.float32),
            jax.ShapeDtypeStruct((t, di), jnp.float32),
        ],
        grid=(num_q, num_k),
        in_specs=[
            pl.BlockSpec((block_q, block_k), lambda qi, ki: (_packed_tile(qi, ki), 0)),
            pl.BlockSpec((heads, block_q, di), lambda qi, ki: (0, qi, 0)),
            pl.BlockSpec((block_k, di), lambda qi, ki: (held(qi, ki), 0)),
            pl.BlockSpec((block_q, heads), lambda qi, ki: (qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((heads, block_q, di), lambda qi, ki: (0, qi, 0)),
            pl.BlockSpec((heads, block_q, lanes), lambda qi, ki: (0, qi, 0)),
            # the one key head's whole gradient: the block stands still and
            # is written out at the last step
            pl.BlockSpec((t, di), lambda qi, ki: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, block_q, di), jnp.float32),
            pltpu.VMEM((heads, block_q, lanes), jnp.float32),
            pltpu.VMEM((t, di), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20,
        ),
    )
    with obs_trace.span("kernel_trace", kernel="dsa_index_bwd"):
        dq, dw, dk = kernel(
            d_scores, index_q, index_k, index_w.astype(jnp.float32)
        )
    return dq, dk.astype(index_k.dtype), jnp.sum(dw, axis=-1).T


# -- the selection ----------------------------------------------------------


def _order_bits(i):
    """A float32's bits (int32) to the key whose signed order is the floats',
    and back: its own inverse."""
    return i ^ ((i >> 31) & 0x7FFFFFFF)


def _order_key(x):
    """float32 -> int32 whose signed order is the floats' (``-0.0`` as
    ``+0.0``, which ``lax.top_k`` cannot tell apart either)."""
    x = jnp.where(x == 0.0, 0.0, x)
    return _order_bits(jax.lax.bitcast_convert_type(x, jnp.int32))


def _kept(key, cols, tau, last):
    """Whether a causal key is of the selection: above the row's threshold,
    or tied with it and no later than the last tied key the row keeps."""
    return (key > tau) | ((key == tau) & (cols <= last))


def _select_kernel(s_ref, tau_ref, last_ref, lse_ref, key_scr, *, topk: int,
                   rows: int, chunk: int, total: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(0)
    row0 = qi * rows
    # no key past the block's last row is causal: the passes stop there
    chunks = jax.lax.div(row0 + rows + chunk - 1, chunk)
    t = row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    want = jnp.minimum(topk, t + 1)                      # keys a row keeps

    def cols_of(c):
        return c * chunk + jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)

    def at(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    lanes = min(128, chunk)

    def fill(c, top):
        # a key past the diagonal holds _INT_MIN, under every finite score's:
        # no later pass asks for the causal test again
        key = jnp.where(cols_of(c) <= t, _order_key(s_ref[:, at(c)]), _INT_MIN)
        key_scr[:, at(c)] = key
        return jnp.maximum(top, _fold_lanes(key, combine=jnp.maximum))

    top = jax.lax.fori_loop(
        0, chunks, fill, jnp.full((rows, lanes), _INT_MIN, jnp.int32)
    )

    def count(hit):
        """Keys a row for which ``hit(keys of a chunk, c)`` holds: [rows, 1]."""
        def body(c, acc):
            return acc + _fold_lanes(hit(key_scr[:, at(c)], c).astype(jnp.int32))

        acc = jax.lax.fori_loop(0, chunks, body, jnp.zeros((rows, lanes), jnp.int32))
        return jnp.sum(acc, axis=-1, keepdims=True)

    def value_bit(i, found):
        # the threshold bit by bit from the top, in the keys' unsigned order
        # (a key with its sign bit flipped): the largest value that at least
        # ``want`` keys reach
        cand = found | jnp.left_shift(jnp.int32(1), 31 - i)
        reached = count(lambda keys, c: keys >= (cand ^ _INT_MIN))
        return jnp.where(reached >= want, cand, found)

    tau = jax.lax.fori_loop(
        0, 32, value_bit, jnp.zeros((rows, 1), jnp.int32)
    ) ^ _INT_MIN
    tau_ref[...] = tau
    above = count(lambda keys, c: keys > tau)
    tied = count(lambda keys, c: keys == tau)
    room = want - above          # tied keys a row keeps: the lowest indices
    last_ref[...] = jnp.full((rows, 1), total, jnp.int32)

    bits = max(1, (total - 1).bit_length())

    @pl.when(jnp.max(tied - room) > 0)
    def _ties():
        def index_bit(i, found):
            # the largest index j with fewer than ``room`` tied keys before it
            cand = found | jnp.left_shift(jnp.int32(1), bits - 1 - i)
            before = count(lambda keys, c: (keys == tau) & (cols_of(c) < cand))
            return jnp.where(before < room, cand, found)

        last_ref[...] = jax.lax.fori_loop(
            0, bits, index_bit, jnp.zeros((rows, 1), jnp.int32)
        )

    # the log-sum-exp of the scores a row keeps, about its largest causal
    # score, which is always kept
    m = jax.lax.bitcast_convert_type(
        _order_bits(jnp.max(top, axis=-1, keepdims=True)), jnp.float32
    )
    last = last_ref[...]

    def kept_exp(c, acc):
        keep = _kept(key_scr[:, at(c)], cols_of(c), tau, last)
        return acc + _fold_lanes(jnp.where(keep, jnp.exp(s_ref[:, at(c)] - m), 0.0))

    acc = jax.lax.fori_loop(0, chunks, kept_exp, jnp.zeros((rows, lanes), jnp.float32))
    lse_ref[...] = m + jnp.log(jnp.sum(acc, axis=-1, keepdims=True))


def _select_kernels(scores, topk: int, rows: int, chunk: int, interpret: bool):
    """``(tau, last, lse)``, three ``[T]``: row ``t`` keeps key ``s <= t`` iff
    ``key(I[t, s]) > tau[t]``, or ``== tau[t]`` and ``s <= last[t]`` (int32
    both); ``lse[t]`` (float32) is ``log sum exp(I[t, s])`` over the keys it
    keeps."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t = scores.shape[0]
    out = jax.ShapeDtypeStruct((t, 1), jnp.int32)
    spec = pl.BlockSpec((rows, 1), lambda qi: (qi, 0))
    kernel = pl.pallas_call(
        functools.partial(
            _select_kernel, topk=topk, rows=rows, chunk=chunk, total=t
        ),
        out_shape=[out, out, jax.ShapeDtypeStruct((t, 1), jnp.float32)],
        grid=(t // rows,),
        in_specs=[pl.BlockSpec((rows, t), lambda qi: (qi, 0))],
        out_specs=[spec, spec, spec],
        scratch_shapes=[pltpu.VMEM((rows, t), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(32 << 20, 4 * rows * t * 4),
        ),
    )
    with obs_trace.span("kernel_trace", kernel="dsa_select"):
        tau, last, lse = kernel(scores)
    return tau[:, 0], last[:, 0], lse[:, 0]


def selection_mask(scores, tau, last):
    """The selection ``[T, T]`` as int8 from the scores and the two
    thresholds of :func:`_select_kernels`."""
    t = scores.shape[0]
    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    keep = _kept(_order_key(scores), cols, tau[:, None], last[:, None])
    return (keep & (cols <= rows)).astype(jnp.int8)


# -- attention under the mask ----------------------------------------------


def _sparse_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, m_scr,
                       l_scr, acc_scr, *, scale: float, block_q: int,
                       block_k: int, num_k: int):
    """``ops/attention.py:_flash2_kernel`` with the visibility read from
    ``mask_ref`` ``[block_q, block_k]``. A row whose first tiles hold none
    of its keys carries ``m = NEG_INF`` and a sum of ones until its first key
    arrives, whose correction ``exp(NEG_INF - m)`` is 0: every row has a key."""
    from jax.experimental import pallas as pl

    qi, step = pl.program_id(1), pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(step <= _last_live(qi, block_q, block_k))
    def _update():
        s = _dot_nt(q_ref[0], k_ref[0]) * scale
        s = jnp.where(mask_ref[...].astype(jnp.int32) != 0, s, NEG_INF)
        m_scr[:], l_scr[:], acc_scr[:] = _softmax_update(
            s, m_scr[:], l_scr[:], acc_scr[:], v_ref[0]
        )

    @pl.when(step == num_k - 1)
    def _finalize():
        l = jnp.maximum(jnp.sum(l_scr[:], axis=-1, keepdims=True), 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:, :1] + jnp.log(l)


def _sparse_forward(q, k, v, mask, scale, block_q, block_k, interpret):
    """``(o [H, T, D], lse [H, T])`` of one sequence under ``mask [T, T]``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, t, d = q.shape
    group = h // k.shape[0]
    num_k = t // block_k

    def held(qi, s):
        return jax.lax.min(s, _last_live(qi, block_q, block_k))

    kv_spec = pl.BlockSpec(
        (1, block_k, d), lambda i, qi, s: (i // group, held(qi, s), 0)
    )
    rows = pl.BlockSpec((1, block_q, d), lambda i, qi, s: (i, qi, 0))
    kernel = pl.pallas_call(
        functools.partial(
            _sparse_fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
            num_k=num_k,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((h, t, d), q.dtype),
            jax.ShapeDtypeStruct((h, t, 1), jnp.float32),
        ],
        grid=(h, t // block_q, num_k),
        in_specs=[
            rows, kv_spec, kv_spec,
            pl.BlockSpec((block_q, block_k), lambda i, qi, s: (qi, held(qi, s))),
        ],
        out_specs=[rows, pl.BlockSpec((1, block_q, 1), lambda i, qi, s: (i, qi, 0))],
        scratch_shapes=[
            pltpu.VMEM((block_q, _state_lanes(block_k)), jnp.float32),
            pltpu.VMEM((block_q, _state_lanes(block_k)), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )
    _note_tiles("sparse_fwd", t, t, block_q, block_k, True, None, "kv")
    with obs_trace.span("kernel_trace", kernel="sparse_fwd"):
        out, lse = kernel(q, k, v, mask)
    return out, lse[..., 0]


def _sparse_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                       dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *,
                       scale: float, block_q: int, block_k: int, num_q: int,
                       num_k: int):
    """``ops/attention.py:_flash2_bwd_kernel`` (one walk for all three
    gradients, the tile transposed, a head's whole dq in VMEM) with the
    visibility read from ``mask_ref``, the mask's transpose ``[block_k,
    block_q]``."""
    from jax.experimental import pallas as pl

    ki, step = pl.program_id(1), pl.program_id(2)

    @pl.when((ki == 0) & (step == 0))
    def _init_head():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(step * block_q + block_q - 1 >= ki * block_k)
    def _update():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = _dot_nt(k, q) * scale                       # [bk, bq]
        s = jnp.where(mask_ref[...].astype(jnp.int32) != 0, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])
        dv_scr[:] = dv_scr[:] + _dot_nn(p.astype(do.dtype), do)
        ds = (p * (_dot_nt(v, do) - delta_ref[0])).astype(q.dtype)
        dk_scr[:] = dk_scr[:] + _dot_nn(ds, q)
        rows = pl.ds(pl.multiple_of(step * block_q, block_q), block_q)
        dq_scr[rows, :] = dq_scr[rows, :] + _dot_tn(ds, k)

    @pl.when(step == num_q - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when((ki == num_k - 1) & (step == num_q - 1))
    def _finalize_head():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _sparse_backward_kernels(q, k, v, g, lse, delta, mask_t, scale, block_q,
                             block_k, interpret):
    """``(dq, dk, dv)`` of one sequence; ``lse`` / ``delta`` ``[H, T]``,
    ``mask_t`` the selection's transpose ``[T keys, T rows]``; dk / dv at the
    grouped width."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, t, d = q.shape
    h_kv = k.shape[0]
    group = h // h_kv
    num_q, num_k = t // block_q, t // block_k

    def held(ki, s):  # a dead step (rows before the block's first key) holds the first live
        return jax.lax.max(s, jax.lax.div(ki * block_k, block_q))

    rows = pl.BlockSpec((1, block_q, d), lambda i, ki, s: (i, held(ki, s), 0))
    lanes = pl.BlockSpec((1, 1, block_q), lambda i, ki, s: (i, 0, held(ki, s)))
    kv_block = pl.BlockSpec((1, block_k, d), lambda i, ki, s: (i // group, ki, 0))
    dkv = pl.BlockSpec((1, block_k, d), lambda i, ki, s: (i, ki, 0))
    need = _fused_bwd_vmem(t, d, block_q, block_k, q.dtype.itemsize)[1]
    need += 2 * block_q * block_k                       # the mask's two buffers
    kernel = pl.pallas_call(
        functools.partial(
            _sparse_bwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
            num_q=num_q, num_k=num_k,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((h, t, d), q.dtype),
            jax.ShapeDtypeStruct((h, t, d), k.dtype),
            jax.ShapeDtypeStruct((h, t, d), v.dtype),
        ],
        grid=(h, num_k, num_q),
        in_specs=[
            rows, kv_block, kv_block, rows, lanes, lanes,
            pl.BlockSpec((block_k, block_q), lambda i, ki, s: (ki, held(ki, s))),
        ],
        out_specs=[pl.BlockSpec((1, t, d), lambda i, ki, s: (i, 0, 0)), dkv, dkv],
        scratch_shapes=[
            pltpu.VMEM((t, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=need,
        ),
    )
    _note_tiles("sparse_bwd", t, t, block_q, block_k, True, None, "q", acc_bytes=t * d * 4)
    with obs_trace.span("kernel_trace", kernel="sparse_bwd"):
        dq, dk, dv = kernel(q, k, v, g, lse[:, None, :], delta[:, None, :], mask_t)
    dk, dv = _fold_dkv(dk[None], dv[None], 1, h_kv, group, t, d)
    return dq, dk[0], dv[0]


def _attention_blocks(t: int, d: int, itemsize: int, blocks=None):
    """``((fwd block_q, block_k), (bwd block_q, block_k))`` fitted to ``t``,
    or ``None`` where the kernels cannot tile it: flash2's full-causal sweep
    (the mask's tiles are int8: 32 sublanes, whole lane tiles)."""
    fwd, bwd = blocks or (_FLASH2_BLOCKS_FWD, _FLASH2_BLOCKS_BWD)
    fwd = tuple(_fit_block(b, t) for b in fwd)
    bwd = tuple(_fit_block(b, t) for b in bwd)
    fits = all(t % b == 0 for b in fwd + bwd)
    need = _fused_bwd_vmem(t, d, *bwd, itemsize)[1]
    if not fits or need > _vmem_capacity() // 2:
        return None
    return fwd, bwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _masked_flash(q, k, v, mask, scale, blocks, interpret):
    return _masked_flash_fwd(q, k, v, mask, scale, blocks, interpret)[0]


def _masked_flash_fwd(q, k, v, mask, scale, blocks, interpret):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _sparse_forward(q, k, v, mask, scale, *blocks[0], interpret)
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return (out, lse), (q, k, v, mask, out, lse)


def _masked_flash_bwd(scale, blocks, interpret, residuals, cotangents):
    q, k, v, mask, out, lse = residuals
    g, _ = cotangents          # lse feeds the detached target alone
    h, t, d = q.shape
    # the mask row-major, as its kernels read it, and this transpose a copy of
    # 268 MB of int8. Left to itself XLA makes the mask (the forward's too)
    # column-major so that the transpose is free, and first copies the 1.07 GB
    # of float32 scores into that layout, twice a layer; the target's dI call
    # in the backward used to hold the layout, and is gone
    mask = with_layout_constraint(mask, Layout(major_to_minor=(0, 1)))
    delta = _bwd_delta(g, out, 1, h, t, d)
    dq, dk, dv = _sparse_backward_kernels(
        q, k, v, g, lse, delta, mask.T, scale, *blocks[1], interpret
    )
    return dq, dk, dv, None


_masked_flash.defvjp(_masked_flash_fwd, _masked_flash_bwd)


# -- the indexer's target and loss -----------------------------------------


def _target_rows(block_q: int) -> int:
    """Rows of the strip of a score tile the target kernel sums the heads over
    at a time (every key of the tile)."""
    return _fit_block(_TARGET_ROWS, block_q)


def _target_kernel(q_ref, k_ref, lse_ref, mask_ref, s_ref, lsi_ref, *refs,
                   scale: float, heads: int, group: int, block_q: int,
                   block_k: int, num_k: int, strip_rows: int, with_grad: bool):
    """One grid step is one score tile for every head: ``q_ref [heads,
    block_q, D]``, ``k_ref [Hkv, block_k, D]``, ``lse_ref [heads, block_q, 1]``
    (the masked forward's row sums in the shape it writes them: XLA then
    schedules the kernels around this call as it did before the heads came
    inside). The tile is walked in strips of ``strip_rows`` rows; in a strip
    the heads are a static loop whose sum of ``exp(s * scale - lse_h)`` is one
    value, added bare in the heads' order, and the selection is applied once,
    to the sum, at the strip's close. An
    unpicked key's exponent can pass float32 (``lse_h`` is over the selected
    keys only), so the sum may hold ``inf`` off the selection: the select comes
    first, before the ``1 / heads``, the ``log`` and the product, and no ``inf
    * 0`` is formed. ``refs``: the rows' KL, ``dI`` with ``with_grad``, and the
    KL's lane-folded scratch."""
    from jax.experimental import pallas as pl

    kl_ref, kl_scr = refs[0], refs[-1]
    d_ref = refs[1] if with_grad else None
    qi, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki == 0)
    def _init_rows():
        kl_scr[...] = jnp.zeros_like(kl_scr)

    @pl.when(ki <= _last_live(qi, block_q, block_k))
    def _tile():
        def strip(r, carry):
            rows = pl.ds(pl.multiple_of(r * strip_rows, strip_rows), strip_rows)
            total = None
            for h in range(heads):
                s = _dot_nt(q_ref[h, rows, :], k_ref[h // group]) * scale
                p = jnp.exp(s - lse_ref[h, rows, :])
                total = p if total is None else total + p
            picked = mask_ref[rows, :].astype(jnp.int32) != 0
            target = jnp.where(picked, total, 0.0) * (1.0 / heads)
            logq = s_ref[rows, :] - lsi_ref[rows, :]
            live = picked & (target > 0)
            term = jnp.where(
                live, target * (jnp.log(jnp.where(live, target, 1.0)) - logq), 0.0
            )
            kl_scr[rows, :] = kl_scr[rows, :] + _fold_lanes(term)
            if with_grad:
                d_ref[rows, :] = jnp.where(
                    picked, jnp.exp(logq) - target, 0.0
                ).astype(d_ref.dtype)
            return carry

        jax.lax.fori_loop(0, block_q // strip_rows, strip, 0)

    @pl.when(ki == num_k - 1)
    def _rows_out():
        kl_ref[...] = jnp.sum(kl_scr[...], axis=-1, keepdims=True)


def _target_vmem(h, h_kv, d, itemsize, block_q, block_k, strip_rows, grad_itemsize):
    """Bytes of VMEM the target kernel asks for: two buffers of every block (a
    ``[rows, 1]`` block is a lane tile wide: the heads' row sums are 8 MB a
    buffer at 32 x 512), the KL's scratch, and sixteen of a strip's float32
    values for Mosaic's own."""
    keys = max(128, block_k)
    blocks = (
        (h * block_q + h_kv * block_k) * d * itemsize
        + block_q * keys * (1 + 4 + grad_itemsize)
        + (h + 2) * block_q * 128 * 4
    )
    return 2 * blocks + block_q * 128 * 4 + 16 * strip_rows * keys * 4


def _target_kernels(q, k, lse, mask, scores, lse_index, scale, block_q, block_k,
                    interpret, grad_dtype=None):
    """The rows' ``KL(p || softmax over the selection of I)`` ``[T]`` and, with
    ``grad_dtype``, ``d (sum of them) / d I`` (``softmax - p`` on the
    selection, 0 off it) as its causal tiles alone, ``[n (n + 1) / 2 * block,
    block]``: tile ``(qi, ki)`` at row block :func:`_packed_tile`."""
    return _target_call(
        q, k, lse, mask, scores, lse_index, scale, block_q, block_k,
        _target_rows(block_q), interpret, grad_dtype,
    )


# jitted so that a step traces and lowers the body, 32 heads unrolled, once a
# mode and not once a call site (fifteen a step at five layers)
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11))
def _target_call(q, k, lse, mask, scores, lse_index, scale, block_q, block_k,
                 strip_rows, interpret, grad_dtype):
    """The grid is the score tiles alone: a row of tiles fetches its q block,
    every head's, once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, t, d = q.shape
    h_kv = k.shape[0]
    num_k = t // block_k
    with_grad = grad_dtype is not None

    def held(qi, ki):
        return jax.lax.min(ki, _last_live(qi, block_q, block_k))

    tile = pl.BlockSpec((block_q, block_k), lambda qi, ki: (qi, held(qi, ki)))
    row = pl.BlockSpec((block_q, 1), lambda qi, ki: (qi, 0))
    out_shape = [jax.ShapeDtypeStruct((t, 1), jnp.float32)]
    out_specs = [row]
    if with_grad:
        out_shape.append(
            jax.ShapeDtypeStruct((_packed_rows(t, block_q), block_k), grad_dtype)
        )
        out_specs.append(
            pl.BlockSpec((block_q, block_k), lambda qi, ki: (_packed_tile(qi, ki), 0))
        )
    kernel = pl.pallas_call(
        functools.partial(
            _target_kernel, scale=scale, heads=h, group=h // h_kv, block_q=block_q,
            block_k=block_k, num_k=num_k, strip_rows=strip_rows,
            with_grad=with_grad,
        ),
        out_shape=out_shape,
        grid=(t // block_q, num_k),
        in_specs=[
            pl.BlockSpec((h, block_q, d), lambda qi, ki: (0, qi, 0)),
            pl.BlockSpec((h_kv, block_k, d), lambda qi, ki: (0, held(qi, ki), 0)),
            pl.BlockSpec((h, block_q, 1), lambda qi, ki: (0, qi, 0)),
            tile, tile, row,
        ],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((block_q, min(128, block_k)), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_target_vmem(
                h, h_kv, d, q.dtype.itemsize, block_q, block_k, strip_rows,
                jnp.dtype(grad_dtype).itemsize if with_grad else 0,
            ),
        ),
    )
    # the scope names the call in a device trace (the innermost one counts)
    with obs_trace.span("kernel_trace", kernel="dsa_target"), jax.named_scope("dsa_target"):
        outs = kernel(q, k, lse[..., None], mask, scores, lse_index[:, None])
    return (outs[0][:, 0], outs[1]) if with_grad else (outs[0][:, 0], None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11))
def _index_loss(index_q, index_k, index_w, scores, mask, lse_index, q, k, lse,
                scale, blocks, interpret):
    """``L_I`` of one sequence from the indexer's three operands; ``scores``
    (their ``I``), the selection, its rows' log-sum-exp of ``I`` (the select
    kernel's) and the main attention's ``q``, ``k``, ``lse`` are constants
    here. Where nothing is differentiated the target kernel writes the rows'
    KL and no ``[T, T]``-sized array."""
    rows, _ = _target_kernels(
        q, k, lse, mask, scores, lse_index, scale, *blocks, interpret
    )
    return jnp.mean(rows)


def _index_loss_fwd(index_q, index_k, index_w, scores, mask, lse_index, q, k,
                    lse, scale, blocks, interpret):
    """The value and ``dL_I/dI`` from one walk of the score tiles: ``dI``'s
    causal tiles are the residual, by name (``DI_NAME``), so the backward
    calls the target kernel no second time and a block's recomputation under
    a policy that keeps the name drops the call (both its outputs are then a
    value nobody reads and a saved name)."""
    from jax.ad_checkpoint import checkpoint_name

    rows, d_scores = _target_kernels(
        q, k, lse, mask, scores, lse_index, scale, *blocks, interpret,
        grad_dtype=index_q.dtype,
    )
    d_scores = checkpoint_name(d_scores, DI_NAME)
    return jnp.mean(rows), (index_q, index_k, index_w, d_scores)


def _index_loss_bwd(scale, blocks, interpret, residuals, g):
    index_q, index_k, index_w, d_scores = residuals
    with jax.named_scope("dsa_index"):  # the innermost scope counts
        dq, dk, dw = _index_backward_kernels(
            d_scores, index_q, index_k, index_w, *blocks, interpret
        )
    g = g / index_q.shape[1]    # the mean over the rows
    return (
        (dq * g).astype(index_q.dtype), (dk * g).astype(index_k.dtype),
        (dw * g).astype(index_w.dtype), None, None, None, None, None, None,
    )


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


# -- the whole --------------------------------------------------------------


def _note_shape(tq, topk, index_heads, index_dim, select, index_lse,
                score_bytes, mask_bytes, index_blocks, fwd_blocks, bwd_blocks,
                select_rows, target_heads_step, di_bytes):
    """One ``dsa_shape`` instant in the span ring for each shape the
    selection's kernels are traced at in a stage (``note_once``;
    ``index_lse``: where the row sums of the indexer's softmax are made;
    ``target_*``: the target kernel's tile, the heads a grid step of it takes
    and the strip their sum is one value over; ``target_grad``: the call that
    makes ``dL_I/dI``, the loss's forward, and ``di_bytes`` what it keeps of
    it for the backward, the causal tiles)."""
    obs_trace.get_tracer().note_once(
        "dsa_shape", tq=tq, topk=topk, index_heads=index_heads,
        index_dim=index_dim, select=select, index_lse=index_lse,
        score_bytes=score_bytes,
        mask_bytes=mask_bytes, index_blocks=list(index_blocks),
        fwd_blocks=list(fwd_blocks), bwd_blocks=list(bwd_blocks),
        select_rows=select_rows, target_blocks=list(index_blocks),
        target_heads_step=target_heads_step,
        target_strip=[_target_rows(index_blocks[0]), index_blocks[1]],
        target_grad="forward", di_bytes=di_bytes,
        path="kernel",
    )


def _kernel_plan(t, d, itemsize, blocks=None):
    """The blocks of every kernel at ``T = t``, or ``None`` where one of them
    cannot tile it (the caller then takes the reference): whole lane tiles of
    keys, no block under an int8 tile's 32 rows, and square index blocks
    (``dL_I/dI`` is kept as its causal tiles: :func:`_packed_tile`)."""
    if t % 128:
        return None
    attn = _attention_blocks(t, d, itemsize, blocks)
    index = tuple(_fit_block(b, t) for b in _INDEX_BLOCKS)
    rows = _fit_block(_SELECT_ROWS, t)
    chunk = _fit_block(_SELECT_CHUNK, t)
    whole = index[0] == index[1] and all(t % b == 0 for b in index + (rows, chunk))
    if attn is None or not whole or min(index + attn[0] + attn[1]) < 32:
        return None
    return {"fwd": attn[0], "bwd": attn[1], "index": index, "rows": rows, "chunk": chunk}


def _one_kernels(q, k, v, index_q, index_k, index_w, topk, scale, plan, interpret):
    from jax.ad_checkpoint import checkpoint_name

    stop = jax.lax.stop_gradient
    t = q.shape[1]
    with jax.named_scope("dsa_index"):
        scores = _index_scores_kernels(
            stop(index_q), stop(index_k), stop(index_w), *plan["index"], interpret
        )
    with jax.named_scope("dsa_select"):
        tau, last, lse_index = (
            checkpoint_name(row, SELECT_NAME)
            for row in _select_kernels(
                scores, topk, plan["rows"], plan["chunk"], interpret
            )
        )
        mask = selection_mask(scores, tau, last)
        stats = _stats(mask, *plan["fwd"])
    _note_shape(
        t, topk, index_q.shape[0], index_q.shape[2], "bisect", "select",
        t * t * 4, t * t, plan["index"], plan["fwd"], plan["bwd"], plan["rows"],
        q.shape[0],
        _packed_rows(t, plan["index"][0]) * plan["index"][1] * index_q.dtype.itemsize,
    )
    with jax.named_scope("attn_sparse"):
        out, lse = _masked_flash(
            q, k, v, mask, scale, (plan["fwd"], plan["bwd"]), interpret
        )
    with jax.named_scope("dsa_target"):
        kl = _index_loss(
            index_q, index_k, index_w, scores, mask, lse_index, stop(q), stop(k),
            stop(lse), scale, plan["index"], interpret,
        )
    return out, kl, stats, _detail(mask, scores)


def sparse_attention(q, k, v, index_q, index_k, index_w, topk: int, scale=None,
                     interpret=None, blocks=None):
    """``(o [B, H, T, D], L_I, stats, detail)``: see the module's text. ``q [B, H, T,
    D]``, ``k`` / ``v [B, Hkv, T, D]``, ``index_q [B, J, T, Di]``, ``index_k
    [B, T, Di]``, ``index_w [B, T, J]``; ``stats`` holds the scalars
    ``selected_share`` and ``tile_live``. On the TPU the kernels run wherever
    they tile ``T``; off it the reference does (``interpret=True``: the
    kernels, interpreted, as the tests run them); the ``dsa_shape`` note says
    which (``path``, and on ``plain`` ``why``: ``backend`` or ``blocks``). ``blocks``: ``((fwd block_q,
    block_k), (bwd block_q, block_k))`` in place of flash2's sweep."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    _gqa_group(q, k)
    kernels = jax.default_backend() == "tpu" if interpret is None else interpret
    plan = kernels and _kernel_plan(
        q.shape[2], q.shape[3], q.dtype.itemsize, blocks
    )
    if not plan:
        obs_trace.get_tracer().note_once(
            "dsa_shape", tq=q.shape[2], topk=topk, index_heads=index_q.shape[1],
            index_dim=index_q.shape[3], path="plain",
            why="blocks" if kernels else "backend",
        )
        return sparse_attention_reference(
            q, k, v, index_q, index_k, index_w, topk, scale
        )
    interpret = _interpret()
    return _stacked([
        _one_kernels(
            q[b], k[b], v[b], index_q[b], index_k[b], index_w[b], topk, scale,
            plan, interpret,
        )
        for b in range(q.shape[0])
    ])
