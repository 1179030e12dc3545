"""Attention: jnp reference + one family of Pallas flash-attention TPU
kernels (:func:`attention` runs them on the TPU at every shape they tile).

A flash kernel streams KV blocks through VMEM with the online-softmax
recurrence (running row-max ``m``, denominator ``l``, numerator ``acc``),
so the [Tq, Tk] score matrix never materializes in HBM — the standard
memory-bandwidth win on TPU where HBM, not FLOPs, bounds attention.

Layout: ``[batch, heads, seq, head_dim]``. The kernels are grid-pipelined
(``flash2``: ``_flash2_kernel`` forward, ``_flash2_bwd_kernel`` backward):
the grid is ``(batch*heads, q_blocks, kv_blocks)`` (backward: the q blocks
innermost), so the other side's blocks are copied block by block behind
the compute and a kernel's VMEM does not grow with the sequence. A mask is
one of **three kinds**: causal (``causal``: query ``i`` sees keys ``j <= i``,
sequence ends aligned), a **window** (``window=W`` with ``causal``: ``i - W <
j <= i``), or **block diffusion** (``block_diffusion=(L, B)`` with ``causal``,
over ``2 L`` positions, a clean copy of a sequence and then its noised copy,
in blocks of ``B``: :func:`_sees` has the rule; a block then sees up to two
runs of the other side's blocks that do not touch, and its innermost steps
walk the one and jump to the other). Under a mask the innermost steps are **spans** of the
other side that start where a block's first visible key (or row) lies, at
an element and not at a block under a window; a step the mask leaves
nothing for holds the nearest live span again, so what a block cannot see
is neither copied nor computed, and every live tile is masked on both
edges. The dense reference takes a window as a mask. Causal masking
compares global q/k positions from ``broadcasted_iota`` (TPU needs ≥2D
iota).

``flash_attention`` is differentiable via ``jax.custom_vjp`` with REAL
flash backward kernels: the forward saves per-row logsumexp (``lse``),
the backward recomputes probabilities blockwise as ``exp(s - lse)`` (no
online-softmax rescan needed), so the backward, where training time
actually goes, also never materializes the [Tq, Tk] score matrix. It is
**one** kernel: a kv block's walk over its rows computes a tile's ``s``,
``p``, ``dp`` and ``ds`` once and adds to all three gradients (five matmuls
a tile, not seven), with the head's whole ``dq`` accumulated in VMEM; a
head whose accumulator the chip's VMEM cannot hold
(:func:`_fused_bwd_vmem`) keeps the older pair, ``_flash2_bwd_dq_kernel``
and ``_flash2_bwd_dkv_kernel``. Ragged shapes fall back to the jnp
reference end-to-end (forward and backward agree by construction).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from edl_tpu.obs import trace as obs_trace

NEG_INF = -1e30


def _dense_causal_mask(
    scores: jax.Array, window: int | None = None, bd=None
) -> jax.Array:
    """End-aligned causal mask for a dense [..., Tq, Tk] score tensor:
    ``qpos = arange(Tq) + (Tk - Tq)`` so sequence ENDS line up (the one
    convention every path in this module must share). With ``window`` a
    query sees the ``window`` newest of those keys, itself included:
    ``qpos - window < kpos <= qpos``; with ``bd`` the block-diffusion rule
    of :func:`_sees`."""
    tq, tk = scores.shape[-2], scores.shape[-1]
    qpos = jnp.arange(tq)[:, None] + (tk - tq)
    kpos = jnp.arange(tk)[None, :]
    return jnp.where(_sees(qpos, kpos, window, bd), scores, NEG_INF)


def _sees(qpos, kpos, window, bd=None):
    """Whether the query at ``qpos`` sees the key at ``kpos``. One of three
    kinds: causal; causal inside a ``window``; or block diffusion, ``bd = (L,
    B)`` over ``2 L`` positions, the clean sequence in ``[0, L)`` and its
    noised copy in ``[L, 2 L)``, position ``i`` of either half in block ``(i
    mod L) // B``: a clean query sees the clean keys of its own and of earlier
    blocks (causal by whole blocks) and no noised key; a noised query sees the
    clean keys of STRICTLY earlier blocks (never its own block's clean tokens,
    which are its answers) and the noised keys of its own block, both ways."""
    if bd is not None:
        length, block = bd
        q_noised, k_noised = qpos >= length, kpos >= length
        return _bd_visible(
            jnp.where(q_noised, qpos - length, qpos),
            jnp.where(k_noised, kpos - length, kpos), q_noised, k_noised, block,
        )
    if window is None:
        return qpos >= kpos
    return (qpos >= kpos) & (qpos - kpos < window)


_FAR = 1 << 30  # below every position's distance, and above


def _bd_visible(q, k, q_noised, k_noised, block, where=jnp.where):
    """:func:`_sees`'s block-diffusion rule on positions inside their halves
    (``q``, ``k``: ``i mod L``) and the halves themselves (arrays in the dense
    reference; in a kernel a tile lies in one half of each side, so they are
    scalars and the rule is two comparisons of the tile). With ``t`` the
    key's distance from the first position of the query's block, the key is
    seen iff ``lo <= t < hi``: clean keys from any distance below up to the
    query's own block (a clean query: ``hi = B``) or short of it (a noised
    one: ``hi = 0``), noised keys inside the block (``0 <= t < B``) and by a
    noised query alone."""
    start = q & -block if block & (block - 1) == 0 else q // block * block
    t = k - start
    hi = where(k_noised, where(q_noised, block, -_FAR), where(q_noised, 0, block))
    lo = where(k_noised, 0, -_FAR)
    return (t >= lo) & (t < hi)


def _check_window(window, causal: bool, block_diffusion=None, tq=None, tk=None):
    """The mask's kind from the arguments every entry point takes: refuses a
    window that is not a causal mask's, a block-diffusion mask over another
    shape than its own, and both at once."""
    if window is not None and block_diffusion is not None:
        raise ValueError(
            "a window (%r) and block_diffusion (%r) together: a mask is causal, "
            "a window or block diffusion, one kind a call"
            % (window, block_diffusion)
        )
    if window is not None and (not causal or window < 1):
        raise ValueError(
            "a window (%r) is the newest keys of a causal mask: it needs "
            "causal=True and at least one key" % (window,)
        )
    if block_diffusion is not None:
        length, block = block_diffusion
        if not causal or block < 1 or length < block or length % block or not (
            tq == tk == 2 * length
        ):
            raise ValueError(
                "block_diffusion=(L, B) (%r) is causal by blocks of B over 2 L "
                "positions, a clean copy and a noised one: it needs causal=True, "
                "B dividing L and 2 L queries and keys (%r, %r)"
                % (block_diffusion, tq, tk)
            )


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: float | None = None,
    window: int | None = None,
    block_diffusion: tuple[int, int] | None = None,
) -> jax.Array:
    """Plain softmax attention; [B, H, T, D] in, [B, H, Tq, D] out."""
    return attention_reference_with_lse(
        q, k, v, causal=causal, scale=scale, window=window,
        block_diffusion=block_diffusion,
    )[0]


def _gqa_group(q: jax.Array, k: jax.Array) -> int:
    """q heads per kv head (1 = plain MHA). Every entry point accepts
    k/v with FEWER heads than q (GQA/MQA) as long as the count divides:
    the kernels read the grouped arrays directly via index mapping (no
    materialized repeat), and dk/dv come back at the grouped width."""
    h, h_kv = q.shape[1], k.shape[1]
    if h == h_kv:
        return 1
    if h_kv < 1 or h % h_kv:
        raise ValueError(
            "kv heads (%d) must divide q heads (%d)" % (h_kv, h)
        )
    return h // h_kv


def _broadcast_kv(q, k, v):
    g = _gqa_group(q, k)
    if g == 1:
        return k, v
    return jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)


def _fold_dkv(dk, dv, b, h_kv, group, tk, d):
    """Sum full-q-head-width dk/dv back to the grouped input width (``d``:
    dk's; dv keeps its own where the values are narrower than the keys)."""
    if group == 1:
        return dk, dv
    dk = dk.reshape(b, h_kv, group, tk, d).sum(axis=2)
    dv = dv.reshape(b, h_kv, group, tk, dv.shape[-1]).sum(axis=2)
    return dk, dv


def attention_reference_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: float | None = None,
    window: int | None = None,
    block_diffusion: tuple[int, int] | None = None,
):
    """Reference attention that also returns per-row logsumexp of the
    scaled scores ``[B, H, Tq]`` — the residual blockwise/ring merging
    needs. Grouped k/v (GQA) broadcast in-graph; their VJP folds dk/dv
    back to the grouped width automatically. The mask, of whichever kind,
    is a dense boolean array here."""
    _check_window(window, causal, block_diffusion, q.shape[2], k.shape[2])
    if scale is None:
        scale = q.shape[-1] ** -0.5
    k, v = _broadcast_kv(q, k, v)
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        scores = _dense_causal_mask(scores, window, block_diffusion)
    lse = jax.scipy.special.logsumexp(scores, axis=-1)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)
    return out, lse


# -- pallas kernel ----------------------------------------------------------
#
# Matmul operands stay in the INPUT dtype (bf16 in training) with fp32
# accumulation via preferred_element_type: the v5e MXU multiplies bf16 at
# full rate but fp32 at a fraction of it, and the round-4 kernels' cast-
# everything-to-fp32 habit measured ~30 TFLOP/s on a 197 TFLOP/s chip.
# Probabilities are cast back to the value dtype for the p@v / p.T@do
# products — exactly what attention_reference's ``probs.astype(v.dtype)``
# does, so kernel and reference share input precision. Softmax state,
# lse/delta and all accumulators remain fp32. The helpers below express
# the transposed products as dot_general contractions so no operand is
# materialized transposed in VMEM.


def _dot_nt(a, b):
    """``a [m, d] @ b [n, d].T -> fp32 [m, n]`` without a transpose."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_nn(a, b):
    """``a [m, k] @ b [k, n] -> fp32 [m, n]``."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_tn(a, b):
    """``a [k, m].T @ b [k, n] -> fp32 [m, n]`` without a transpose."""
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _causal_mask(s, q_lo, k_lo, window=None, keys_first=False, bd=None):
    """Mask one [rows, keys] score tile (``keys_first``: [keys, rows])
    whose first row sits at position ``q_lo`` (its index plus ``q_offset =
    tk - tq``, which aligns sequence *ends*, matching
    ``attention_reference``) and whose first key is ``k_lo``; ``window`` and
    ``bd`` as in :func:`_dense_causal_mask`. A block-diffusion tile lies in
    one half of each side (the blocks divide ``L``), so which halves is read
    off its corner and the positions count from their half's start."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, int(keys_first))
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, int(not keys_first))
    if bd is not None:
        length, block = bd
        q_noised, k_noised = q_lo >= length, k_lo >= length
        seen = _bd_visible(
            qpos + (q_lo - _select(q_noised, length, 0)),
            kpos + (k_lo - _select(k_noised, length, 0)),
            q_noised, k_noised, block, where=_select,
        )
        return jnp.where(seen, s, NEG_INF)
    return jnp.where(_sees(qpos + q_lo, kpos + k_lo, window), s, NEG_INF)


def _select(flag, a, b):
    """``jnp.where`` of two whole numbers on a kernel's scalar flag."""
    return jax.lax.select(flag, jnp.int32(a), jnp.int32(b))


def _tile_class(q_lo, nq, k_lo, nk, window, bd=None):
    """``(dead, interior)`` of the tile whose ``nq`` rows start at position
    ``q_lo`` and whose ``nk`` keys start at ``k_lo``: dead when no row sees
    any key, interior when every row sees every key, an edge (the diagonal,
    the window's old side or a block-diffusion boundary crosses it)
    otherwise. Scalars in a kernel, numpy arrays in :func:`tile_census`."""
    q_hi = q_lo + nq - 1
    k_hi = k_lo + nk - 1
    if bd is not None:
        # a tile in one half of each side; rows and keys by their blocks
        length, block = bd
        q_noised, k_noised = q_lo >= length, k_lo >= length
        first, last = (q_lo % length) // block, (q_hi % length) // block
        k_first, k_last = (k_lo % length) // block, (k_hi % length) // block
        own = q_noised & k_noised        # noised rows, noised keys: own block
        clean = ~q_noised & ~k_noised    # causal by blocks
        earlier = q_noised & ~k_noised   # strictly earlier blocks
        dead = (
            (~q_noised & k_noised) | (clean & (k_first > last))
            | (earlier & (k_first >= last))
            | (own & ((k_first > last) | (k_last < first)))
        )
        interior = (
            (clean & (k_last <= first)) | (earlier & (k_last < first))
            | (own & (k_first == last) & (k_last == first))
        )
        return dead, interior
    dead = q_hi < k_lo
    interior = q_lo >= k_hi
    if window is not None:
        dead = dead | (q_lo - k_hi >= window)
        interior = interior & (q_hi - k_lo < window)
    return dead, interior


# Spans: the innermost grid dimension of the grid-pipelined kernels under a
# mask. A q block walks ``steps`` spans of ``block_k`` keys from the first
# key its first row sees (for dk/dv a kv block walks spans of ``block_q``
# rows from the first row that sees it). Without a window the first span
# starts at 0 and the steps are every block (the last q block sees them
# all). Under a window a span starts where the window does, rounded down to
# ``_SPAN_ALIGN`` and not to a block, so a q block of 256 rows under a
# window of 2048 walks 2304 keys and not the 3072 of three aligned blocks
# of 1024: the span's start is an element offset (``pl.Element``), which
# is all the copy needs. A step outside the spans the mask leaves live
# (past the diagonal; before the first row that sees a kv block) holds the
# nearest live span again: Pallas sees the index stand still and copies
# nothing, and the kernel, which knows where the step would have been,
# skips it.

_SPAN_ALIGN = 128


class _lax:
    """The span arithmetic's three operations as lax's flat primitives
    (numpy's names, so :func:`tile_census` runs the same code on arrays).
    jnp's are jitted helpers, a nested call each for Mosaic to lower in
    every index map of every call site at every start; every operand
    here is non-negative, so ``lax.div`` floors."""

    minimum = staticmethod(jax.lax.min)
    maximum = staticmethod(jax.lax.max)
    floor_divide = staticmethod(jax.lax.div)


def _kv_range(qi, block_q, q_offset, window, xp=_lax):
    """``(lo, hi)``: the oldest and the newest key q block ``qi`` sees."""
    q_lo = qi * block_q + q_offset
    lo = 0 * qi if window is None else xp.maximum(q_lo - (window - 1), 0)
    return lo, q_lo + block_q - 1


def _q_range(ki, block_k, q_offset, window, tq, xp=_lax):
    """``(lo, hi)``: the first and the last row that sees kv block ``ki``
    (``hi < lo``: keys older than every row's window, which none sees)."""
    lo = xp.maximum(ki * block_k - q_offset, 0)
    if window is None:
        return lo, 0 * ki + tq - 1
    return lo, xp.minimum(
        (ki + 1) * block_k - 1 + (window - 1) - q_offset, tq - 1
    )


def _spans(seen, block, steps, total, window, xp=_lax):
    """``(start, first, last)``: where the ``steps`` spans of ``block``
    begin so that they cover what a block of the other side has ``seen``
    (``(lo, hi)`` of :func:`_kv_range` or :func:`_q_range`, of ``total``
    keys or rows), and the first and the last live one of them."""
    lo, hi = seen
    align = block if window is None else _SPAN_ALIGN
    start = xp.minimum(xp.floor_divide(lo, align) * align, total - steps * block)
    return (
        start, xp.floor_divide(lo - start, block),
        xp.floor_divide(xp.maximum(hi, lo) - start, block),
    )


# Block diffusion: a block of either side sees up to TWO runs of the other
# side's blocks, which do not touch. A q block of the clean half walks the
# clean keys up to its own last row (a causal walk); one of the noised half
# walks the clean keys of the blocks before its last row's and then jumps to
# the noised keys of its own rows. A kv block of clean keys is walked by the
# clean rows from its first key on and by the noised rows of later blocks; one
# of noised keys by its own noised rows alone. The innermost grid steps count
# through the first run and then the second; the steps left over hold the last
# live block again (nothing is copied) and the kernel skips them. Blocks
# divide ``L``, so a tile lies in one half of each side, and they are whole
# blocks of ``B``, at least two.


def _bd_seen(i, own, bd, side, xp=_lax):
    """``(a_lo, a_hi, b_lo, b_hi, b_on)``: the two runs of keys (``side``
    ``"kv"``) that q block ``i`` of ``own`` rows sees, or of rows (``"q"``)
    that see kv block ``i`` of ``own`` keys, first and last element of each;
    ``b_on`` is 1 where there is a second run and 0 where not."""
    length, block = bd
    noised = xp.floor_divide(i * own, length)      # 0: the clean half, 1
    lo = i * own - noised * length                 # from its half's start
    if side == "kv":
        return (
            0 * i, lo + own - 1 - noised * block,
            length + lo, length + lo + own - 1, noised,
        )
    return (
        i * own, length + noised * (lo + own) - 1,
        length + lo + block, 0 * i + 2 * length - 1, 1 - noised,
    )


def _bd_runs(seen, other, xp=_lax):
    """``(a_first, a_count, b_first, b_count)``: :func:`_bd_seen`'s two runs
    in blocks of ``other``."""
    a_lo, a_hi, b_lo, b_hi, b_on = seen
    a_first, b_first = xp.floor_divide(a_lo, other), xp.floor_divide(b_lo, other)
    return (
        a_first, xp.floor_divide(a_hi, other) - a_first + 1,
        b_first, b_on * (xp.floor_divide(b_hi, other) - b_first + 1),
    )


def _bd_block(runs, s, xp=_lax):
    """``(block, live)``: the block of the other side that step ``s`` holds
    (the last live one again past the runs' end) and whether the step is one
    of the runs' at all."""
    a_first, a_count, b_first, b_count = runs
    t = xp.minimum(s, a_count + b_count - 1)
    jumped = xp.minimum(xp.maximum(t - a_count + 1, 0), 1)
    return (
        a_first + t + jumped * (b_first - a_first - a_count),
        s < a_count + b_count,
    )


def _bd_steps(bd, block_q, block_k):
    """``(kv steps a q block, q steps a kv block)`` under block diffusion:
    the longest walk of either side."""
    import numpy as np

    length = bd[0]

    def most(own, other, side):
        blocks = np.arange(2 * length // own)
        runs = _bd_runs(_bd_seen(blocks, own, bd, side, np), other, np)
        return int((runs[1] + runs[3]).max())

    return most(block_q, block_k, "kv"), most(block_k, block_q, "q")


def _span_need(seen):
    """The most keys (rows) any block walks: from where its first span
    starts to the last one it sees (:func:`_spans` under a window)."""
    lo, hi = seen
    return int((hi.clip(lo) + 1 - lo // _SPAN_ALIGN * _SPAN_ALIGN).max())


def _kv_need(window, block_q, tq, tk):
    import numpy as np

    blocks = np.arange(tq // block_q)
    return _span_need(_kv_range(blocks, block_q, tk - tq, window, np))


def _q_need(window, block_k, tq, tk):
    import numpy as np

    blocks = np.arange(tk // block_k)
    return _span_need(_q_range(blocks, block_k, tk - tq, window, tq, np))


def _span_steps(window, block_q, block_k, tq, tk):
    """``(kv steps a q block, q steps a kv block)``: the most spans of the
    other side any block needs, from the static shapes. (Each is exact
    where its own side's block fits: :func:`_spans_fit`.)"""
    if window is None:
        return tk // block_k, tq // block_q
    return (
        min(-(-_kv_need(window, block_q, tq, tk) // block_k), tk // block_k),
        min(-(-_q_need(window, block_k, tq, tk) // block_q), tq // block_q),
    )


def _flash2_maps(causal, window, block_q, block_k, tq, tk, group, bd=None):
    """``((kv steps, kv index map), (q steps, q index map))`` of the
    grid-pipelined kernels: where the span of the other side that the
    innermost grid step ``s`` of a block holds begins, as a block index
    without a window and as an element offset under one. Under block
    diffusion (``bd``) a block index that jumps from the first run to the
    second (:func:`_bd_block`)."""
    from jax.experimental import pallas as pl

    num_q, num_k, off = tq // block_q, tk // block_k, tk - tq
    if not causal:
        return (
            (num_k, lambda i, qi, s: (i // group, s, 0)),
            (num_q, lambda i, ki, s: (i, s, 0)),
        )
    if bd is not None:
        kv_steps, q_steps = _bd_steps(bd, block_q, block_k)

        def jumping(own, other, side, head):
            def index_map(i, block, s):
                runs = _bd_runs(_bd_seen(block, own, bd, side), other)
                return (head(i), _bd_block(runs, s)[0], 0)
            return index_map

        return (
            (kv_steps, jumping(block_q, block_k, "kv", lambda i: i // group)),
            (q_steps, jumping(block_k, block_q, "q", lambda i: i)),
        )
    kv_steps, q_steps = _span_steps(window, block_q, block_k, tq, tk)

    def held(seen, s, block, steps, total):
        """Where step ``s`` holds its span: a block index without a window,
        an element under one (with what divides it, which Mosaic has to be
        told: the copy starts on a whole tile)."""
        start, first, last = _spans(seen, block, steps, total, window)
        begin = start + _lax.minimum(_lax.maximum(s, first), last) * block
        if window is None:
            return _lax.floor_divide(begin, block)
        slack = total - steps * block
        return pl.multiple_of(begin, math.gcd(_SPAN_ALIGN, block, slack))

    def kv_map(i, qi, s):
        seen = _kv_range(qi, block_q, off, window)
        return (i // group, held(seen, s, block_k, kv_steps, tk), 0)

    def q_map(i, ki, s):
        seen = _q_range(ki, block_k, off, window, tq)
        return (i, held(seen, s, block_q, q_steps, tq), 0)

    return (kv_steps, kv_map), (q_steps, q_map)


def _span_spec(block, width, index_map, window):
    """BlockSpec of one span of ``block`` rows (or keys) of ``width``.
    Under a window ``index_map`` gives the element a span starts at, and
    Mosaic takes element offsets in every dimension or in none."""
    from jax.experimental import pallas as pl

    if window is None:
        return pl.BlockSpec((1, block, width), index_map)
    return pl.BlockSpec(
        (pl.Element(1), pl.Element(block), pl.Element(width)), index_map
    )


def tile_census(tq, tk, block_q, block_k, causal, window=None, side="kv",
                bd=None):
    """Shares of the ``tq x tk`` score rectangle, by area, that a
    grid-pipelined kernel with these blocks finds ``dead`` (never walked,
    or stepped over: neither copied nor computed), ``interior`` and
    ``edge`` (both computed under the mask; the keys an edge tile masks are
    the waste). ``side``: ``"kv"`` for the forward and dq, which walk spans
    of keys a q block, ``"q"`` for dk/dv."""
    import numpy as np

    if not causal:
        return {"dead": 0.0, "interior": 1.0, "edge": 0.0}
    if bd is not None:
        own, other = (block_q, block_k) if side == "kv" else (block_k, block_q)
        steps = _bd_steps(bd, block_q, block_k)[side != "kv"]
        block = np.arange(2 * bd[0] // own)[:, None]
        runs = _bd_runs(_bd_seen(block, own, bd, side, np), other, np)
        held, live = _bd_block(runs, np.arange(steps)[None, :], np)
        q_lo, k_lo = block * own, held * other
        if side != "kv":
            q_lo, k_lo = k_lo, q_lo
        dead, interior = _tile_class(q_lo, block_q, k_lo, block_k, None, bd)
        tile = block_q * block_k / (tq * tk)
        interior = (live & interior).sum() * tile
        edge = (live & ~dead).sum() * tile - interior
        return {
            "dead": float(1.0 - interior - edge), "interior": float(interior),
            "edge": float(edge),
        }
    off = tk - tq
    kv_steps, q_steps = _span_steps(window, block_q, block_k, tq, tk)
    if side == "kv":
        block = np.arange(tq // block_q)[:, None]
        seen = _kv_range(block, block_q, off, window, np)
        start = _spans(seen, block_k, kv_steps, tk, window, np)[0]
        q_lo = block * block_q + off
        k_lo = start + np.arange(kv_steps)[None, :] * block_k
    else:
        block = np.arange(tk // block_k)[:, None]
        seen = _q_range(block, block_k, off, window, tq, np)
        start = _spans(seen, block_q, q_steps, tq, window, np)[0]
        q_lo = start + np.arange(q_steps)[None, :] * block_q + off
        k_lo = block * block_k
    dead, interior = _tile_class(q_lo, block_q, k_lo, block_k, window)
    tile = block_q * block_k / (tq * tk)
    interior, edge = interior.sum() * tile, (~dead & ~interior).sum() * tile
    return {
        "dead": float(1.0 - interior - edge), "interior": float(interior),
        "edge": float(edge),
    }


def _note_tiles(kernel, tq, tk, block_q, block_k, causal, window, side,
                bd=None, **more):
    """One ``attn_tiles`` instant in the span ring for each shape a
    grid-pipelined kernel is traced at in a stage (``note_once``): what the
    mask makes of its tiles (and what ``more`` the kernel has to say of
    itself). Under block diffusion also the mask's kind, its ``(L, B)``, the
    share of the rectangle's pairs that are ``visible`` and the ``path``:
    ``kernel`` from a kernel's own trace, ``plain`` (with ``why``) from
    :func:`_note_planned_tiles`."""
    shares = tile_census(tq, tk, block_q, block_k, causal, window, side, bd)
    live = shares["interior"] + shares["edge"]
    if bd is not None:
        more = {
            "path": "kernel", **more, "mask": "block_diffusion", "length": bd[0],
            "block": bd[1], "visible": bd[0] * (bd[0] + bd[1]) / (tq * tk),
        }
    obs_trace.get_tracer().note_once(
        "attn_tiles", kernel=kernel, tq=tq, tk=tk, block_q=block_q,
        block_k=block_k, window=window,
        masked_share=shares["edge"] / live if live else 0.0, **shares, **more,
    )


# The grid-pipelined forward's online-softmax state. ``m`` and ``l`` are
# kept a lane tile wide (``_state_lanes``): ``m`` the row maximum in every
# lane, ``l`` the sum of the keys that fell on each lane, summed across
# lanes once, at the end. A row sum across lanes is a pass through the
# cross-lane unit an update, and a ``[block_q, 1]`` array a relayout each
# time it meets a tile: that forward was bound by its updates, about as
# much an update as 700 keys whatever its width, not by its keys (PERF.md,
# PR 32).


def _state_lanes(block_k: int) -> int:
    return math.gcd(128, block_k)


def _softmax_update(s, m, l, acc, v):
    """``(m, l, acc)`` after the masked scores ``s`` [rows, keys] of one
    more tile over its values ``v`` [keys, d]."""
    from jax.experimental.pallas import tpu as pltpu

    lanes, d = m.shape[1], acc.shape[1]
    tiles = s.shape[1] // lanes
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - pltpu.repeat(m_new, tiles, axis=1))
    part = p[:, :lanes]
    for t in range(1, tiles):
        part = part + p[:, t * lanes:(t + 1) * lanes]
    l = l * corr + part
    if d != lanes:
        corr = corr[:, :d] if d < lanes else corr[:, :1]
    return m_new, l, acc * corr + _dot_nn(p.astype(v.dtype), v)


def _flash2_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                   acc_scr, *, causal: bool, scale: float, q_block: int,
                   block_k: int, num_k: int, q_offset: int,
                   window: int | None = None, seq_k: int = 0, bd=None):
    """Grid-pipelined forward: the KV loop lives in the GRID (innermost
    dimension), so Pallas double-buffers each KV block's HBM→VMEM copy
    behind the previous block's compute, and the VMEM footprint does not
    scale with the sequence. Online-softmax state (m, l, acc) carries across
    the innermost grid steps in VMEM scratch, initialized at step 0 and
    finalized into (o, lse) at step num_k-1. Under a mask the ``num_k``
    steps are the q block's spans of ``seq_k`` keys (:func:`_kv_range`, :func:`_spans`)."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    step = pl.program_id(2)
    q_lo = qi * q_block + q_offset
    k_lo = step * block_k
    live = True
    if bd is not None:
        k_lo, live = _bd_tile(qi, step, q_block, block_k, bd, "kv")
    elif causal:
        seen = _kv_range(qi, q_block, q_offset, window)
        k_lo += _spans(seen, block_k, num_k, seq_k, window)[0]
        # a dead tile (past the diagonal, before the window) skips the
        # FLOPs; its step held a live span again, so nothing was copied
        live = ~_tile_class(q_lo, q_block, k_lo, block_k, window)[0]

    @pl.when(step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(live)
    def _update():
        v = v_ref[0]
        s = _dot_nt(q_ref[0], k_ref[0]) * scale
        if causal:
            s = _causal_mask(s, q_lo, k_lo, window, bd=bd)
        m_scr[:], l_scr[:], acc_scr[:] = _softmax_update(
            s, m_scr[:], l_scr[:], acc_scr[:], v
        )

    @pl.when(step == num_k - 1)
    def _finalize():
        l = jnp.maximum(jnp.sum(l_scr[:], axis=-1, keepdims=True), 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        # per-row logsumexp of the SCALED scores: the backward's residual.
        # lse rides pallas as [B*H, Tq, 1] — a (1, block_q, 1) block keeps the
        # sublane dim 8-aligned, which the TPU lowering requires (a plain
        # (1, block_q) block over [B*H, Tq] has sublane 1 and is rejected)
        lse_ref[0] = m_scr[:, :1] + jnp.log(l)


def _bd_tile(i, step, own, other, bd, side):
    """``(first element of the other side's block that step ``step`` of block
    ``i`` holds, whether the step is live)`` in a block-diffusion kernel."""
    held, live = _bd_block(_bd_runs(_bd_seen(i, own, bd, side), other), step)
    return held * other, live


def _grid_pipeline_kwargs() -> dict:
    """pallas_call kwargs shared by every flash2-family kernel: batch and
    the outer block dimension are independent ('parallel'); only the
    innermost accumulation walk is sequential ('arbitrary')."""
    from jax.experimental.pallas import tpu as pltpu

    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    }


def _bwd_delta(g: jax.Array, o: jax.Array, b: int, h: int, tq: int, d: int):
    """delta_i = sum_d dO_i O_i, in kernel layout — the softmax-jacobian
    row correction every backward kernel consumes."""
    return jnp.sum(
        g.reshape(b * h, tq, d).astype(jnp.float32)
        * o.reshape(b * h, tq, d).astype(jnp.float32),
        axis=-1,
    )


def _flash2_forward(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool, scale: float,
    block_q: int, block_k: int, interpret: bool, window: int | None = None,
    bd=None,
):
    """(o, lse) via the grid-pipelined kernel; ``lse is None`` marks the
    ragged-shape fallback to the jnp reference (the backward then uses the
    reference too)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    tk, d_v = k.shape[2], v.shape[3]  # values may be narrower than the keys
    block_q, block_k = _fit_blocks(block_q, block_k, tq, tk, window, "kv", bd)
    if not _spans_fit(block_q, block_k, tq, tk, window, "kv", bd) or (
        causal and tq > tk
    ):
        # ragged blocks, or end-aligned causal with MORE queries than keys:
        # the latter leaves early q rows with zero visible keys, where the
        # reference degenerates to a uniform softmax — not worth defeating
        # the kernel's masked-block skipping to reproduce
        return attention_reference(
            q, k, v, causal=causal, scale=scale, window=window,
            block_diffusion=bd,
        ), None

    g = _gqa_group(q, k)
    qf = q.reshape(b * h, tq, d)
    kf = k.reshape(b * (h // g), tk, d)
    vf = v.reshape(b * (h // g), tk, d_v)
    (num_k, kv_map), _ = _flash2_maps(
        causal, window, block_q, block_k, tq, tk, g, bd
    )
    _note_tiles("flash2_fwd", tq, tk, block_q, block_k, causal, window, "kv", bd)
    kv_spec = _span_spec(block_k, d, kv_map, window)
    v_spec = kv_spec if d_v == d else _span_spec(block_k, d_v, kv_map, window)
    grid = (b * h, tq // block_q, num_k)
    kwargs = _grid_pipeline_kwargs()
    kernel = pl.pallas_call(
        functools.partial(
            _flash2_kernel,
            causal=causal,
            scale=scale,
            q_block=block_q,
            block_k=block_k,
            num_k=num_k,
            q_offset=tk - tq,
            window=window,
            seq_k=tk,
            bd=bd,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq, d_v), q.dtype),
            jax.ShapeDtypeStruct((b * h, tq, 1), jnp.float32),
        ],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, qi, j: (i, qi, 0)),
            kv_spec,
            v_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d_v), lambda i, qi, j: (i, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, qi, j: (i, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _state_lanes(block_k)), jnp.float32),
            pltpu.VMEM((block_q, _state_lanes(block_k)), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
    )
    with obs_trace.span("kernel_trace", kernel="flash2_fwd"):
        out, lse = kernel(qf, kf, vf)
    return out.reshape(b, h, tq, d_v), lse[..., 0]


def _flash2_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dq_ref, dq_scr, *, causal: bool, scale: float,
                          q_block: int, block_k: int, num_k: int,
                          q_offset: int, window: int | None = None,
                          seq_k: int = 0, bd=None):
    """Grid-pipelined dq: KV blocks ride the innermost grid dimension
    (double-buffered DMA), dq accumulates in VMEM scratch across steps —
    the backward twin of :func:`_flash2_kernel`'s structure, the spans
    under a mask included."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    step = pl.program_id(2)
    q_lo = qi * q_block + q_offset
    k_lo = step * block_k
    live = True
    if bd is not None:
        k_lo, live = _bd_tile(qi, step, q_block, block_k, bd, "kv")
    elif causal:
        seen = _kv_range(qi, q_block, q_offset, window)
        k_lo += _spans(seen, block_k, num_k, seq_k, window)[0]
        live = ~_tile_class(q_lo, q_block, k_lo, block_k, window)[0]

    @pl.when(step == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(live)
    def _update():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                                # [bq, 1]
        delta = delta_ref[0]
        s = _dot_nt(q, k) * scale
        if causal:
            s = _causal_mask(s, q_lo, k_lo, window, bd=bd)
        p = jnp.exp(s - lse)
        dp = _dot_nt(do, v)
        ds = p * (dp - delta)
        dq_scr[:] = dq_scr[:] + _dot_nn(ds.astype(k.dtype), k)

    @pl.when(step == num_k - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _flash2_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                           scale: float, block_q: int, k_block: int,
                           num_q: int, q_offset: int,
                           window: int | None = None, seq_q: int = 0,
                           bd=None):
    """Grid-pipelined dk/dv: Q/dO/lse/delta blocks ride the innermost
    grid dimension, dk/dv accumulate in scratch per KV block. Under a
    mask the ``num_q`` steps are the kv block's spans of the ``seq_q``
    rows (:func:`_q_range`, :func:`_spans`): from the first row that sees it."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    step = pl.program_id(2)
    k_lo = ki * k_block
    q_lo = step * block_q + q_offset
    live = True
    if bd is not None:
        q_lo, live = _bd_tile(ki, step, k_block, block_q, bd, "q")
    elif causal:
        seen = _q_range(ki, k_block, q_offset, window, seq_q)
        q_lo += _spans(seen, block_q, num_q, seq_q, window)[0]
        # q rows entirely before this kv block's first column, or past the
        # window of its last, are dead
        live = ~_tile_class(q_lo, block_q, k_lo, k_block, window)[0]

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(live)
    def _update():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                                # [bq, 1]
        delta = delta_ref[0]
        s = _dot_nt(q, k) * scale
        if causal:
            s = _causal_mask(s, q_lo, k_lo, window, bd=bd)
        p = jnp.exp(s - lse)
        dv_scr[:] = dv_scr[:] + _dot_tn(p.astype(do.dtype), do)
        dp = _dot_nt(do, v)
        ds = p * (dp - delta)
        dk_scr[:] = dk_scr[:] + _dot_tn(ds.astype(q.dtype), q)

    @pl.when(step == num_q - 1)
    def _finalize():
        # scale was applied to s, not pre-folded into q, so dk takes its
        # one factor of ``scale`` here
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash2_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *,
                       causal: bool, scale: float, block_q: int, k_block: int,
                       num_q: int, num_k: int, q_offset: int, row_align: int,
                       window: int | None = None, seq_q: int = 0, bd=None):
    """The whole grid-pipelined backward in one walk, dk/dv's: a kv block's
    spans of rows ride the innermost grid dimension, and a live tile's
    ``s``, ``p``, ``dp`` and ``ds`` are computed once for all three
    gradients (five matmuls and one ``exp`` a tile where the two kernels
    above spend seven and two, and q, k, v, dO, lse, delta are read once).
    The tile is **transposed**, ``[keys, rows]``: ``dv += pT dO`` and ``dk +=
    dsT q`` are then plain products (only dq's contracts over the first
    axis), and ``lse`` / ``delta`` come as ``[1, rows]`` along the lanes, a
    sublane broadcast in the tile and an eighth of a ``[rows, 1]`` block's
    bytes in HBM. dk/dv accumulate in scratch a kv block as above; dq
    accumulates in a float32 scratch of the head's **whole** ``[seq_q, d]``,
    each tile into its rows, zeroed at the head's first step and written at
    its last: the kv blocks of a head therefore run in order
    (``"arbitrary"``)."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    step = pl.program_id(2)
    k_lo = ki * k_block
    row = step * block_q
    live = True
    if bd is not None:
        row, live = _bd_tile(ki, step, k_block, block_q, bd, "q")
    elif causal:
        seen = _q_range(ki, k_block, q_offset, window, seq_q)
        row += _spans(seen, block_q, num_q, seq_q, window)[0]
        live = ~_tile_class(row + q_offset, block_q, k_lo, k_block, window)[0]

    @pl.when((ki == 0) & (step == 0))
    def _init_head():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(live)
    def _update():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                                # [1, bq]
        delta = delta_ref[0]
        s = _dot_nt(k, q) * scale                       # [bk, bq]
        if causal:
            s = _causal_mask(
                s, row + q_offset, k_lo, window, keys_first=True, bd=bd
            )
        p = jnp.exp(s - lse)
        dv_scr[:] = dv_scr[:] + _dot_nn(p.astype(do.dtype), do)
        dp = _dot_nt(v, do)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_scr[:] = dk_scr[:] + _dot_nn(ds, q)
        rows = pl.ds(pl.multiple_of(row, row_align), block_q)
        dq_scr[rows, :] = dq_scr[rows, :] + _dot_tn(ds, k)

    @pl.when(step == num_q - 1)
    def _finalize():
        # scale as in the two kernels above: once, on the way out
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when((ki == num_k - 1) & (step == num_q - 1))
    def _finalize_head():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


# The fused backward holds a head's dq on the chip. What it asks of VMEM,
# from the shapes: the float32 accumulator and the two buffers of its
# output block, dk/dv's scratch, two buffers of every other block (a
# ``[1, rows]`` block is eight sublanes deep there), and a tile's float32
# intermediates (s, p, dp, ds, their casts and transposes: eight tiles'
# worth has taken every shape the rehearsals tried). A call takes the fused
# kernel where that is at most half the core's VMEM, and the kernel's limit
# is that figure (Mosaic's default, 16 MiB, holds no 8192 x 128 head).
_VMEM_V5E = 128 << 20


def _vmem_capacity() -> int:
    """Bytes of VMEM a TensorCore has: the chip's own where there is one,
    the v5e's (the chip the blocks were swept on) in interpret mode and in
    a compile for a described chip, which see the CPU (also where the
    caller steers ``jax.default_backend`` to ``tpu`` for that compile, as
    ``benchmark/tools/compile_for_v5e.py`` does: no chip answers then)."""
    if jax.default_backend() != "tpu":
        return _VMEM_V5E
    from jax.experimental.pallas import tpu as pltpu

    try:
        return pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:  # "Unsupported TPU device kind: cpu"
        return _VMEM_V5E


def _fused_bwd_vmem(tq, d, block_q, block_k, itemsize):
    """``(bytes of the dq accumulator, bytes of VMEM the fused backward
    needs)`` at these shapes."""
    acc = tq * d * 4
    blocks = 2 * (block_q + 2 * block_k) * d * itemsize + 2 * 8 * block_q * 4
    need = (
        acc + 2 * tq * d * itemsize      # dq: the accumulator, its output
        + 2 * block_k * d * 4            # dk/dv scratch
        + 2 * blocks                     # every other block, twice
        + 8 * block_q * block_k * 4      # a tile's intermediates
    )
    return acc, need


def _flash2_backward(
    q, k, v, o, lse, g, causal: bool, scale: float,
    block_q: int, block_k: int, interpret: bool, window: int | None = None,
    dkv_blocks: tuple[int, int] | None = None, bd=None,
):
    """(dq, dk, dv) via the grid-pipelined backward kernels;
    ``lse`` in kernel layout [B*H, Tq]."""
    b, h, tq, _ = q.shape
    delta = _bwd_delta(g, o, b, h, tq, v.shape[3])
    return _flash2_backward_kernels(
        q, k, v, g, lse, delta, causal, scale, block_q, block_k, interpret,
        window, dkv_blocks, bd,
    )


def _flash2_backward_kernels(
    q, k, v, g, lse, delta, causal: bool, scale: float,
    block_q: int, block_k: int, interpret: bool, window: int | None = None,
    dkv_blocks: tuple[int, int] | None = None, bd=None,
):
    """The grid-pipelined backward; ``lse``/``delta`` are [B*H, Tq]
    (external residuals welcome — ring attention's per-rotation block
    grads come here). One pallas call,
    :func:`_flash2_bwd_kernel`, where a head's dq accumulator fits the
    chip's VMEM (:func:`_fused_bwd_vmem`) and a span of rows is whole lane
    tiles; the two older ones, dq and then dk/dv, where not. ``dkv_blocks``: those of the kernels that
    walk rows a kv block (the fused one, and dk/dv), where they differ
    from dq's ``block_q`` and ``block_k``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    tk, d_v = k.shape[2], v.shape[3]  # v, dO and dv at the values' width
    grp = _gqa_group(q, k)
    h_kv = h // grp
    kv_q, kv_k = _fit_blocks(
        *(dkv_blocks or (block_q, block_k)), tq, tk, window, "q", bd
    )

    qf = q.reshape(b * h, tq, d)
    kf = k.reshape(b * h_kv, tk, d)
    vf = v.reshape(b * h_kv, tk, d_v)
    gf = g.reshape(b * h, tq, d_v)
    common = dict(
        causal=causal, scale=scale, q_offset=tk - tq, window=window, bd=bd
    )
    _, (q_steps, q_map) = _flash2_maps(
        causal, window, kv_q, kv_k, tq, tk, grp, bd
    )
    # the kernels that walk rows a kv block: the rows in spans, k and v a
    # grouped block (programs in one GQA group share it, so no H-wide repeat
    # ever materializes in HBM), dk/dv at FULL q-head width (each program
    # owns one q head's contribution), folded to the grouped width outside
    rows_spec = _span_spec(kv_q, d, q_map, window)
    kv_block = pl.BlockSpec((1, kv_k, d), lambda i, ki, j, g=grp: (i // g, ki, 0))
    if d_v == d:
        do_spec, v_block = rows_spec, kv_block
    else:
        do_spec = _span_spec(kv_q, d_v, q_map, window)
        v_block = pl.BlockSpec((1, kv_k, d_v), lambda i, ki, j, g=grp: (i // g, ki, 0))
    dkv_shape = [
        jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
        jax.ShapeDtypeStruct((b * h, tk, d_v), v.dtype),
    ]
    dkv_specs = [
        pl.BlockSpec((1, kv_k, width), lambda i, ki, j: (i, ki, 0))
        for width in (d, d_v)
    ]
    dkv_scratch = [pltpu.VMEM((kv_k, width), jnp.float32) for width in (d, d_v)]

    acc, need = _fused_bwd_vmem(tq, d, kv_q, kv_k, q.dtype.itemsize)
    # lse and delta ride the lanes there: whole lane tiles a span (Mosaic's
    # rule, like every tiling rule not the interpreter's)
    lanes_fit = interpret or kv_q == tq or (kv_q % 128 == 0 and tq % 128 == 0)
    if need <= _vmem_capacity() // 2 and lanes_fit:
        _note_tiles(
            "flash2_bwd", tq, tk, kv_q, kv_k, causal, window, "q", bd,
            acc_bytes=acc,
        )
        # lse and delta along the lanes, a span of them a step
        lanes = _span_spec(
            1, kv_q, lambda i, ki, j: (i, 0, q_map(i, ki, j)[1]), window
        )
        # what divides the row a span starts at (as in _flash2_maps)
        row_align = kv_q if window is None else math.gcd(
            _SPAN_ALIGN, kv_q, tq - q_steps * kv_q
        )
        kernel = pl.pallas_call(
            functools.partial(
                _flash2_bwd_kernel,
                block_q=kv_q, k_block=kv_k, num_q=q_steps, num_k=tk // kv_k,
                seq_q=tq, row_align=row_align, **common,
            ),
            out_shape=[
                jax.ShapeDtypeStruct((b * h, tq, d), q.dtype), *dkv_shape,
            ],
            grid=(b * h, tk // kv_k, q_steps),
            in_specs=[rows_spec, kv_block, v_block, do_spec, lanes, lanes],
            out_specs=[
                # a head's whole dq: the block stands still over the head's
                # steps and is written out when the head changes
                pl.BlockSpec((1, tq, d), lambda i, ki, j: (i, 0, 0)),
                *dkv_specs,
            ],
            scratch_shapes=[pltpu.VMEM((tq, d), jnp.float32), *dkv_scratch],
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=need,
            ),
        )
        with obs_trace.span("kernel_trace", kernel="flash2_bwd"):
            dq, dk, dv = kernel(
                qf, kf, vf, gf, lse[:, None, :], delta[:, None, :]
            )
    else:
        block_q, block_k = _fit_blocks(
            block_q, block_k, tq, tk, window, "kv", bd
        )
        # pallas layout: trailing singleton keeps the block sublane 8-aligned
        lse3 = lse[..., None]
        delta3 = delta[..., None]
        kwargs = _grid_pipeline_kwargs()
        (kv_steps, kv_map), _ = _flash2_maps(
            causal, window, block_q, block_k, tq, tk, grp, bd
        )
        _note_tiles("flash2_dq", tq, tk, block_q, block_k, causal, window, "kv", bd)
        _note_tiles("flash2_dkv", tq, tk, kv_q, kv_k, causal, window, "q", bd)
        kv_spec = _span_spec(block_k, d, kv_map, window)
        q_spec = pl.BlockSpec((1, block_q, d), lambda i, qi, j: (i, qi, 0))
        if d_v == d:
            v_spec, dq_do_spec = kv_spec, q_spec
        else:
            v_spec = _span_spec(block_k, d_v, kv_map, window)
            dq_do_spec = pl.BlockSpec((1, block_q, d_v), lambda i, qi, j: (i, qi, 0))
        row_spec = pl.BlockSpec((1, block_q, 1), lambda i, qi, j: (i, qi, 0))
        kernel = pl.pallas_call(
            functools.partial(
                _flash2_bwd_dq_kernel,
                q_block=block_q, block_k=block_k, num_k=kv_steps, seq_k=tk,
                **common,
            ),
            out_shape=jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
            grid=(b * h, tq // block_q, kv_steps),
            in_specs=[q_spec, kv_spec, v_spec, dq_do_spec, row_spec, row_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            interpret=interpret,
            **kwargs,
        )
        with obs_trace.span("kernel_trace", kernel="flash2_dq"):
            dq = kernel(qf, kf, vf, gf, lse3, delta3)

        row_spec = _span_spec(kv_q, 1, q_map, window)
        kernel = pl.pallas_call(
            functools.partial(
                _flash2_bwd_dkv_kernel,
                block_q=kv_q, k_block=kv_k, num_q=q_steps, seq_q=tq, **common,
            ),
            out_shape=dkv_shape,
            grid=(b * h, tk // kv_k, q_steps),
            in_specs=[
                rows_spec, kv_block, v_block, do_spec, row_spec, row_spec,
            ],
            out_specs=dkv_specs,
            scratch_shapes=dkv_scratch,
            interpret=interpret,
            **kwargs,
        )
        with obs_trace.span("kernel_trace", kernel="flash2_dkv"):
            dk, dv = kernel(qf, kf, vf, gf, lse3, delta3)

    dk, dv = _fold_dkv(
        dk.reshape(b, h, tk, d), dv.reshape(b, h, tk, d_v),
        b, h_kv, grp, tk, d,
    )
    return dq.reshape(b, h, tq, d), dk, dv


# flash2 (grid-pipelined) blocks, full-causal. ``_FWD``: PR 48's sweep at the
# seven shapes the benchmark's cells call it at (T = 4096 to 16384, heads of
# 64, 128 and 192 / 128, GQA 1:1 to 8:1, and the masked copy in
# ops/sparse_attention.py; bench_results/README.md, "the forward's blocks"):
# 1024 rows x 1024 keys read fastest at every one, the kernel alone and in
# one program with its backward, 16-37% under the 256 x 1024 that the first
# sweep (seq 8192, bq up to 512) had left. A row block's keys stream through
# the matrix unit once a block, so fewer, taller blocks copy less and pay
# fewer online-softmax updates; 2048 keys a step lose to 1024 from 512 rows
# on. The tile fits Mosaic's default VMEM limit at float32 and at a head of
# 256 (compiled for a described v5e). ``_BWD``: the fused backward's (PR 34's
# sweep, heads of 128 and 64, T = 8192 and 4096: same file), and dk/dv's
# where a head's dq does not fit the chip; ``_DQ``: dq's there.
_FLASH2_BLOCKS_FWD = (1024, 1024)
_FLASH2_BLOCKS_BWD = (1024, 1024)
_FLASH2_BLOCKS_DQ = (512, 1024)


def _spans_fit(block_q, block_k, tq, tk, window, side, bd=None):
    """Whether a grid-pipelined kernel can tile the shapes with these
    blocks. Each block has to divide its side, except under a window the
    block of the side the kernel walks in spans (``side``: ``"kv"`` keys,
    ``"q"`` rows): spans start at elements, so whole sublanes will do, as
    long as the spans a block needs fit into the side. Under block diffusion
    each block divides a half (``L``) and is whole blocks of ``B``, two at
    least (a tile then lies in one half of each side, and a block's first run
    is never empty)."""
    if bd is not None:
        return all(
            bd[0] % block == 0 and block % bd[1] == 0 and block >= 2 * bd[1]
            for block in (block_q, block_k)
        )
    q_divides, k_divides = tq % block_q == 0, tk % block_k == 0
    if window is None or (q_divides and k_divides):
        return q_divides and k_divides
    if side == "kv" and q_divides:
        block, need, total = block_k, _kv_need(window, block_q, tq, tk), tk
    elif side == "q" and k_divides:
        block, need, total = block_q, _q_need(window, block_k, tq, tk), tq
    else:
        return False
    return block % 8 == 0 and -(-need // block) * block <= total


def _fit_blocks(block_q, block_k, tq, tk, window, side, bd=None):
    """``(block_q, block_k)`` fitted to the shapes: as given where
    :func:`_spans_fit` takes them, else through :func:`_fit_block` (under
    block diffusion to a half, which a block may not straddle)."""
    if bd is not None:
        return _fit_block(block_q, bd[0]), _fit_block(block_k, bd[0])
    bq, bk = _fit_block(block_q, tq), _fit_block(block_k, tk)
    if side == "kv" and _spans_fit(bq, block_k, tq, tk, window, side):
        return bq, block_k
    if side == "q" and _spans_fit(block_q, bk, tq, tk, window, side):
        return block_q, bk
    return bq, bk


# A windowed call's blocks, from the window and the shapes (v5e sweep at
# GQA 32:4 x 128, T = 8192, window 2048: bench_results/README.md). The
# side a kernel does not walk takes the sweep's block; the walked side is
# cut into the fewest equal spans no longer than the sweep's that cover
# what such a block needs (its own rows or keys and the window), in whole
# lane tiles: the forward there takes one update of 2560 keys a 512 rows,
# dq one of 2304 keys a 256 rows, the backward two of 1280 rows a 512 keys.
# An online-softmax update costs the forward about as much as 700 keys do,
# whatever its width, so the forward wants few; the backward reads faster
# in two spans than in one.
_WINDOW_BLOCKS = {"fwd": (512, 2560), "dq": (256, 2560), "bwd": (1280, 512)}


def _flash2_blocks(kind, tq, tk, window, given=None, bd=None):
    """``(block_q, block_k)`` for the grid-pipelined kernel ``kind``
    (``"fwd"``; ``"bwd"``, the kernels that walk rows a kv block: the fused
    backward, and dk/dv where a head's dq does not fit the chip; ``"dq"``,
    dq's there), fitted to the shapes: what the caller ``given`` (a pair,
    either of it ``None``) wins, then a windowed call's blocks from the
    window and the shapes, else the full-causal sweep's (a block-diffusion
    call's too, fitted to a half: its clean half is a causal walk)."""
    side = "q" if kind == "bwd" else "kv"
    bq, bk = {
        "fwd": _FLASH2_BLOCKS_FWD, "dq": _FLASH2_BLOCKS_DQ,
        "bwd": _FLASH2_BLOCKS_BWD,
    }[kind]
    if window is not None:
        wq, wk = _WINDOW_BLOCKS[kind]
        if kind == "bwd":
            wk = _fit_block(wk, tk)
            need, longest = _q_need(window, wk, tq, tk), wq
        else:
            wq = _fit_block(wq, tq)
            need, longest = _kv_need(window, wq, tq, tk), wk
        span = -(-need // -(-need // longest))   # fewest equal steps
        span = -(-span // 128) * 128             # in whole lane tiles
        wq, wk = (span, wk) if kind == "bwd" else (wq, span)
        if _spans_fit(wq, wk, tq, tk, window, side):
            bq, bk = wq, wk
    given = given or (None, None)
    return _fit_blocks(given[0] or bq, given[1] or bk, tq, tk, window, side, bd)


def _fit_block(block: int, t: int) -> int:
    # largest divisor of t that is <= block and sublane-aligned, so a
    # large default block never disqualifies shapes a smaller one
    # handled (e.g. tk=768 with block_k=512 -> 256, not a fallback)
    block = min(block, t)
    while block > 8 and t % block:
        block //= 2
    return block


def _block_grads_reference(q, k, v, g, lse, delta, causal, scale):
    """jnp twin of the backward kernels for shapes they can't tile:
    block gradients given EXTERNAL (global) lse and delta."""
    b, h_kv, tk, d = k.shape
    grp = _gqa_group(q, k)
    k, v = _broadcast_kv(q, k, v)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        s = _dense_causal_mask(s)
    p = jnp.exp(s - lse[..., None])
    g32 = g.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, g32)
    dp = jnp.einsum(
        "bhqd,bhkd->bhqk", g32, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta[..., None])
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(jnp.float32)) * scale
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32)) * scale
    dk, dv = _fold_dkv(dk, dv, b, h_kv, grp, tk, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def flash_block_grads(
    q, k, v, g, lse, delta,
    causal: bool = False,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    block_diffusion: tuple[int, int] | None = None,
):
    """(dq, dk, dv) for one attention block given external residuals:
    per-row logsumexp ``lse`` and row correction ``delta`` [B, H, Tq],
    both computed over the GLOBAL softmax. This is the building block for
    distributed backward passes (ring attention accumulates these per KV
    rotation); shapes the kernels can't tile use the jnp twin.

    Default blocks are the fused backward's (``_FLASH2_BLOCKS_BWD``);
    explicit block args always reach the kernel."""
    _no_block_diffusion("flash_block_grads", block_diffusion)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, h, tq, d = q.shape
    tk = k.shape[2]
    bq = _fit_block(block_q or _FLASH2_BLOCKS_BWD[0], tq)
    bk = _fit_block(block_k or _FLASH2_BLOCKS_BWD[1], tk)
    if tq % bq or tk % bk or (causal and tq > tk):
        return _block_grads_reference(q, k, v, g, lse, delta, causal, scale)
    return _flash2_backward_kernels(
        q, k, v, g,
        lse.reshape(b * h, tq), delta.reshape(b * h, tq),
        causal, scale, bq, bk, _interpret(),
    )


def _no_block_diffusion(name: str, block_diffusion) -> None:
    """The blockwise primitives hold one block of a sequence that other
    devices share: the block-diffusion mask is over a whole sequence's two
    copies, which no caller of theirs (``parallel/ring.py``, ``ulysses.py``)
    lays out yet."""
    if block_diffusion is not None:
        raise NotImplementedError(
            "%s takes no block_diffusion mask (%r): a block of a sequence that "
            "devices share has no clean and noised half of its own"
            % (name, block_diffusion)
        )


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    block_diffusion: tuple[int, int] | None = None,
):
    """Forward-only ``(o, lse)`` with ``lse`` as [B, H, Tq] float32 —
    the primitive blockwise/ring merging builds on. Callers own
    differentiation (ring attention defines its own VJP from
    :func:`flash_block_grads`). Default blocks are the forward's
    (``_FLASH2_BLOCKS_FWD``); explicit block args always reach the kernel."""
    _no_block_diffusion("flash_with_lse", block_diffusion)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, h, tq, d = q.shape
    tk = k.shape[2]
    # resolve the blocks FIRST so the ragged precheck validates the exact
    # blocks the kernel will run with
    bq = _fit_block(block_q or _FLASH2_BLOCKS_FWD[0], tq)
    bk = _fit_block(block_k or _FLASH2_BLOCKS_FWD[1], tk)
    if tq % bq or tk % bk or (causal and tq > tk):
        # ragged: take the reference path directly (one compute, with lse)
        return attention_reference_with_lse(
            q, k, v, causal=causal, scale=scale
        )
    out, lse = _flash2_forward(q, k, v, causal, scale, bq, bk, _interpret())
    return out, lse.reshape(b, h, tq)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    window: int | None = None,
    block_diffusion: tuple[int, int] | None = None,
) -> jax.Array:
    """Flash attention; falls back to the reference on ragged shapes.

    The kernels are the ones :func:`attention` runs on the TPU (here on
    every backend: off the TPU they run in the interpreter), so what a
    caller checks under this name is what a step runs. Default blocks come
    from :func:`_flash2_blocks`; explicit block args win, in the forward and
    in the backward. ``window`` and ``block_diffusion`` as :func:`attention`'s."""
    _check_window(window, causal, block_diffusion, q.shape[2], k.shape[2])
    if scale is None:
        scale = q.shape[-1] ** -0.5
    blocks = (block_q, block_k)
    bd = None if block_diffusion is None else tuple(block_diffusion)
    return _auto(q, k, v, causal, scale, blocks, blocks, window, bd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _auto(q, k, v, causal, scale, fwd_blocks=None, bwd_blocks=None,
          window=None, bd=None):
    """``fwd_blocks``/``bwd_blocks`` are optional (bq, bk) overrides for
    the kernels (hashable tuples — they ride nondiff_argnums); ``None``,
    for a pair or for either of it, means the measured defaults."""
    return _auto_fwd(
        q, k, v, causal, scale, fwd_blocks, bwd_blocks, window, bd
    )[0]


def _auto_fwd(q, k, v, causal, scale, fwd_blocks=None, bwd_blocks=None,
              window=None, bd=None):
    bq, bk = _flash2_blocks(
        "fwd", q.shape[2], k.shape[2], window, fwd_blocks, bd
    )
    out, lse = _flash2_forward(
        q, k, v, causal, scale, bq, bk, _interpret(), window, bd
    )
    return _name_residuals(q, k, v, out, lse)


def _name_residuals(q, k, v, out, lse):
    """Tag the vjp residuals with ``checkpoint_name`` so a ``jax.remat``
    policy can choose to SAVE the attention forward's products instead of
    re-running the kernel in the backward (``save_only_these_names``
    sees names inside a custom_vjp fwd). ``flash_out``/``flash_lse``
    are the expensive ones — saving them skips the whole forward kernel
    re-run under remat; ``flash_qkv`` additionally skips the projection
    recompute. See TransformerLM.remat_policy."""
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash_out")
    if lse is not None:
        lse = checkpoint_name(lse, "flash_lse")
    q = checkpoint_name(q, "flash_qkv")
    k = checkpoint_name(k, "flash_qkv")
    v = checkpoint_name(v, "flash_qkv")
    return out, (q, k, v, out, lse)


def _auto_bwd(causal, scale, fwd_blocks, bwd_blocks, window, bd, residuals, g):
    q, k, v, o, lse = residuals
    tq, tk = q.shape[2], k.shape[2]
    kernels = lse is not None and not (causal and tq > tk)
    if kernels:
        dq_blocks = _flash2_blocks("dq", tq, tk, window, bwd_blocks, bd)
        dkv_blocks = _flash2_blocks("bwd", tq, tk, window, bwd_blocks, bd)
        if _spans_fit(*dq_blocks, tq, tk, window, "kv", bd) and _spans_fit(
            *dkv_blocks, tq, tk, window, "q", bd
        ):
            return _flash2_backward(
                q, k, v, o, lse, g, causal, scale, *dq_blocks, _interpret(),
                window, dkv_blocks, bd,
            )
    obs_trace.get_tracer().note_once(
        "attn_route", tq=tq, tk=tk, window=window, side="backward",
        path="plain",
        why="lse" if lse is None else "blocks" if kernels else "shape",
        **_mask_note(bd),
    )
    _, vjp = jax.vjp(
        lambda q, k, v: attention_reference(
            q, k, v, causal=causal, scale=scale, window=window,
            block_diffusion=bd,
        ),
        q, k, v,
    )
    return vjp(g)


def _note_planned_tiles(tq, tk, bd, why):
    """The ``attn_tiles`` census of a block-diffusion call that took the dense
    reference (``path="plain"``, ``why`` as its ``attn_route`` note has it):
    what the forward and the fused backward would walk at the blocks the shape
    gets, where those tile it. A rehearsal on a CPU reads the plan there; a
    note from the chip says by its ``path`` that no kernel walked it."""
    for kernel, kind, side in (("flash2_fwd", "fwd", "kv"), ("flash2_bwd", "bwd", "q")):
        blocks = _flash2_blocks(kind, tq, tk, None, None, bd)
        if _spans_fit(*blocks, tq, tk, None, side, bd):
            _note_tiles(
                kernel, tq, tk, *blocks, True, None, side, bd, path="plain", why=why,
            )


def _mask_note(bd) -> dict:
    """What an ``attn_route`` note says of a block-diffusion call's mask (a
    causal or windowed call's note is what it was)."""
    if bd is None:
        return {}
    return {"mask": "block_diffusion", "length": bd[0], "block": bd[1]}


_auto.defvjp(_auto_fwd, _auto_bwd)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: float | None = None,
    window: int | None = None,
    block_diffusion: tuple[int, int] | None = None,
) -> jax.Array:
    """The default entry point for every model in the tree
    (TransformerLM, the LM examples). On the TPU it is the flash kernels,
    forward and backward; off the TPU it is exactly the dense reference.
    ``flash_attention`` / ``attention_reference`` remain for callers that
    want a specific implementation. The mask is one of three kinds:
    ``causal`` alone; ``window`` (with ``causal``): a query sees its
    ``window`` newest keys, itself included; ``block_diffusion=(L, B)`` (with
    ``causal``, over ``2 L`` positions): the block-diffusion training mask
    over a clean copy of a sequence and its noised copy, in blocks of ``B``
    (:func:`_sees`). The reference takes a window or a block-diffusion mask
    as a dense mask, the kernels as spans. Which of the two a shape took
    is an ``attn_route`` note (``path``, and ``why`` where it is the plain
    form: ``backend`` here; ``lse`` / ``shape`` / ``blocks`` where a backward
    pass on the TPU fell to the reference's; under block diffusion also the
    ``mask``'s kind and its ``length`` and ``block``, and off the TPU the
    ``attn_tiles`` census the kernels would have left, as a plan:
    :func:`_note_planned_tiles`)."""
    _check_window(window, causal, block_diffusion, q.shape[2], k.shape[2])
    if scale is None:
        scale = q.shape[-1] ** -0.5
    bd = None if block_diffusion is None else tuple(block_diffusion)
    note = functools.partial(
        obs_trace.get_tracer().note_once, "attn_route", tq=q.shape[2],
        tk=k.shape[2], window=window, **_mask_note(bd),
    )
    if jax.default_backend() != "tpu":
        # native autodiff, not a custom_vjp around the reference: that
        # would recompute the whole forward in every backward, where plain
        # differentiation reuses the saved activations
        note(path="plain", why="backend")
        if bd is not None:
            _note_planned_tiles(q.shape[2], k.shape[2], bd, "backend")
        return attention_reference(
            q, k, v, causal=causal, scale=scale, window=window,
            block_diffusion=bd,
        )
    note(path="kernel")
    return _auto(q, k, v, causal, scale, None, None, window, bd)
