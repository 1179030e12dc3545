"""Chunked gated delta rule: the state update of a linear-attention layer.

The recurrence, per head with state ``S`` of ``[d_k, d_v]`` (Yang, Kautz and
Hatamizadeh, "Gated Delta Networks", arXiv:2412.06464, equation 10), for a
log-decay ``g_t <= 0`` and a writing strength ``beta_t``::

    S'  = exp(g_t) S_{t-1}
    u_t = beta_t (v_t - S'^T k_t)          what the state does not hold yet
    S_t = S' + k_t u_t^T                   = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Where ``ops/ssd.py``'s state only decays and accumulates, this one is
corrected by a rank-one term a step, so the ``u`` of a chunk depend on each
other: with ``gamma`` the running sum of ``g`` inside a chunk of ``C`` steps
and ``K``, ``Q``, ``V`` its rows, they solve a unit lower-triangular system
(the WY / UT transform, section 3.2 of the paper)::

    A = tril(diag(beta) (K K^T o exp(gamma_i - gamma_j)), -1)      T = (I + A)^-1
    W = T diag(beta exp(gamma)) K          U = T diag(beta) V
    V_new = U - W S                        S: the state the chunk inherits
    O = (Q o exp(gamma)) S + (Q K^T o exp(gamma_i - gamma_j) o [j <= i]) V_new
    S <- exp(gamma_C) S + (K o exp(gamma_C - gamma))^T V_new

``T`` is had by a **blockwise solve that doubles**: the inverse of a unit
lower-triangular ``[[M11, 0], [M21, M22]]`` is ``[[T11, 0], [-T22 M21 T11,
T22]]``, so from the 1 x 1 blocks of the diagonal (ones) ``log2(C)`` rounds of
``D <- D - D A_s D`` (``A_s``: the entries of ``A`` that join two neighbouring
blocks of ``s`` rows) double the inverted blocks to the whole chunk. Every
round is two ``[C, C]`` matmuls, nothing walks the rows one at a time, and the
arithmetic is that of block forward substitution: no power of ``A`` is formed,
so keys that repeat inside a chunk (``A`` entries near ``beta``, whose powers
``(I - A)(I + A^2)...`` would reach 1e18 before cancelling) cost nothing.

Only the state is carried sequentially (``carried_states``: a ``lax.scan`` over
the chunks of two matmuls a step, ``W S`` and ``K^T V_new``); the outputs of all
chunks are then computed at once from the states the scan emits.

**What the carry leaves for its backward.** ``carried_states`` has a backward
of its own: the forward keeps the float32 state every chunk inherits, the
backward is one reverse ``lax.scan`` that takes ``jax.vjp`` of the same step
at the saved state. The saved states, ``V_new`` and the final state bear the
name ``gdn_carry`` (``jax.ad_checkpoint.checkpoint_name``), so a remat policy
that saves the name (``TransformerLM.remat_policy`` ``"save_flash"``) finds
all the later products read of the loop and does not run it a second time: the
compiled gradient holds one forward and one reverse loop a call. jax's own
transpose of a ``lax.scan`` takes its residuals from inside the loop, so the
forward loop would run again whatever was saved outside it. ``T``, all its
own backward keeps, bears the name ``gdn_inverse``: saved, the doubling's
rounds (the costliest of the chunk-parallel parts) are not repeated either.

Precision: ``g``, its running sums, every ``exp``, ``beta``, ``A``, ``T`` (its
rounds at ``Precision.HIGHEST``) and the carried state are float32; the other
matmuls take their operands in ``q``'s dtype with float32 accumulation, as
``ssd_scan``'s do. Every ``exp`` is of a difference that is never positive, so
nothing overflows however fast a head forgets. Plain ``jax.numpy`` / ``lax``:
the backward is jax's, but for the inverse's (``unit_lower_inverse``) and the
carry's (``carried_states``), and the carry's is jax's of one step.

:func:`kda_rule` is the same rule with a decay for every key channel (Kimi
delta attention): the decay no longer factors out of the products over the key
channels, so it rides the operands, a sub-block of steps at a time; the solve,
the carry (its decay then a vector over ``d_k``) and the names are shared.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from edl_tpu.obs import trace as obs_trace

SOLVE = "block_doubling"  # how T is had, as the ``gdn_chunks`` instant names it
# the names a remat policy saves to spare the rule's two costly parts a second
# run: what ``carried_states`` keeps, every chunk's ``T``, and the rule's
# outputs (tagged by the caller)
CARRY_NAME, INVERSE_NAME, OUT_NAME = "gdn_carry", "gdn_inverse", "gdn_out"
REMAT_NAMES = (CARRY_NAME, INVERSE_NAME, OUT_NAME)
_exact = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` ``[..., C, C]``
    (float32, ``C`` a power of two), by the doubling above. Its backward is
    the inverse's own, ``d a = -T^T d T T^T``: two products and nothing kept
    but ``T``, where jax's through the rounds would keep three ``[C, C]``
    arrays a round (1.1 GB a layer at 3840 chunks of 64 a head)."""
    size = a.shape[-1]
    if size & (size - 1):
        raise ValueError("unit_lower_inverse: %d is not a power of two" % size)
    row = jnp.arange(size)[:, None]
    col = jnp.arange(size)[None, :]
    inverse = jnp.broadcast_to(jnp.eye(size, dtype=a.dtype), a.shape)
    s = 1
    while s < size:
        # rows of an odd block of s against the columns of the even block
        # before it: the M21 of every pair of neighbours
        joins = (row // (2 * s) == col // (2 * s)) & (row // s > col // s)
        inverse = inverse - _exact(_exact(inverse, jnp.where(joins, a, 0.0)), inverse)
        s *= 2
    return inverse


def _unit_lower_inverse_fwd(a):
    # by name: 16 KB a chunk a head saved spares a recomputation its rounds
    inverse = checkpoint_name(unit_lower_inverse(a), INVERSE_NAME)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, ct):
    transposed = jnp.swapaxes(inverse, -1, -2)
    return (-_exact(_exact(transposed, ct), transposed),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _carry(state, inputs):
    """One chunk's step of the state, float32 ``[b h k v]``: what the chunk
    writes (``V_new``, in the operands' dtype) and the state it leaves."""
    w_n, u_n, k_n, whole_n = inputs
    dtype, dot = w_n.dtype, dict(preferred_element_type=jnp.float32)
    new = u_n - jnp.einsum("bhck,bhkv->bhcv", w_n, state.astype(dtype), **dot)
    new = new.astype(dtype)
    # a decay a head ``[b h]``, or one a key channel ``[b h k]`` (``kda_rule``)
    per_channel = whole_n.ndim == state.ndim - 1
    decay = whole_n[..., None] if per_channel else whole_n[..., None, None]
    after = decay * state + jnp.einsum("bchk,bhcv->bhkv", k_n, new, **dot)
    return after, new


@jax.custom_vjp
def carried_states(state, w, u, k_out, whole):
    """The state from chunk to chunk: ``(states, new, final)`` for the initial
    ``state`` ``[b h k v]`` (float32) and, chunks first, ``w`` ``[n b h c k]``,
    ``u`` ``[n b h c v]`` (float32), ``k_out`` ``[n b c h k]``, ``whole`` ``[n b
    h]`` (``kda_rule``: ``[n b h k]``, a decay a key channel). ``states`` ``[n b
    h k v]`` are the float32 states the chunks inherit (float32 because the
    gradient of ``whole`` is ``<dS, S>``), ``new`` ``[n b h c v]`` what they
    write, ``final`` the state after the last."""

    def step(state, inputs):
        after, new = _carry(state, inputs)
        return after, (state, new)

    final, (states, new) = jax.lax.scan(step, state, (w, u, k_out, whole))
    return states, new, final


def _carried_states_fwd(state, w, u, k_out, whole):
    # by name, so that a remat policy can keep all three: whatever the later
    # products read of the loop has to be saved, or the loop runs again
    states, new, final = (
        checkpoint_name(a, CARRY_NAME)
        for a in carried_states(state, w, u, k_out, whole)
    )
    return (states, new, final), (states, w, u, k_out, whole)


def _carried_states_bwd(residuals, cotangents):
    states, *inputs = residuals
    d_states, d_new, d_final = cotangents

    def step(d_state, at):
        state, inputs_n, d_entering, d_new_n = at
        _, pull = jax.vjp(_carry, state, inputs_n)
        d_before, d_inputs = pull((d_state, d_new_n))
        return d_before + d_entering, d_inputs

    d_state, d_inputs = jax.lax.scan(
        step, d_final, (states, tuple(inputs), d_states, d_new), reverse=True
    )
    return (d_state, *d_inputs)


carried_states.defvjp(_carried_states_fwd, _carried_states_bwd)


def saved_bytes(chunk, chunks, heads, d_k, d_v, itemsize, batch=1):
    """What one call leaves for its backward under a policy that saves
    ``REMAT_NAMES``: the float32 states the chunks inherit and the final one,
    every chunk's float32 ``T``, ``V_new`` and ``o`` in the operands' dtype."""
    states = 4 * (chunks + 1) * heads * d_k * d_v
    inverses = 4 * chunks * heads * chunk * chunk
    return batch * (states + inverses + 2 * itemsize * chunks * chunk * heads * d_v)


@functools.lru_cache(maxsize=None)
def _note_chunks(chunk, chunks, heads, d_k, d_v, itemsize, batch):
    """One ``gdn_chunks`` instant in the span ring for each shape the rule
    is traced at."""
    obs_trace.get_tracer().instant(
        "gdn_chunks", chunk=chunk, chunks=chunks, heads=heads, d_k=d_k, d_v=d_v,
        state_bytes=4 * heads * d_k * d_v, solve=SOLVE, carry="saved",
        saved_bytes=saved_bytes(chunk, chunks, heads, d_k, d_v, itemsize, batch),
    )


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64, initial_state=None,
                     return_final_state: bool = False):
    """``o`` ``[B, T, H, d_v]`` in ``q``'s dtype (and the final state, float32
    ``[B, H, d_k, d_v]``, with ``return_final_state``).

    ``q``, ``k`` ``[B, T, H, d_k]``, as the layer hands them over (normalised,
    ``q`` scaled); ``v`` ``[B, T, H, d_v]``; ``g`` ``[B, T, H]``, the log of the
    decay, never positive; ``beta`` ``[B, T, H]``; ``initial_state`` ``[B, H,
    d_k, d_v]`` or None for zeros. ``chunk`` is a power of two. The result does
    not depend on it beyond rounding; a ``T`` it does not divide is padded with
    steps of ``g = 0``, ``beta = 0`` and zero rows, which leave the state as it
    is.
    """
    batch, t, h, d_k = q.shape
    d_v = v.shape[-1]
    if k.shape != q.shape or v.shape[:3] != q.shape[:3]:
        raise ValueError(
            "gated_delta_rule: q %s, k %s, v %s" % (q.shape, k.shape, v.shape)
        )
    if g.shape != q.shape[:3] or beta.shape != q.shape[:3]:
        raise ValueError(
            "gated_delta_rule: g %s, beta %s for %s" % (g.shape, beta.shape, q.shape[:3])
        )
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError("gated_delta_rule: chunk %d is not a power of two" % chunk)
    size = chunk
    pad = -t % size
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta)
        )
    nc = (t + pad) // size
    f32, dtype = jnp.float32, q.dtype
    _note_chunks(size, nc, h, d_k, d_v, jnp.dtype(dtype).itemsize, batch)
    dot = dict(preferred_element_type=f32)

    # everything below: b batch, n chunk, c / s step in a chunk, h head,
    # k key width, v value width
    q = q.reshape(batch, nc, size, h, d_k)
    k = k.reshape(batch, nc, size, h, d_k)
    v = v.reshape(batch, nc, size, h, d_v)
    steps = lambda a: jnp.moveaxis(  # noqa: E731 — heads before steps
        a.astype(f32).reshape(batch, nc, size, h), 2, -1
    )
    beta = steps(beta)                                           # [b n h c]
    gamma = jnp.cumsum(steps(g), axis=-1)
    by_step = lambda a: jnp.moveaxis(a, -1, 2)[..., None]  # noqa: E731 — [b n c h 1]

    lower = jnp.tril(jnp.ones((size, size), bool))
    between = jnp.exp(jnp.where(
        lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf
    ))                                                           # [b n h c s]

    # inside a chunk: the system, its inverse, and what it makes of K and V
    kk = jnp.einsum("bnchk,bnshk->bnhcs", k, k, **dot)
    system = jnp.where(
        jnp.tril(jnp.ones((size, size), bool), -1),
        beta[..., None] * kk * between, 0.0,
    )
    inverse = unit_lower_inverse(system).astype(dtype)
    k_in = (k.astype(f32) * by_step(beta * jnp.exp(gamma))).astype(dtype)
    v_in = (v.astype(f32) * by_step(beta)).astype(dtype)
    w = jnp.einsum("bnhcs,bnshk->bnhck", inverse, k_in, **dot).astype(dtype)
    u = jnp.einsum("bnhcs,bnshv->bnhcv", inverse, v_in, **dot)
    to_end = jnp.exp(gamma[..., -1:] - gamma)                    # [b n h c]
    k_out = (k.astype(f32) * by_step(to_end)).astype(dtype)
    whole = jnp.exp(gamma[..., -1])                              # [b n h]

    # from chunk to chunk, the state in float32
    if initial_state is None:
        state = jnp.zeros((batch, h, d_k, d_v), f32)
    else:
        state = initial_state.astype(f32)
    chunks_first = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    states, new, state = carried_states(
        state, *(chunks_first(a) for a in (w, u, k_out, whole))
    )
    # a cast of what is saved: the backward needs no copy of its own
    entering = jnp.moveaxis(states.astype(dtype), 0, 1)          # [b n h k v]
    new = jnp.moveaxis(new, 0, 1)                                # [b n h c v]

    # every chunk's outputs: what it inherits, and what it wrote itself
    q_in = (q.astype(f32) * by_step(jnp.exp(gamma))).astype(dtype)
    inherited = jnp.einsum("bnchk,bnhkv->bnchv", q_in, entering, **dot)
    scores = jnp.einsum("bnchk,bnshk->bnhcs", q, k, **dot)
    own = jnp.einsum(
        "bnhcs,bnhsv->bnchv", (scores * between).astype(dtype), new, **dot
    )
    o = (inherited + own).reshape(batch, t + pad, h, d_v)[:, :t].astype(dtype)
    if return_final_state:
        return o, state
    return o


# -- a decay for every key channel (Kimi delta attention) --------------------

# Steps that share one reference point of the exponents. A factor lies within
# e^+-(SUB_BLOCK / 2 * max|g|) and a masked pair's product under the square of
# it, which float32 holds up to e^88: ``MAX_DECAY_A_STEP`` is the most a caller
# may let ``|g|`` reach, and ``KimiDeltaMixer`` holds its gate's bound to it.
SUB_BLOCK = 16
MAX_DECAY_A_STEP = 88.0 / SUB_BLOCK


@functools.lru_cache(maxsize=None)
def _note_kda_chunks(chunk, sub_block, chunks, heads, d_k, d_v, itemsize, batch):
    """One ``kda_chunks`` instant in the span ring for each shape
    :func:`kda_rule` is traced at."""
    obs_trace.get_tracer().instant(
        "kda_chunks", chunk=chunk, sub_block=sub_block, chunks=chunks, heads=heads,
        d_k=d_k, d_v=d_v, state_bytes=4 * heads * d_k * d_v, solve=SOLVE,
        saved_bytes=saved_bytes(chunk, chunks, heads, d_k, d_v, itemsize, batch),
    )


def kda_rule(q, k, v, g, beta, *, chunk: int = 64, initial_state=None,
             return_final_state: bool = False):
    """The delta rule with **a decay for every key channel** (Kimi delta
    attention, arXiv:2510.26692, equation 1): per head, for a log-decay ``g_t``
    of ``d_k`` values, none positive::

        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t

    ``q``, ``k``, ``g`` ``[B, T, H, d_k]``; ``v`` ``[B, T, H, d_v]``; ``beta``
    ``[B, T, H]``; returns ``o`` ``[B, T, H, d_v]`` in ``q``'s dtype (and the
    final state, float32 ``[B, H, d_k, d_v]``, with ``return_final_state``).

    The chunked form is :func:`gated_delta_rule`'s with the decay inside every
    contraction over the key channels: with ``Gamma`` the running sum of ``g``
    in a chunk, ``A_ij = beta_i sum_d k_i[d] k_j[d] exp(Gamma_i[d] -
    Gamma_j[d])`` and the scores likewise with ``q_i``, so ``exp(Gamma_i -
    Gamma_j)`` no longer factors out as one ``[C, C]`` matrix a head. It
    factors a channel: ``(k_i o exp(Gamma_i - R)) . (k_j o exp(R - Gamma_j))``
    for any reference ``R``, and the rows of each **sub-block** of
    ``SUB_BLOCK`` steps take ``R`` = the running sum at the sub-block's middle
    step. A row's exponent and, inside the rows' own sub-block, a column's are
    then within half a sub-block's decay of zero either way; the columns of
    earlier sub-blocks have none positive, those of later ones are zeros (the
    mask's). **The caller keeps ``|g|`` under ``MAX_DECAY_A_STEP``** (the KDA
    layer's safe gate holds ``g`` in (-5, 0): 40 over half a sub-block, so a
    factor lies in e^+-40 and the product of a masked pair under e^80, inside
    float32's e^88); no other exponent here is of a positive argument. ``W``,
    ``U``, the carry
    (``carried_states``, its decay a vector over ``d_k``) and the outputs are
    the scalar rule's with ``exp(Gamma)`` a channel; the solve is
    ``unit_lower_inverse``; what a remat policy saves bears the same names
    (``REMAT_NAMES``).

    Precision as the scalar rule's: ``g``, its sums, every ``exp``, ``beta``,
    the system, its inverse and the carried state in float32; each matmul
    operand rounded once to ``q``'s dtype, float32 accumulation.
    """
    batch, t, h, d_k = q.shape
    d_v = v.shape[-1]
    if k.shape != q.shape or g.shape != q.shape or v.shape[:3] != q.shape[:3]:
        raise ValueError(
            "kda_rule: q %s, k %s, g %s, v %s" % (q.shape, k.shape, g.shape, v.shape)
        )
    if beta.shape != q.shape[:3]:
        raise ValueError("kda_rule: beta %s for %s" % (beta.shape, q.shape[:3]))
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError("kda_rule: chunk %d is not a power of two" % chunk)
    sub = min(SUB_BLOCK, chunk)  # both powers of two: it divides the chunk
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta)
        )
    steps = t + pad
    size, blocks, nc = chunk, chunk // sub, steps // chunk
    f32, dtype = jnp.float32, q.dtype
    _note_kda_chunks(size, sub, nc, h, d_k, d_v, jnp.dtype(dtype).itemsize, batch)
    dot = dict(preferred_element_type=f32)

    # b batch, n chunk, i sub-block, c / s step (in a chunk, or in a sub-block
    # behind an i), h head, k key width, v value width
    q = q.reshape(batch, nc, size, h, d_k)
    k = k.reshape(batch, nc, size, h, d_k)
    v = v.reshape(batch, nc, size, h, d_v)
    beta = beta.astype(f32).reshape(batch, nc, size, h)
    gamma = jnp.cumsum(g.astype(f32).reshape(batch, nc, size, h, d_k), axis=2)

    # a sub-block's reference: the running sum at its middle step
    in_blocks = lambda a: a.reshape(batch, nc, blocks, sub, *a.shape[3:])  # noqa: E731
    ref = in_blocks(gamma)[:, :, :, (sub - 1) // 2]             # [b n i h k]
    rows = jnp.exp(in_blocks(gamma) - ref[:, :, :, None])        # [b n i c h k]
    # columns up to the end of the rows' own sub-block, zeros past it
    reach = jnp.arange(size)[None, :] // sub <= jnp.arange(blocks)[:, None]   # [i s]
    cols = jnp.exp(jnp.where(
        reach[:, :, None, None], ref[:, :, :, None] - gamma[:, :, None], -jnp.inf
    ))                                                           # [b n i s h k]
    k32, q32 = k.astype(f32), q.astype(f32)
    k_rows = (in_blocks(k32) * rows).astype(dtype)
    q_rows = (in_blocks(q32) * rows).astype(dtype)
    k_cols = (k32[:, :, None] * cols).astype(dtype)

    def against_columns(rows):
        """``rows`` [b n i c h k] against ``k_cols``, a sub-block at a time:
        [b n h C S], the sub-blocks' rows one after another."""
        merged = lambda a: a.reshape(batch, nc * blocks, *a.shape[3:])  # noqa: E731
        tile = jnp.einsum("bmchk,bmshk->bmhcs", merged(rows), merged(k_cols), **dot)
        tile = tile.reshape(batch, nc, blocks, h, sub, size)
        return jnp.moveaxis(tile, 2, 3).reshape(batch, nc, h, size, size)

    kk, scores = against_columns(k_rows), against_columns(q_rows)

    # inside a chunk: the system, its inverse, and what it makes of K and V
    lower = jnp.tril(jnp.ones((size, size), bool))
    beta_h = jnp.moveaxis(beta, 2, -1)                           # [b n h c]
    system = jnp.where(jnp.tril(lower, -1), beta_h[..., None] * kk, 0.0)
    inverse = unit_lower_inverse(system).astype(dtype)
    k_in = (k32 * (beta[..., None] * jnp.exp(gamma))).astype(dtype)
    v_in = (v.astype(f32) * beta[..., None]).astype(dtype)
    w = jnp.einsum("bnhcs,bnshk->bnhck", inverse, k_in, **dot).astype(dtype)
    u = jnp.einsum("bnhcs,bnshv->bnhcv", inverse, v_in, **dot)
    k_out = (k32 * jnp.exp(gamma[:, :, -1:] - gamma)).astype(dtype)
    whole = jnp.exp(gamma[:, :, -1])                             # [b n h k]

    # from chunk to chunk, the state in float32
    if initial_state is None:
        state = jnp.zeros((batch, h, d_k, d_v), f32)
    else:
        state = initial_state.astype(f32)
    chunks_first = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    states, new, state = carried_states(
        state, *(chunks_first(a) for a in (w, u, k_out, whole))
    )
    entering = jnp.moveaxis(states.astype(dtype), 0, 1)          # [b n h k v]
    new = jnp.moveaxis(new, 0, 1)                                # [b n h c v]

    # every chunk's outputs: what it inherits, and what it wrote itself
    q_in = (q32 * jnp.exp(gamma)).astype(dtype)
    inherited = jnp.einsum("bnchk,bnhkv->bnchv", q_in, entering, **dot)
    own = jnp.einsum(
        "bnhcs,bnhsv->bnchv", jnp.where(lower, scores, 0.0).astype(dtype), new, **dot
    )
    o = (inherited + own).reshape(batch, steps, h, d_v)[:, :t].astype(dtype)
    if return_final_state:
        return o, state
    return o
