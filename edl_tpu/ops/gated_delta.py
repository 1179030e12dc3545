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

Only the state is carried sequentially, and **the carry and the output stage
have two forms under one contract** (:func:`_carried_outputs`). On a TPU
backend, for bfloat16 operands at a chunk of 64 and widths that tile, one
Pallas call walks a batch row's chunks in order with every head's float32
state in VMEM and writes ``V_new``, ``o`` and the state each chunk inherits
(``delta_carry``; ``delta_carry_back`` walks them back with ``dS`` in VMEM),
shared by both rules: no state goes to HBM between two chunks but the copy the
backward reads. Everywhere else, the plain form: ``carried_states``, a
``lax.scan`` over the chunks of two matmuls a step (``W S`` and ``K^T
V_new``), then the outputs of all chunks at once from the states the scan
emits; it is also what the kernels are tested against.

**What the carry leaves for its backward.** Either form has a backward of its
own: the forward keeps the float32 state every chunk inherits, the backward is
one walk in reverse at the saved states (``delta_carry_back``; the plain
form's a reverse ``lax.scan`` that takes ``jax.vjp`` of the same step). The
saved states, ``V_new`` and the final state bear the name ``gdn_carry``
(``jax.ad_checkpoint.checkpoint_name``), so a remat policy that saves the name
(``TransformerLM.remat_policy`` ``"save_flash"``) finds all that is read of
the carry and does not run it a second time: the compiled gradient holds one
forward and one reverse walk (or loop) a call. jax's own transpose of a
``lax.scan`` takes its residuals from inside the loop, so the forward loop
would run again whatever was saved outside it. ``T``, all its own backward
keeps, bears the name ``gdn_inverse``: saved, the doubling's rounds (the
costliest of the chunk-parallel parts) are not repeated either.

Precision: ``g``, its running sums, every ``exp``, ``beta``, ``A``, ``T`` (its
rounds at ``Precision.HIGHEST``) and the carried state are float32; the other
matmuls take their operands in ``q``'s dtype with float32 accumulation, as
``ssd_scan``'s do. Every ``exp`` is of a difference that is never positive, so
nothing overflows however fast a head forgets. Where the forms are plain
``jax.numpy`` / ``lax`` the backward is jax's, but for the inverse's
(``unit_lower_inverse``) and the carry's (``carried_states``), and the carry's
is jax's of one step.

:func:`kda_rule` is the same rule with a decay for every key channel (Kimi
delta attention): the decay no longer factors out of the products over the key
channels, so it rides the operands, under references that keep every factor
at or under 1 whatever the decay (the halving form, below); the solve,
the carry (its decay then a vector over ``d_k``) and the names are shared.
Its **chunk-local stage**, everything between the rule's inputs and the
carry's operands that depends on one chunk of one head only, has two forms
under one contract. On a TPU backend, for bfloat16 operands at a chunk of 64
and widths of whole lane tiles, three Pallas kernels under one
``jax.custom_vjp`` hold a chunk of every head in VMEM a grid step and never
write an intermediate to HBM: ``kda_inverse`` (``k``, ``g``, ``beta`` to every
chunk's float32 ``T``; the solve's rounds run here and nowhere else, and ``T``
bears ``gdn_inverse``), ``kda_operands`` (the inputs and ``T`` to ``w``, ``u``,
``k_out``, ``whole``, ``q_in`` and the masked scores, each in its reader's
layout) and ``kda_backward`` (the inputs, ``T`` and the six cotangents to
``dq``, ``dk``, ``dv``, ``dg``, ``dbeta``). Everywhere else, :func:`_local_plain`:
the plain ``jax.numpy`` form with jax's backward, which is also what the
kernels are tested against. :func:`gated_delta_rule`'s chunk-local stage has
kernels of its own, the steps along the lanes (``gdn_inverse``,
``gdn_operands``, ``gdn_backward``). The walk after either takes ``k_out`` and
``q_in`` where these kernels wrote them: rows whose heads lie side by side
(``kda_operands``) or a tile a head (``gdn_operands``).

``ops/ssd.py`` (Mamba-2's scan, which has no solve) runs its own chunk-local
stage as two kernels on the same frame and imports it from here: the call
builder ``_chunk_call`` (a grid step a chunk, the blocks and the VMEM limit
from a table of kinds; ``_run`` is this file's table; ``walk``, the chunks in
order and the scratch kept between them, is the carry's alone), ``_iota``,
``_running_sum`` (``axis`` 1: along the lanes), ``_column_of``, ``_over_heads``
(``width`` heads a round) and the two transposed products. What a backward
keeps differs: ``kda_backward`` the inputs and ``T``, ``ssd_backward`` the
inputs alone.
"""

from __future__ import annotations

import functools
import math

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


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64, initial_state=None,
                     return_final_state: bool = False, interpret: bool = False):
    """``o`` ``[B, T, H, d_v]`` in ``q``'s dtype (and the final state, float32
    ``[B, H, d_k, d_v]``, with ``return_final_state``).

    ``q``, ``k`` ``[B, T, H, d_k]``, as the layer hands them over (normalised,
    ``q`` scaled); ``v`` ``[B, T, H, d_v]``; ``g`` ``[B, T, H]``, the log of the
    decay, never positive; ``beta`` ``[B, T, H]``; ``initial_state`` ``[B, H,
    d_k, d_v]`` or None for zeros. ``chunk`` is a power of two. The result does
    not depend on it beyond rounding; a ``T`` it does not divide is padded with
    steps of ``g = 0``, ``beta = 0`` and zero rows, which leave the state as it
    is.

    **Two forms of the chunk-local stage** (from the inputs to the carry's
    operands ``w``, ``u``, ``k_out``, ``whole`` and the output stage's ``q_in``
    and masked scores), one contract, decided from what the call can see as
    :func:`kda_rule`'s is: on a TPU backend, for bfloat16 ``q``, ``k``, ``v``,
    a chunk of 64, a ``T`` of whole lane tiles (128 steps, two chunks: a grid
    step) and widths that are multiples of 16, the three Pallas kernels
    ``gdn_inverse``, ``gdn_operands`` and ``gdn_backward`` under one
    ``jax.custom_vjp`` (:func:`_scalar_kernels`); everywhere else the plain
    form (:func:`_scalar_plain`), which is also the kernels' reference.
    ``interpret`` runs the kernels in the Pallas interpreter (tests on the
    CPU). The kernels hold **the steps along the lanes** (``[B, H d, T]``, the
    view of ``[B, T, H, d]`` that the mixer's convolution writes and XLA keeps
    through the norms), so a head is ``d`` sublanes of a block wherever it
    starts: any count of heads, an odd last one by itself after the loop over
    pairs, and the cell's 15 of 96 / 192 as they are, nothing padded and
    nothing copied. The carry and the output stage after it
    (:func:`_carried_outputs`) decide for themselves, from the operands they
    are handed: the walk's two kernels (``delta_carry``, ``delta_carry_back``)
    where they can be, on the chunk-local kernels' ``k_out`` and ``q_in`` as
    those wrote them, a tile a head.
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
    why_plain = _kernels_refuse(q, k, v, t, chunk, interpret, along_lanes=True)
    size = chunk
    pad = -t % size
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta)
        )
    steps = t + pad
    nc = steps // size
    f32, dtype = jnp.float32, q.dtype
    # once a shape and stage: which form the chunk-local stage took, and why
    # where it is the plain one
    obs_trace.get_tracer().note_once(
        "gdn_chunks", chunk=size, chunks=nc, heads=h, d_k=d_k, d_v=d_v,
        state_bytes=4 * h * d_k * d_v, solve=SOLVE, carry="saved",
        saved_bytes=saved_bytes(
            size, nc, h, d_k, d_v, jnp.dtype(dtype).itemsize, batch
        ),
        **(dict(path="kernel") if why_plain is None else dict(path="plain", why=why_plain)),
    )
    if why_plain is None:
        # the steps along the lanes, a head's channels one under another: as
        # the mixer's convolution left them, so a view and no copy
        lanes = lambda a: jnp.swapaxes(a.reshape(batch, steps, -1), 1, 2)  # noqa: E731
        w, u, k_out, whole, q_in, scores = _scalar_kernels(
            lanes(q), lanes(k), lanes(v), lanes(g.astype(f32)), lanes(beta.astype(f32)),
            interpret,
        )
        # k_out and q_in a tile a head, as the kernels wrote them
        whole = whole.reshape(nc, batch, h)
    else:
        w, u, k_out, whole, q_in, scores, _ = _scalar_plain(q, k, v, g, beta, size)
        k_out, q_in = k_out.reshape(nc, batch, size, -1), q_in.reshape(batch, steps, -1)
    o, state = _carried_outputs(
        initial_state, w, u, k_out, whole, q_in, scores, t, interpret
    )
    if return_final_state:
        return o, state
    return o


def _scalar_plain(q, k, v, g, beta, size):
    """The scalar rule's chunk-local stage in plain ``jax.numpy``: everything
    of the rule that depends on one chunk of one head only. ``q``, ``k`` ``[B,
    T, H, d_k]``, ``v`` ``[B, T, H, d_v]``, ``g``, ``beta`` ``[B, T, H]``, ``T``
    a multiple of ``size``; returns the carry's operands chunks first (``w``
    ``[n b h c k]``, ``u`` ``[n b h c v]`` float32, ``k_out`` ``[n b c h k]``,
    ``whole`` ``[n b h]`` float32), the output stage's (``q_in`` ``[b n c h
    k]``, the causal-masked decayed scores ``[b n h c s]``) and every chunk's
    float32 ``T`` ``[b n h c s]``."""
    batch, steps, h, d_k = q.shape
    nc = steps // size
    f32, dtype = jnp.float32, q.dtype
    dot = dict(preferred_element_type=f32)

    # everything below: b batch, n chunk, c / s step in a chunk, h head,
    # k key width, v value width
    q = q.reshape(batch, nc, size, h, d_k)
    k = k.reshape(batch, nc, size, h, d_k)
    v = v.reshape(batch, nc, size, h, v.shape[-1])
    by_head = lambda a: jnp.moveaxis(  # noqa: E731 — heads before steps
        a.astype(f32).reshape(batch, nc, size, h), 2, -1
    )
    beta = by_head(beta)                                         # [b n h c]
    gamma = jnp.cumsum(by_head(g), axis=-1)
    by_step = lambda a: jnp.moveaxis(a, -1, 2)[..., None]  # noqa: E731 — [b n c h 1]

    lower = jnp.tril(jnp.ones((size, size), bool))
    between = jnp.exp(jnp.where(
        lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf
    ))                                                           # [b n h c s]

    # inside a chunk: the system, its inverse, and what it makes of K and V
    kk = jnp.einsum("bnchk,bnshk->bnhcs", k, k, **dot)
    system = jnp.where(jnp.tril(lower, -1), beta[..., None] * kk * between, 0.0)
    exact = unit_lower_inverse(system)
    inverse = exact.astype(dtype)
    k_in = (k.astype(f32) * by_step(beta * jnp.exp(gamma))).astype(dtype)
    v_in = (v.astype(f32) * by_step(beta)).astype(dtype)
    w = jnp.einsum("bnhcs,bnshk->bnhck", inverse, k_in, **dot).astype(dtype)
    u = jnp.einsum("bnhcs,bnshv->bnhcv", inverse, v_in, **dot)
    to_end = jnp.exp(gamma[..., -1:] - gamma)                    # [b n h c]
    k_out = (k.astype(f32) * by_step(to_end)).astype(dtype)
    whole = jnp.exp(gamma[..., -1])                              # [b n h]
    q_in = (q.astype(f32) * by_step(jnp.exp(gamma))).astype(dtype)
    scores = jnp.einsum("bnchk,bnshk->bnhcs", q, k, **dot)
    chunks_first = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    return (
        *(chunks_first(a) for a in (w, u, k_out, whole)), q_in,
        (scores * between).astype(dtype), exact,
    )


def _carried_outputs(initial_state, w, u, k_out, whole, q_in, scores, t, interpret=False):
    """From the chunk-local stage's six operands to the first ``t`` steps' ``o``
    ``[B, t, H, d_v]`` in the operands' dtype and the final state: the state
    from chunk to chunk in float32 and every chunk's outputs, what it inherits
    and what it wrote itself.

    ``w``, ``u``, ``whole`` and ``scores`` as :func:`_scalar_plain` and
    :func:`_local_plain` lay them out; ``k_out`` and ``q_in`` **where their
    producer left them**: rows whose heads lie side by side (``[n b c (h k)]``
    and ``[b (n c) (h k)]``: the plain forms' arrays and ``kda_operands``'
    blocks alike) or a tile a head (``[n b h c k]`` and ``[b n h c k]``, five
    axes: ``gdn_operands``'). ``whole`` a decay a head ``[n b h]`` or a key
    channel ``[n b h k]``.

    **Two forms, one contract**, decided from the operands as the chunk-local
    stage's is (:func:`_carry_refuses`) and noted once a shape and stage
    (``delta_carry``: ``path`` and, where plain, ``why``): on a TPU backend,
    for bfloat16 operands at a chunk of 64 and widths that tile, one Pallas
    call that walks a batch row's chunks with every head's state in VMEM and
    writes ``o`` (``delta_carry``; ``delta_carry_back`` its backward, under one
    ``jax.custom_vjp``: :func:`_carry_kernels`); everywhere else the plain
    form, ``carried_states`` (a ``lax.scan``) and two products over all chunks
    at once, which is also the kernels' reference. ``interpret`` runs the
    kernels in the Pallas interpreter."""
    nc, batch, h, size, d_k = w.shape
    d_v = u.shape[-1]
    f32, dtype = jnp.float32, w.dtype
    dot = dict(preferred_element_type=f32)
    tiles = k_out.ndim == 5
    if initial_state is None:
        state = jnp.zeros((batch, h, d_k, d_v), f32)
    else:
        state = initial_state.astype(f32)
    why_plain = _carry_refuses(w, u, k_out, q_in, scores, interpret)
    obs_trace.get_tracer().note_once(
        "delta_carry", chunk=size, chunks=nc, heads=h, d_k=d_k, d_v=d_v,
        decay="channel" if whole.ndim == 4 else "head",
        operands="tiles" if tiles else "rows", heads_a_step=h,
        state_bytes=4 * h * d_k * d_v,
        **(dict(path="kernel") if why_plain is None else dict(path="plain", why=why_plain)),
    )
    if why_plain is None:
        # the kernel's one decay is a key channel's: a head's is the same along
        # them. Its state lies [d_v, d_k], the channels along the lanes
        if whole.ndim == 3:
            whole = jnp.broadcast_to(whole[..., None], (*whole.shape, d_k))
        o, state = _carry_kernels(
            jnp.swapaxes(state, 2, 3), w, u, k_out, whole, q_in, scores, interpret
        )
        if tiles:
            o = jnp.swapaxes(o, 2, 3)
        o = o.reshape(batch, nc * size, h, d_v)[:, :t]
        return o, jnp.swapaxes(state, 2, 3)
    if tiles:
        k_out, q_in = jnp.swapaxes(k_out, 2, 3), jnp.swapaxes(q_in, 2, 3)
    else:
        k_out = k_out.reshape(nc, batch, size, h, d_k)
        q_in = q_in.reshape(batch, nc, size, h, d_k)
    states, new, state = carried_states(state, w, u, k_out, whole)
    # a cast of what is saved: the backward needs no copy of its own
    entering = jnp.moveaxis(states.astype(dtype), 0, 1)          # [b n h k v]
    new = jnp.moveaxis(new, 0, 1)                                # [b n h c v]
    inherited = jnp.einsum("bnchk,bnhkv->bnchv", q_in, entering, **dot)
    own = jnp.einsum("bnhcs,bnhsv->bnchv", scores, new, **dot)
    o = (inherited + own).reshape(batch, nc * size, h, d_v)[:, :t].astype(dtype)
    return o, state


# -- a decay for every key channel (Kimi delta attention) --------------------

def _halves(size):
    """The half-sizes of the halving form, largest first: ``size / 2, ..., 1``."""
    return [1 << b for b in reversed(range(size.bit_length() - 1))]


def _plain_pairs(q32, k32, gamma, dtype):
    """``sum_d a_i[d] k_j[d] exp(Gamma_i[d] - Gamma_j[d])`` for ``a`` = ``k``
    and ``a`` = ``q``, ``[b n h c s]`` each (``kk`` below the diagonal, the
    scores on and below it), **for any** ``Gamma`` **that does not grow along
    the steps**: the chunk is halved down to single steps, and a pair ``i > j``
    is had at the one level where ``i`` lies in the upper half and ``j`` in the
    lower half of the same block, under the reference ``Gamma`` at the lower
    half's last step. ``exp(Gamma_i - R)`` and ``exp(R - Gamma_j)`` are then
    both at most 1, whatever the decay; the diagonal has no decay at all.
    Operands ``[b n c h k]`` float32, rounded to ``dtype`` once decayed."""
    size = gamma.shape[2]
    step = jnp.arange(size)
    dot = dict(preferred_element_type=jnp.float32)
    kk = scores = 0.0
    for s in _halves(size):
        upper = (step & s) != 0
        at = jnp.where(upper, (step & ~(s - 1)) - 1, step | (s - 1))
        ref, up = gamma[:, :, at], upper[:, None, None]
        factor = jnp.exp(jnp.minimum(jnp.where(up, gamma - ref, ref - gamma), 0.0))
        k_rows = jnp.where(up, k32 * factor, 0.0).astype(dtype)
        q_rows = jnp.where(up, q32 * factor, 0.0).astype(dtype)
        k_cols = jnp.where(up, 0.0, k32 * factor).astype(dtype)
        same = (step[:, None] // (2 * s)) == (step[None, :] // (2 * s))
        kk += jnp.where(same, jnp.einsum("bnchk,bnshk->bnhcs", k_rows, k_cols, **dot), 0.0)
        scores += jnp.where(same, jnp.einsum("bnchk,bnshk->bnhcs", q_rows, k_cols, **dot), 0.0)
    own = jnp.einsum("bnchk,bnchk->bnhc", q32.astype(dtype), k32.astype(dtype), **dot)
    return kk, scores + own[..., None] * jnp.eye(size, dtype=jnp.float32)


def _local_plain(q, k, v, g, beta, size):
    """The chunk-local stage in plain ``jax.numpy``: everything of the rule
    that depends on one chunk of one head only. ``q``, ``k``, ``g`` ``[B, T, H,
    d_k]``, ``v`` ``[B, T, H, d_v]``, ``beta`` ``[B, T, H]``, ``T`` a multiple
    of ``size``; returns the carry's operands chunks first (``w`` ``[n b h c
    k]``, ``u`` ``[n b h c v]`` float32, ``k_out`` ``[n b c h k]``, ``whole``
    ``[n b h k]`` float32), the output stage's (``q_in`` ``[b n c h k]``, the
    causal-masked scores ``[b n h c s]``) and every chunk's float32 ``T`` ``[b
    n h c s]``. Holds for any ``g <= 0`` (:func:`_plain_pairs`)."""
    batch, steps, h, d_k = q.shape
    nc = steps // size
    f32, dtype = jnp.float32, q.dtype
    dot = dict(preferred_element_type=f32)

    # b batch, n chunk, c / s step in a chunk, h head, k key width, v value width
    q = q.reshape(batch, nc, size, h, d_k)
    k = k.reshape(batch, nc, size, h, d_k)
    v = v.reshape(batch, nc, size, h, v.shape[-1])
    beta = beta.astype(f32).reshape(batch, nc, size, h)
    gamma = jnp.cumsum(g.astype(f32).reshape(batch, nc, size, h, d_k), axis=2)

    k32, q32 = k.astype(f32), q.astype(f32)
    kk, scores = _plain_pairs(q32, k32, gamma, dtype)

    # inside a chunk: the system, its inverse, and what it makes of K and V
    lower = jnp.tril(jnp.ones((size, size), bool))
    beta_h = jnp.moveaxis(beta, 2, -1)                           # [b n h c]
    system = jnp.where(jnp.tril(lower, -1), beta_h[..., None] * kk, 0.0)
    exact = unit_lower_inverse(system)
    inverse = exact.astype(dtype)
    k_in = (k32 * (beta[..., None] * jnp.exp(gamma))).astype(dtype)
    v_in = (v.astype(f32) * beta[..., None]).astype(dtype)
    w = jnp.einsum("bnhcs,bnshk->bnhck", inverse, k_in, **dot).astype(dtype)
    u = jnp.einsum("bnhcs,bnshv->bnhcv", inverse, v_in, **dot)
    k_out = (k32 * jnp.exp(gamma[:, :, -1:] - gamma)).astype(dtype)
    whole = jnp.exp(gamma[:, :, -1])                             # [b n h k]
    q_in = (q32 * jnp.exp(gamma)).astype(dtype)
    chunks_first = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    return (
        *(chunks_first(a) for a in (w, u, k_out, whole)), q_in,
        jnp.where(lower, scores, 0.0).astype(dtype), exact,
    )


# -- the chunk-local stage as Pallas kernels ---------------------------------
#
# One grid step holds a chunk of every head in VMEM: the blocks are ``chunk``
# rows of the mixer's own ``[B, T, H d]`` arrays (a free reshape of ``[B, T, H,
# d]``; a head is ``d`` lanes of a row), so no neighbour transposes, and the
# heads are walked by a loop inside the body. Per head everything is a ``[C,
# d]`` or ``[C, C]`` tile: eight float32 registers' worth. (A scalar decay a
# step, ``gated_delta_rule``, has kernels of its own further down.)

_KERNEL_CHUNK = 64       # the chunk the kernels are written for


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _running_sum(a, reverse=False, axis=0, period=None):
    """The running sum of ``a`` ``[C, d]`` down its rows (``reverse``: up;
    ``axis`` 1: along each row; ``period``: from anew every so many, a power of
    two), by ``log2(C)`` shifted additions in float32 (Mosaic lowers no
    ``cumsum``)."""
    from jax.experimental.pallas import tpu as pltpu

    size, at = a.shape[axis], _iota(a.shape, axis)
    if period:
        at = at & (period - 1)
    s = 1
    while s < (period or size):
        if reverse:
            a = a + jnp.where(at < (period or size) - s, pltpu.roll(a, size - s, axis=axis), 0.0)
        else:
            a = a + jnp.where(at >= s, pltpu.roll(a, s, axis=axis), 0.0)
        s *= 2
    return a


def _times_transposed(a, b, **how):
    """``a b^T``, float32 accumulation."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, **how
    )


def _transposed_times(a, b, **how):
    """``a^T b``, float32 accumulation."""
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32, **how
    )


def _halved(gamma):
    """The halving form's factors of one tile, for any ``gamma`` ``[C, d]``
    that does not grow down the rows: a level a half-size ``s`` (``_halves``),
    ``(s, upper, factor)`` with ``upper`` the steps in the upper half of their
    block of ``2 s`` and ``factor`` ``exp(-|gamma - R|)``, ``R`` the running
    sum at the last step of the block's lower half (:func:`_plain_pairs`).
    ``R`` comes to every row by rolls: ``last`` holds ``gamma`` at the last step
    of a row's own block of ``s``, which a lower row reads in place and an
    upper row ``s`` rows up."""
    from jax.experimental.pallas import tpu as pltpu

    size, step = gamma.shape[0], _iota(gamma.shape, 0)
    lasts, last = {}, gamma
    for s in reversed(_halves(size)):                    # 1, 2, ..., C / 2
        lasts[s] = last
        if 2 * s < size:
            last = jnp.where((step & s) != 0, last, pltpu.roll(last, size - s, axis=0))
    levels = []
    for s in _halves(size):
        upper = (step & s) != 0
        ref = jnp.where(upper, pltpu.roll(lasts[s], s, axis=0), lasts[s])
        apart = jnp.where(upper, gamma - ref, ref - gamma)
        levels.append((s, upper, jnp.exp(jnp.minimum(apart, 0.0))))
    return levels


def _same_block(shape, s):
    """``[C, C]``: whether row and column lie in one block of ``2 s`` steps."""
    bits = s.bit_length()
    return (_iota(shape, 0) >> bits) == (_iota(shape, 1) >> bits)


def _halved_pairs(levels, rows32, k32, dtype):
    """``[C, C]``, below the diagonal: ``sum_d rows_i[d] k_j[d] exp(Gamma_i[d]
    - Gamma_j[d])`` of one tile by the halving form (``rows32`` the keys
    themselves for ``kk``, the queries for the scores): a level's upper rows
    against its lower columns, kept inside the blocks of ``2 s``."""
    pairs = jnp.zeros((k32.shape[0],) * 2, jnp.float32)
    for s, upper, factor in levels:
        rows = jnp.where(upper, rows32 * factor, 0.0).astype(dtype)
        k_cols = jnp.where(upper, 0.0, k32 * factor).astype(dtype)
        pairs = pairs + jnp.where(
            _same_block(pairs.shape, s), _times_transposed(rows, k_cols), 0.0
        )
    return pairs


def _on_diagonal(tile, column):
    """``tile`` ``[C, C]`` with ``column`` ``[C, 1]`` on its diagonal."""
    return jnp.where(_iota(tile.shape, 0) == _iota(tile.shape, 1), column, tile)


def _halved_back(levels, k32, q32, d_kk, d_scores, dtype):
    """Through :func:`_halved_pairs`: from the cotangents of ``kk`` (below the
    diagonal) and of the scores (on and below it) to ``(dq, dk, dgamma, kk)``,
    ``kk`` made again for ``dbeta``. A level's two products go back as two more;
    a factor's cotangent goes to ``gamma`` directly and, with the other sign,
    to the level's reference, which the rolls of :func:`_halved` carried: they
    are undone in the opposite order."""
    from jax.experimental.pallas import tpu as pltpu

    size, step = k32.shape[0], _iota(k32.shape, 0)
    kk = jnp.zeros((size, size), jnp.float32)
    d_q = d_k = d_gamma = jnp.zeros_like(k32)
    to_last = {}
    for s, upper, factor in levels:
        k_dec, q_dec = k32 * factor, q32 * factor
        k_rows = jnp.where(upper, k_dec, 0.0).astype(dtype)
        q_rows = jnp.where(upper, q_dec, 0.0).astype(dtype)
        k_cols = jnp.where(upper, 0.0, k_dec).astype(dtype)
        same = _same_block(kk.shape, s)
        kk = kk + jnp.where(same, _times_transposed(k_rows, k_cols), 0.0)
        both = jnp.concatenate(
            [jnp.where(same, d_kk, 0.0), jnp.where(same, d_scores, 0.0)], axis=0
        ).astype(dtype)                                                  # [2 C, C]
        d_rows = jnp.dot(both, k_cols, preferred_element_type=jnp.float32)
        d_cols = _transposed_times(both, jnp.concatenate([k_rows, q_rows], axis=0))
        d_k_dec = jnp.where(upper, d_rows[:size], d_cols)
        d_q_dec = jnp.where(upper, d_rows[size:], 0.0)
        d_k, d_q = d_k + d_k_dec * factor, d_q + d_q_dec * factor
        # the exponent: gamma - R in the upper half, R - gamma in the lower
        d_apart = (d_k_dec * k_dec + d_q_dec * q_dec)
        direct = jnp.where(upper, d_apart, -d_apart)
        d_gamma = d_gamma + direct
        to_last[s] = (
            jnp.where(upper, 0.0, -direct)
            + pltpu.roll(jnp.where(upper, -direct, 0.0), size - s, axis=0)
        )
    d_last = None
    for s in _halves(size):                              # C / 2, ..., 1
        if d_last is None:
            d_last = to_last[s]
        else:
            d_last = to_last[s] + jnp.where(
                (step & s) != 0, d_last + pltpu.roll(d_last, s, axis=0), 0.0
            )
    own = jnp.sum(_on_diagonal(jnp.zeros_like(d_scores), d_scores), axis=1, keepdims=True)
    return d_q + own * k32, d_k + own * q32, d_gamma + d_last, kk


def _solve(system, rounds=None):
    """``unit_lower_inverse``'s rounds on one ``[C, C]`` float32 tile whose
    strict lower triangle is the system (the rest is masked away here). The
    first round, ``I - I A_1 I``, is taken without its two products. Fewer
    ``rounds`` leave diagonal blocks of ``1 << rounds`` rows inverted, each
    for itself: all there is to a tile that is zero outside them."""
    row, col = _iota(system.shape, 0), _iota(system.shape, 1)
    inverse = None
    for shift in range(rounds or system.shape[0].bit_length() - 1):  # blocks of 1 << shift rows
        joins = ((row >> (shift + 1)) == (col >> (shift + 1))) & ((row >> shift) > (col >> shift))
        joined = jnp.where(joins, system, 0.0)
        if inverse is None:
            inverse = jnp.where(row == col, 1.0, 0.0) - joined
        else:
            inverse = inverse - _exact(_exact(inverse, joined), inverse)
    return inverse


def _head_of(h, width):
    """The lanes of head ``h`` in a row of ``heads * width``; ``h`` the loop's
    index or, in an odd tail, a number."""
    from jax.experimental import pallas as pl

    start = h * width
    return pl.ds(start if isinstance(h, int) else pl.multiple_of(start, 128), width)


def _column_of(betas, h):
    """Head ``h``'s column ``[C, 1]`` of ``betas`` ``[C, H]``."""
    return jnp.sum(jnp.where(_iota(betas.shape, 1) == h, betas, 0.0), axis=1, keepdims=True)


def _over_heads(heads, body, init=0, width=2):
    """``body(pair, half, carry)`` for every head ``2 * pair + half`` in turn,
    a pair to a round of the loop: the scheduler may interleave the two bodies
    (Mosaic's own ``unroll`` is all or nothing), and ``half`` is static. With
    ``width``, that many heads a round: head ``width * pair + half``. Heads
    that fill no round (an odd last one) come after the loop, ``pair`` then a
    number and not the loop's index."""

    def together(pair, carry):
        for half in range(width):
            carry = body(pair, half, carry)
        return carry

    carry = jax.lax.fori_loop(0, heads // width, together, init)
    for half in range(heads % width):
        carry = body(heads // width, half, carry)
    return carry


def _inverse_at(inverse_ref, pair, half):
    """Where head ``2 * pair + half``'s ``T`` ``[C, C]`` lies in a block ``[1,
    1, H / 2, C, 2 C]``: a pair of heads side by side along the lanes, so the
    float32 array every layer saves for its backward has no lane of padding."""
    size = inverse_ref.shape[3]
    return 0, 0, pair, slice(None), slice(half * size, (half + 1) * size)


def kda_inverse_kernel(k_ref, g_ref, beta_ref, inverse_ref):
    """Every head's ``T = (I + A)^-1`` of one chunk, float32 ``[H / 2, C, 2
    C]``: a pair of heads a row."""
    f32 = jnp.float32
    heads = beta_ref.shape[2]
    d_k = k_ref.shape[2] // heads
    betas = beta_ref[0]

    def head(pair, half, carry):
        h = 2 * pair + half
        lanes = _head_of(h, d_k)
        levels = _halved(_running_sum(g_ref[0, :, lanes]))
        k32 = k_ref[0, :, lanes].astype(f32)
        kk = _halved_pairs(levels, k32, k32, k_ref.dtype)
        inverse_ref[_inverse_at(inverse_ref, pair, half)] = _solve(_column_of(betas, h) * kk)
        return carry

    _over_heads(heads, head)


def kda_operands_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, inverse_ref,
                        w_ref, u_ref, k_out_ref, whole_ref, q_in_ref, scores_ref):
    """From one chunk's inputs and ``T`` to the carry's operands (``w``, ``u``,
    ``k_out``, ``whole``) and the output stage's (``q_in``, the masked scores),
    each written where its reader takes it."""
    from jax.experimental import pallas as pl

    f32, dtype = jnp.float32, q_ref.dtype
    heads = beta_ref.shape[2]
    d_k, d_v = k_ref.shape[2] // heads, v_ref.shape[2] // heads
    betas = beta_ref[0]
    dot = functools.partial(jnp.dot, preferred_element_type=f32)

    def head(pair, half, carry):
        h = 2 * pair + half
        keys, values = _head_of(h, d_k), _head_of(h, d_v)
        gamma = _running_sum(g_ref[0, :, keys])
        q32, k32 = q_ref[0, :, keys].astype(f32), k_ref[0, :, keys].astype(f32)
        beta, grown = _column_of(betas, h), jnp.exp(gamma)
        # the diagonal, which no decay touches, is q . k a row
        scores = _on_diagonal(
            _halved_pairs(_halved(gamma), q32, k32, dtype),
            jnp.sum(q32 * k32, axis=1, keepdims=True),
        )
        lower = _iota(scores.shape, 0) >= _iota(scores.shape, 1)
        scores_ref[0, 0, h] = jnp.where(lower, scores, 0.0).astype(dtype)
        inverse = inverse_ref[_inverse_at(inverse_ref, pair, half)].astype(dtype)
        k_in = (k32 * (beta * grown)).astype(dtype)
        v_in = (v_ref[0, :, values].astype(f32) * beta).astype(dtype)
        w_ref[0, 0, h] = dot(inverse, k_in).astype(dtype)
        u_ref[0, 0, h] = dot(inverse, v_in)
        k_out_ref[0, 0, :, keys] = (k32 * jnp.exp(gamma[-1:] - gamma)).astype(dtype)
        whole_ref[0, 0, pl.ds(h, 1), :] = jnp.exp(gamma[-1:])
        q_in_ref[0, :, keys] = (q32 * grown).astype(dtype)
        return carry

    _over_heads(heads, head)


def kda_backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, inverse_ref,
                        dw_ref, du_ref, dk_out_ref, dwhole_ref, dq_in_ref, dscores_ref,
                        dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref):
    """The cotangents of one chunk's inputs from those of the six operands:
    the tile's intermediates are made again in VMEM from the inputs and the
    saved ``T``, and every step of the way back is local to the tile."""
    from jax.experimental import pallas as pl

    f32, dtype = jnp.float32, q_ref.dtype
    heads = beta_ref.shape[2]
    d_k, d_v = k_ref.shape[2] // heads, v_ref.shape[2] // heads
    size = q_ref.shape[1]
    betas = beta_ref[0]
    down = lambda a: jnp.sum(a, axis=0, keepdims=True)      # noqa: E731 — [1, d]
    across = lambda a: jnp.sum(a, axis=1, keepdims=True)    # noqa: E731 — [C, 1]

    def head(pair, half, d_betas):
        h = 2 * pair + half
        keys, values = _head_of(h, d_k), _head_of(h, d_v)
        gamma = _running_sum(g_ref[0, :, keys])
        q32, k32 = q_ref[0, :, keys].astype(f32), k_ref[0, :, keys].astype(f32)
        v32 = v_ref[0, :, values].astype(f32)
        beta, grown = _column_of(betas, h), jnp.exp(gamma)
        to_end = jnp.exp(gamma[-1:] - gamma)
        exact = inverse_ref[_inverse_at(inverse_ref, pair, half)]
        inverse = exact.astype(dtype)
        k_in = (k32 * (beta * grown)).astype(dtype)
        v_in = (v32 * beta).astype(dtype)

        # through w = T k_in and u = T v_in
        dw, du = dw_ref[0, 0, h], du_ref[0, 0, h].astype(dtype)
        d_inverse = _times_transposed(dw, k_in) + _times_transposed(du, v_in)
        d_k_in, d_v_in = _transposed_times(inverse, dw), _transposed_times(inverse, du)

        # through the solve, as ``_unit_lower_inverse_bwd``: dA = -T^T dT T^T
        highest = dict(precision=jax.lax.Precision.HIGHEST)
        d_system = -_times_transposed(
            _transposed_times(exact, d_inverse, **highest), exact, **highest
        )
        row, col = _iota(d_system.shape, 0), _iota(d_system.shape, 1)
        d_system = jnp.where(row > col, d_system, 0.0)
        d_kk = d_system * beta
        d_scores = jnp.where(row >= col, dscores_ref[0, 0, h].astype(f32), 0.0)

        d_queries, d_keys, d_gamma, kk = _halved_back(
            _halved(gamma), k32, q32, d_kk, d_scores, dtype
        )
        d_beta = across(d_system * kk)
        step = _iota(gamma.shape, 0)

        # through the elementwise operands
        dk_out, dq_in = dk_out_ref[0, 0, :, keys].astype(f32), dq_in_ref[0, :, keys].astype(f32)
        leaving = dk_out * k32 * to_end
        d_last = down(leaving) + dwhole_ref[0, 0, pl.ds(h, 1), :] * jnp.exp(gamma[-1:])
        d_gamma = (
            d_gamma + d_k_in * k32 * (beta * grown) - leaving + dq_in * q32 * grown
            + jnp.where(step == size - 1, d_last, 0.0)
        )
        dq_ref[0, :, keys] = (d_queries + dq_in * grown).astype(dtype)
        dk_ref[0, :, keys] = (
            d_keys + d_k_in * (beta * grown) + dk_out * to_end
        ).astype(dtype)
        dv_ref[0, :, values] = (d_v_in * beta).astype(dtype)
        dg_ref[0, :, keys] = _running_sum(d_gamma, reverse=True)
        d_beta = d_beta + across(d_k_in * k32 * grown) + across(d_v_in * v32)
        return jnp.where(_iota(d_betas.shape, 1) == h, d_beta, d_betas)

    dbeta_ref[0] = _over_heads(heads, head, jnp.zeros(betas.shape, f32))


def _chunk_call(kernel, grid, kinds, ins, outs, operands, interpret, scratch=(),
                walk=False):
    """``kernel`` as one Pallas call over ``grid`` = (batch, chunks), every
    grid step independent, or with ``walk`` a batch row's chunks one after
    another in the grid's order (``"arbitrary"``), the ``scratch`` kept from
    one to the next: ``kinds`` gives a kind of operand its shape, dtype,
    block and the block's place at batch ``b``, chunk ``n`` (its leading block
    indices; the rest are zeros), ``ins`` and ``outs`` name the kinds of
    ``operands`` and of the results, ``scratch`` the body's VMEM arrays (shape,
    dtype). The call bears the kernel's name less ``_kernel``; its VMEM limit
    is reckoned from the blocks. Shared with ``ops/ssd.py``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def spec(kind):
        _, _, block, place = kinds[kind]

        def index(b, n):
            at = place(b, n)
            return at + (0,) * (len(block) - len(at))

        return pl.BlockSpec(block, index)

    def padded(shape, dt):  # bytes in VMEM, rows padded to whole lane tiles
        return math.prod(shape[:-1]) * -(-shape[-1] // 128) * 128 * jnp.dtype(dt).itemsize

    def held(kind):
        _, dt, block, _ = kinds[kind]
        return padded(block, dt)

    name = kernel.__name__.removesuffix("_kernel")
    call = pl.pallas_call(
        kernel, name=name, grid=grid,
        in_specs=[spec(kind) for kind in ins], out_specs=[spec(kind) for kind in outs],
        out_shape=[jax.ShapeDtypeStruct(*kinds[kind][:2]) for kind in outs],
        scratch_shapes=[pltpu.VMEM(shape, dt) for shape, dt in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary" if walk else "parallel"),
            # every block twice (the pipeline's two buffers), the scratch, and
            # room for the body's own tiles
            vmem_limit_bytes=2 * sum(held(kind) for kind in (*ins, *outs))
            + sum(padded(shape, dt) for shape, dt in scratch) + (16 << 20),
        ),
        interpret=interpret,
    )
    with obs_trace.span("kernel_trace", kernel=name):
        return tuple(call(*operands))


_INPUTS = ("keys", "keys", "values", "decays", "beta")                 # q k v g beta
_OPERANDS = ("w", "u", "k_out", "whole", "keys", "scores")             # ..., q_in, scores


def _run(kernel, ins, outs, operands, interpret):
    """One of the three kernels on ``operands``, whose kinds ``ins`` names
    (``outs`` those of its results): a grid step a chunk, every block a chunk
    of every head."""
    batch, steps, h = operands[ins.index("beta")].shape
    size, nc = _KERNEL_CHUNK, steps // _KERNEL_CHUNK
    keys = operands[ins.index("keys")]
    values = operands[ins.index("values")] if "values" in ins else keys
    d_k, d_v = keys.shape[2] // h, values.shape[2] // h
    f32, dtype = jnp.float32, keys.dtype
    # a kind's shape, dtype, block and the block's place at batch b, chunk n
    here, first = (lambda b, n: (b, n)), (lambda b, n: (n, b))  # noqa: E731
    kinds = dict(
        keys=((batch, steps, h * d_k), dtype, (1, size, h * d_k), here),
        values=((batch, steps, h * d_v), dtype, (1, size, h * d_v), here),
        decays=((batch, steps, h * d_k), f32, (1, size, h * d_k), here),
        beta=((batch, steps, h), f32, (1, size, h), here),
        # every chunk's T, a pair of heads a row; the scores [b n h c s]
        inverse=((batch, nc, h // 2, size, 2 * size), f32, (1, 1, h // 2, size, 2 * size), here),
        scores=((batch, nc, h, size, size), dtype, (1, 1, h, size, size), here),
        # the carry's operands, chunks first
        w=((nc, batch, h, size, d_k), dtype, (1, 1, h, size, d_k), first),
        u=((nc, batch, h, size, d_v), f32, (1, 1, h, size, d_v), first),
        k_out=((nc, batch, size, h * d_k), dtype, (1, 1, size, h * d_k), first),
        whole=((nc, batch, h, d_k), f32, (1, 1, h, d_k), first),
    )
    return _chunk_call(kernel, (batch, nc), kinds, ins, outs, operands, interpret)


# jitted, as ``ops/causal_conv.py``'s: a step traces and lowers each body once
@functools.partial(jax.jit, static_argnums=3)
def _inverse_call(k, g, beta, interpret):
    ins = ("keys", "decays", "beta")
    return _run(kda_inverse_kernel, ins, ("inverse",), (k, g, beta), interpret)[0]


@functools.partial(jax.jit, static_argnums=6)
def _operands_call(q, k, v, g, beta, inverse, interpret):
    ins = (*_INPUTS, "inverse")
    return _run(kda_operands_kernel, ins, _OPERANDS, (q, k, v, g, beta, inverse), interpret)


@functools.partial(jax.jit, static_argnums=12)
def _backward_call(q, k, v, g, beta, inverse, dw, du, dk_out, dwhole, dq_in, dscores, interpret):
    ins = (*_INPUTS, "inverse", *_OPERANDS)
    operands = (q, k, v, g, beta, inverse, dw, du, dk_out, dwhole, dq_in, dscores)
    return _run(kda_backward_kernel, ins, _INPUTS, operands, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _local_kernels(q, k, v, g, beta, interpret):
    """The chunk-local stage by the kernels, for ``q``, ``k``, ``g`` ``[B, T, H
    d_k]``, ``v`` ``[B, T, H d_v]`` and ``beta`` ``[B, T, H]`` (float32 ``g``
    and ``beta``): ``(w, u, k_out, whole, q_in, scores)`` as ``_local_plain``
    lays them out, ``k_out`` and ``q_in`` with a row's heads side by side."""
    inverse = _inverse_call(k, g, beta, interpret)
    return _operands_call(q, k, v, g, beta, inverse, interpret)


def _local_kernels_fwd(q, k, v, g, beta, interpret):
    # T apart from the rest and by name, as ``_unit_lower_inverse_fwd``: a
    # policy that saves it spares the recomputation the solve, which is most
    # of the stage's arithmetic, and the backward reads it
    inverse = checkpoint_name(_inverse_call(k, g, beta, interpret), INVERSE_NAME)
    operands = _operands_call(q, k, v, g, beta, inverse, interpret)
    return operands, (q, k, v, g, beta, inverse)


def _local_kernels_bwd(interpret, residuals, cotangents):
    return _backward_call(*residuals, *cotangents, interpret)


_local_kernels.defvjp(_local_kernels_fwd, _local_kernels_bwd)


def _kernels_refuse(q, k, v, steps, chunk, interpret, along_lanes=False):
    """Why the chunk-local stage of these operands is not the kernels', or
    None where it is: the first of a TPU backend or the interpreter
    (``backend``), bfloat16 operands (``dtype``), the kernels' chunk
    (``chunk``), a length of whole grid steps (``steps``: a chunk; two, a lane
    tile, for the scalar rule's kernels, which hold the steps ``along_lanes``)
    and a head that tiles (``heads_odd``, ``width``) that does not hold. With
    the heads along the lanes (``kda_rule``) they come in pairs of whole lane
    tiles; along the sublanes a head is any multiple of a packed bfloat16
    tile's 16 rows, and any count of them."""
    tile = 16 if along_lanes else 128
    conditions = (
        ("backend", interpret or jax.default_backend() == "tpu"),
        ("dtype", q.dtype == k.dtype == v.dtype == jnp.bfloat16),
        ("chunk", chunk == _KERNEL_CHUNK),
        ("steps", steps % (_LANE_STEPS if along_lanes else chunk) == 0),
        ("heads_odd", along_lanes or q.shape[2] % 2 == 0),
        ("width", q.shape[-1] % tile == 0 and v.shape[-1] % tile == 0),
    )
    return next((why for why, met in conditions if not met), None)


# -- the carry and the output stage: one walk over the chunks ----------------
#
# What ``carried_states`` and the two products after it do, as one Pallas call
# forward and one backward: the grid is (batch, chunks) with the chunks in
# order (``_chunk_call``'s ``walk``), every head's float32 state stays in a
# VMEM scratch from one chunk to the next, and a grid step reads one chunk's
# six operands where the chunk-local stage wrote them and writes the chunk's
# ``o``, ``V_new`` and the state it inherited (the backward's residual). The
# state lies transposed, ``[d_v, d_k]``: the decay of a key channel is then a
# row along the lanes, as ``whole``'s blocks hold it, and so is its cotangent
# ``<dS, S>``. One kernel for both rules: a decay a head comes broadcast along
# the channels, and a head's tile is taken by the block's rank
# (:func:`_tile_of`). The heads are walked by number, each tile at a place the
# compiler knows (PR 62's probe: Solar's 8 heads as one round of 8 read 0.28 ms
# a call where two rounds of 4, a head's lanes at a dynamic start, read 0.51);
# more than ``_CARRY_HEADS`` in rounds of 8 (:func:`_over_heads`).

_CARRY_HEADS = 16        # the most heads a step of the walk takes by number


def _each_head(heads, body):
    """``body(h)`` for every head of a step of the walk."""
    if heads <= _CARRY_HEADS:
        for h in range(heads):
            body(h)
        return

    def of_a_round(group, half, carry):
        body(8 * group + half)
        return carry

    _over_heads(heads, of_a_round, width=8)


def _tile_of(ref, h, width):
    """Where head ``h``'s ``[C, width]`` tile lies in a block: a tile a head
    (five axes, ``[1, 1, H, C, width]``) or the head's lanes of rows whose
    heads lie side by side (``[.., C, H width]``)."""
    if len(ref.shape) == 5:
        return 0, 0, h
    return (0,) * (len(ref.shape) - 2) + (slice(None), _head_of(h, width))


def delta_carry_kernel(w_ref, u_ref, k_out_ref, whole_ref, q_in_ref, scores_ref, initial_ref,
                       o_ref, new_ref, entering_ref, final_ref, state_ref):
    """One chunk of the walk, every head: ``V_new = U - W S`` rounded to the
    operands' dtype, ``o = q_in S + scores V_new``, ``S <- whole o S + k_out^T
    V_new``, with ``S`` ``[H, d_v, d_k]`` float32 in ``state_ref`` from the
    chunk before (the initial state at the first) and rounded once for its
    two products; the state the chunk inherits is written as it is."""
    from jax.experimental import pallas as pl

    f32, dtype = jnp.float32, w_ref.dtype
    heads, size, d_k = w_ref.shape[2:]
    d_v = u_ref.shape[-1]
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _():
        state_ref[...] = initial_ref[0]

    def head(h):
        state = state_ref[h]
        entering_ref[0, 0, h] = state
        held = state.astype(dtype)
        k_out, q_in = k_out_ref[_tile_of(k_out_ref, h, d_k)], q_in_ref[_tile_of(q_in_ref, h, d_k)]
        # W S and q_in S as one product of 2 C rows against the state
        both = _times_transposed(jnp.concatenate([w_ref[0, 0, h], q_in], axis=0), held)
        new = (u_ref[0, 0, h] - both[:size]).astype(dtype)
        new_ref[0, 0, h] = new
        own = jnp.dot(scores_ref[0, 0, h], new, preferred_element_type=f32)
        o_ref[_tile_of(o_ref, h, d_v)] = (both[size:] + own).astype(dtype)
        state_ref[h] = whole_ref[0, 0, pl.ds(h, 1), :] * state + _transposed_times(new, k_out)

    _each_head(heads, head)

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        final_ref[0] = state_ref[...]


def delta_carry_back_kernel(w_ref, k_out_ref, whole_ref, q_in_ref, scores_ref, entering_ref,
                            new_ref, do_ref, dfinal_ref,
                            dw_ref, du_ref, dk_out_ref, dwhole_ref, dq_in_ref, dscores_ref,
                            dinitial_ref, dstate_ref):
    """One chunk of the walk back, every head, the chunks last to first: from
    ``d o`` and the cotangent ``dS'`` of the state the chunk left
    (``dstate_ref``, the final state's at the first step) to the six
    operands' cotangents and ``dS`` of the state it inherited, what
    ``_carried_states_bwd`` and the two products' transposes return. Each
    product's operand is rounded once to the operands' dtype; ``d u``, ``d
    whole`` (``<dS', S>`` a channel) and ``dS`` are float32."""
    from jax.experimental import pallas as pl

    f32, dtype = jnp.float32, w_ref.dtype
    heads, size, d_k = w_ref.shape[2:]
    d_v = new_ref.shape[-1]
    n = pl.program_id(1)
    dot = functools.partial(jnp.dot, preferred_element_type=f32)

    @pl.when(n == 0)
    def _():
        dstate_ref[...] = dfinal_ref[0]

    def head(h):
        state, leaving = entering_ref[0, 0, h], dstate_ref[h]           # [d_v, d_k]
        held, passed = state.astype(dtype), leaving.astype(dtype)
        k_out, q_in = k_out_ref[_tile_of(k_out_ref, h, d_k)], q_in_ref[_tile_of(q_in_ref, h, d_k)]
        new, d_o = new_ref[0, 0, h], do_ref[_tile_of(do_ref, h, d_v)]
        # V_new fed the chunk's own outputs and the state it left
        d_new = _transposed_times(scores_ref[0, 0, h], d_o) + _times_transposed(k_out, passed)
        du_ref[0, 0, h] = d_new
        dscores_ref[0, 0, h] = _times_transposed(d_o, new).astype(dtype)
        fed = jnp.concatenate([(-d_new).astype(dtype), d_o], axis=0)    # [2 C, d_v]
        both = dot(fed, held)                                           # d w | d q_in
        dw_ref[0, 0, h] = both[:size].astype(dtype)
        dq_in_ref[_tile_of(dq_in_ref, h, d_k)] = both[size:].astype(dtype)
        dk_out_ref[_tile_of(dk_out_ref, h, d_k)] = dot(new, passed).astype(dtype)
        dwhole_ref[0, 0, pl.ds(h, 1), :] = jnp.sum(leaving * state, axis=0, keepdims=True)
        dstate_ref[h] = whole_ref[0, 0, pl.ds(h, 1), :] * leaving + _transposed_times(
            fed, jnp.concatenate([w_ref[0, 0, h], q_in], axis=0)
        )

    _each_head(heads, head)

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        dinitial_ref[0] = dstate_ref[...]


def _carry_run(kernel, ins, outs, operands, interpret, back=False):
    """One of the walk's two kernels on ``operands``, whose kinds ``ins`` names
    (``outs`` those of its results): a grid step a chunk of every head, the
    chunks first to last or, ``back``, last to first."""
    nc, batch, h, size, d_k = operands[ins.index("w")].shape
    d_v = operands[ins.index("new" if "new" in ins else "u")].shape[-1]
    f32, dtype = jnp.float32, operands[ins.index("w")].dtype
    at = (lambda n: nc - 1 - n) if back else (lambda n: n)  # noqa: E731
    here, first = (lambda b, n: (b, at(n))), (lambda b, n: (at(n), b))  # noqa: E731
    once = lambda b, n: (b,)  # noqa: E731 — a batch row's, whatever the chunk
    by_head = lambda d, dt: ((nc, batch, h, size, d), dt, (1, 1, h, size, d), first)  # noqa: E731
    if operands[ins.index("k_out")].ndim == 5:   # a tile a head
        k_out = by_head(d_k, dtype)
        q_in, o = (
            ((batch, nc, h, size, d), dtype, (1, 1, h, size, d), here) for d in (d_k, d_v)
        )
    else:                                        # a row's heads side by side
        k_out = ((nc, batch, size, h * d_k), dtype, (1, 1, size, h * d_k), first)
        q_in, o = (
            ((batch, nc * size, h * d), dtype, (1, size, h * d), here) for d in (d_k, d_v)
        )
    kinds = dict(
        w=by_head(d_k, dtype), u=by_head(d_v, f32), new=by_head(d_v, dtype),
        k_out=k_out, q_in=q_in, o=o,
        whole=((nc, batch, h, d_k), f32, (1, 1, h, d_k), first),
        scores=((batch, nc, h, size, size), dtype, (1, 1, h, size, size), here),
        # the states, transposed: the ones the chunks inherit, a batch row's one
        entering=((nc, batch, h, d_v, d_k), f32, (1, 1, h, d_v, d_k), first),
        state=((batch, h, d_v, d_k), f32, (1, h, d_v, d_k), once),
    )
    return _chunk_call(
        kernel, (batch, nc), kinds, ins, outs, operands, interpret,
        scratch=(((h, d_v, d_k), f32),), walk=True,
    )


_CARRY_OPERANDS = ("w", "u", "k_out", "whole", "q_in", "scores")
_CARRY_KEPT = ("w", "k_out", "whole", "q_in", "scores", "entering", "new")


# jitted, as the chunk-local stage's: a step traces and lowers each body once
@functools.partial(jax.jit, static_argnums=7)
def _carry_call(w, u, k_out, whole, q_in, scores, state, interpret):
    ins, outs = (*_CARRY_OPERANDS, "state"), ("o", "new", "entering", "state")
    operands = (w, u, k_out, whole, q_in, scores, state)
    return _carry_run(delta_carry_kernel, ins, outs, operands, interpret)


@functools.partial(jax.jit, static_argnums=9)
def _carry_back_call(w, k_out, whole, q_in, scores, entering, new, d_o, d_final, interpret):
    ins, outs = (*_CARRY_KEPT, "o", "state"), (*_CARRY_OPERANDS, "state")
    operands = (w, k_out, whole, q_in, scores, entering, new, d_o, d_final)
    return _carry_run(delta_carry_back_kernel, ins, outs, operands, interpret, back=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _carry_kernels(state, w, u, k_out, whole, q_in, scores, interpret):
    """The carry and the output stage by the walk: every chunk's ``o`` (laid
    out as ``q_in`` is, ``d_v`` wide) and the final state, from the initial
    ``state`` ``[b h v k]`` (float32, transposed, as the final one) and the
    six operands as :func:`_carried_outputs` takes them, ``whole`` ``[n b h
    k]``."""
    o, _, _, final = _carry_call(w, u, k_out, whole, q_in, scores, state, interpret)
    return o, final


def _carry_kernels_fwd(state, w, u, k_out, whole, q_in, scores, interpret):
    # by name, as ``_carried_states_fwd``: with the states the chunks inherit,
    # ``V_new`` and the final state saved (and ``o``, which the caller names),
    # nothing is read of the walk that a recomputation would have to run it for
    o, new, entering, final = _carry_call(w, u, k_out, whole, q_in, scores, state, interpret)
    new, entering, final = (checkpoint_name(a, CARRY_NAME) for a in (new, entering, final))
    return (o, final), (w, k_out, whole, q_in, scores, entering, new)


def _carry_kernels_bwd(interpret, residuals, cotangents):
    *d_operands, d_state = _carry_back_call(*residuals, *cotangents, interpret)
    return (d_state, *d_operands)


_carry_kernels.defvjp(_carry_kernels_fwd, _carry_kernels_bwd)


def _carry_refuses(w, u, k_out, q_in, scores, interpret):
    """Why the carry of these operands is not the walk's kernels, or None
    where it is: the first of a TPU backend or the interpreter (``backend``),
    bfloat16 operands beside a float32 ``u`` (``dtype``), the kernels' chunk
    (``chunk``), widths that tile (``width``: whole lane tiles where a row's
    heads lie side by side, a packed bfloat16 tile's 16 rows where a head has
    a tile of its own) and every head's state, ten times over for the blocks
    that hold it, within half of VMEM (``state``) that does not hold."""
    _, _, h, size, d_k = w.shape
    d_v = u.shape[-1]
    tile = 16 if k_out.ndim == 5 else 128
    conditions = (
        ("backend", interpret or jax.default_backend() == "tpu"),
        ("dtype", u.dtype == jnp.float32 and all(
            a.dtype == jnp.bfloat16 for a in (w, k_out, q_in, scores)
        )),
        ("chunk", size == _KERNEL_CHUNK),
        ("width", d_k % tile == 0 and d_v % tile == 0),
        ("state", 40 * h * d_k * d_v <= 64 << 20),
    )
    return next((why for why, met in conditions if not met), None)


# -- a decay a step: the scalar rule's chunk-local stage as kernels ----------
#
# The same frame and the same three roles, **time along the lanes**: the
# mixer's convolution leaves ``[q | k | v]`` with the steps minor, XLA keeps
# them so through the norms, and the kernels read them so (``[B, H d, T]``; a
# head is ``d`` sublanes at ``h d``, whatever ``d`` a multiple of 16 and
# however many heads: no lane tile to fit, nothing padded, nothing
# transposed on the way in or on the way back). A grid step is a lane tile
# of steps, two chunks of 64, of every head; the two are worked as one
# ``[128, 128]`` tile that is zero outside its two diagonal blocks, so every
# product is the MXU's own size. With one ``gamma`` a step and head,
# ``exp(gamma_i - gamma_j)`` factors out of the contraction over the key
# channels: a tile is one ``K K^T`` and one ``Q K^T`` under a mask of ``exp``
# of a difference that is never positive, where the halving form pays six
# ``exp`` and six masked products. ``g`` and ``beta`` are ``[H, 128]`` blocks:
# the running sum of every head at once.

_LANE_STEPS = 2 * _KERNEL_CHUNK   # steps a grid step: a lane tile, two chunks


def _rows_of(h, width):
    """The sublanes of head ``h`` in a block of ``heads * width`` rows; ``h``
    the loop's index or, in an odd tail, a number."""
    from jax.experimental import pallas as pl

    start = h * width
    return pl.ds(start if isinstance(h, int) else pl.multiple_of(start, math.gcd(width, 128)), width)


def _row_of(rows, h):
    """Head ``h``'s row ``[1, L]`` of ``rows`` ``[H, L]``."""
    return jnp.sum(jnp.where(_iota(rows.shape, 0) == h, rows, 0.0), axis=0, keepdims=True)


def _turned(vector):
    """A row ``[1, L]`` as the column ``[L, 1]``, a column as the row: off the
    diagonal of a tile, exactly."""
    size = max(vector.shape)
    on = _iota((size, size), 0) == _iota((size, size), 1)
    return jnp.sum(jnp.where(on, vector, 0.0), axis=int(vector.shape[0] == 1), keepdims=True)


def _same_chunk(shape):
    """``[L, L]``: whether row and column are steps of one chunk."""
    bits = _KERNEL_CHUNK.bit_length() - 1
    return (_iota(shape, 0) >> bits) == (_iota(shape, 1) >> bits)


def _decay_between(gamma):
    """``[L, L]``: ``exp(gamma_i - gamma_j)`` for a step ``i`` on or after
    ``j`` in ``j``'s chunk, zeros elsewhere, for a head's running sums
    ``gamma`` ``[1, L]`` (each chunk's from its own first step)."""
    shape = (gamma.shape[1],) * 2
    live = _same_chunk(shape) & (_iota(shape, 0) >= _iota(shape, 1))
    return jnp.exp(jnp.where(live, _turned(gamma) - gamma, -jnp.inf))


def _folded(tile):
    """A ``[2 C, 2 C]`` tile that is zero outside its two diagonal blocks as
    ``[C, 2 C]``, the blocks side by side: no lane of padding in what a layer
    saves. :func:`_unfolded` is the way back."""
    return tile[:_KERNEL_CHUNK] + tile[_KERNEL_CHUNK:]


def _unfolded(blocks):
    tile = jnp.concatenate([blocks, blocks], axis=0)
    return jnp.where(_same_chunk(tile.shape), tile, 0.0)


def _decays(g_ref):
    """Every head's running sum of ``g`` over each chunk and what the operands
    take of it, ``[H, L]`` each: ``gamma``, ``exp(gamma)``, ``exp(gamma_C -
    gamma)``; and ``exp(gamma_C)`` of the two chunks, ``[H, 1]`` each."""
    gammas = _running_sum(g_ref[0], axis=1, period=_KERNEL_CHUNK)
    ends = [gammas[:, c * _KERNEL_CHUNK - 1:c * _KERNEL_CHUNK] for c in (1, 2)]
    last = jnp.where(_iota(gammas.shape, 1) < _KERNEL_CHUNK, *ends)
    return gammas, jnp.exp(gammas), jnp.exp(last - gammas), [jnp.exp(end) for end in ends]


def gdn_inverse_kernel(k_ref, g_ref, beta_ref, inverse_ref):
    """Every head's ``T = (I + A)^-1`` of two chunks, float32 ``[H, C, 2 C]``:
    a head's two side by side."""
    heads = beta_ref.shape[1]
    d_k = k_ref.shape[1] // heads
    gammas = _running_sum(g_ref[0], axis=1, period=_KERNEL_CHUNK)
    betas = beta_ref[0]
    rounds = _KERNEL_CHUNK.bit_length() - 1  # no round joins the two chunks

    def head(pair, half, carry):
        h = 2 * pair + half
        k = k_ref[0, _rows_of(h, d_k), :]                               # [d_k, L]
        system = _turned(_row_of(betas, h)) * _transposed_times(k, k) * _decay_between(
            _row_of(gammas, h)
        )
        inverse_ref[0, 0, h] = _folded(_solve(system, rounds))
        return carry

    _over_heads(heads, head)


def gdn_operands_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, inverse_ref,
                        w_ref, u_ref, k_out_ref, whole_ref, q_in_ref, scores_ref):
    """From two chunks' inputs and ``T`` to the carry's operands (``w``, ``u``,
    ``k_out``, ``whole``) and the output stage's (``q_in``, the masked decayed
    scores), a ``[C, d]`` tile a head and chunk each: steps along the sublanes
    there, as their readers take them."""
    f32, dtype = jnp.float32, q_ref.dtype
    heads, size = beta_ref.shape[1], _KERNEL_CHUNK
    d_k, d_v = k_ref.shape[1] // heads, v_ref.shape[1] // heads
    gammas, growns, to_ends, wholes = _decays(g_ref)
    betas = beta_ref[0]
    whole_ref[0, 0], whole_ref[1, 0] = wholes
    square = (_LANE_STEPS,) * 2
    # a tile's transpose through the MXU: exact for what is rounded already
    eye = (_iota(square, 0) == _iota(square, 1)).astype(dtype)

    def head(pair, half, carry):
        h = 2 * pair + half
        keys, values = _rows_of(h, d_k), _rows_of(h, d_v)
        q, k = q_ref[0, keys, :], k_ref[0, keys, :]                     # [d_k, L]
        k32 = k.astype(f32)
        beta, grown = _row_of(betas, h), _row_of(growns, h)             # [1, L]
        between = _decay_between(_row_of(gammas, h))
        scores = (_transposed_times(q, k) * between).astype(dtype)      # [L, L]
        inverse = _unfolded(inverse_ref[0, 0, h]).astype(dtype)
        k_in = (k32 * (beta * grown)).astype(dtype)
        v_in = (v_ref[0, values, :].astype(f32) * beta).astype(dtype)
        w = _times_transposed(inverse, k_in).astype(dtype)              # [L, d_k]
        u = _times_transposed(inverse, v_in)                            # [L, d_v]
        k_out = _times_transposed(eye, (k32 * _row_of(to_ends, h)).astype(dtype)).astype(dtype)
        q_in = _times_transposed(eye, (q.astype(f32) * grown).astype(dtype)).astype(dtype)
        for c in range(_LANE_STEPS // size):  # a chunk's rows, and its block of the scores
            at = slice(c * size, (c + 1) * size)
            w_ref[c, 0, h], u_ref[c, 0, h], k_out_ref[c, 0, h] = w[at], u[at], k_out[at]
            q_in_ref[0, c, h], scores_ref[0, c, h] = q_in[at], scores[at, at]
        return carry

    _over_heads(heads, head)


def gdn_backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, inverse_ref,
                        dw_ref, du_ref, dk_out_ref, dwhole_ref, dq_in_ref, dscores_ref,
                        dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref):
    """The cotangents of two chunks' inputs from those of the six operands:
    the tile's intermediates are made again in VMEM from the inputs and the
    saved ``T``. A head leaves its row of ``dbeta`` and of the running sum's
    cotangent; ``dg`` is every head's reverse running sum at the end."""
    f32, dtype = jnp.float32, q_ref.dtype
    heads, size, steps = beta_ref.shape[1], _KERNEL_CHUNK, _LANE_STEPS
    d_k, d_v = k_ref.shape[1] // heads, v_ref.shape[1] // heads
    gammas, growns, to_ends, wholes = _decays(g_ref)
    betas = beta_ref[0]
    down = lambda a: jnp.sum(a, axis=0, keepdims=True)      # noqa: E731 — [1, L]
    across = lambda a: jnp.sum(a, axis=1, keepdims=True)    # noqa: E731 — [L, 1]
    highest = dict(precision=jax.lax.Precision.HIGHEST)
    dot = functools.partial(jnp.dot, preferred_element_type=f32)
    row, col = _iota((steps, steps), 0), _iota((steps, steps), 1)
    same = _same_chunk((steps, steps))
    eye = (row == col).astype(dtype)

    def head(pair, half, carry):
        h = 2 * pair + half
        keys, values = _rows_of(h, d_k), _rows_of(h, d_v)
        q, k = q_ref[0, keys, :], k_ref[0, keys, :]                     # [d_k, L]
        q32, k32, v32 = q.astype(f32), k.astype(f32), v_ref[0, values, :].astype(f32)
        beta, grown, to_end = (_row_of(a, h) for a in (betas, growns, to_ends))
        between = _decay_between(_row_of(gammas, h))
        exact = _unfolded(inverse_ref[0, 0, h])
        inverse = exact.astype(dtype)
        k_in = (k32 * (beta * grown)).astype(dtype)
        v_in = (v32 * beta).astype(dtype)
        # the two chunks' cotangent tiles one under another, [L, d]
        both_chunks = lambda tile: jnp.concatenate([tile(0), tile(1)], axis=0)  # noqa: E731
        dw = both_chunks(lambda c: dw_ref[c, 0, h])
        du = both_chunks(lambda c: du_ref[c, 0, h]).astype(dtype)

        # through w = T k_in and u = T v_in
        d_inverse = jnp.where(same, dot(dw, k_in) + dot(du, v_in), 0.0)
        d_k_in, d_v_in = _transposed_times(dw, inverse), _transposed_times(du, inverse)

        # through the solve, as ``_unit_lower_inverse_bwd``: dA = -T^T dT T^T
        d_system = -_times_transposed(
            _transposed_times(exact, d_inverse, **highest), exact, **highest
        )
        d_system = jnp.where(same & (row > col), d_system, 0.0)
        d_scores = both_chunks(lambda c: jnp.concatenate([dscores_ref[0, c, h]] * 2, axis=1))
        d_scores = jnp.where(same & (row >= col), d_scores.astype(f32), 0.0)

        # through A = beta (K K^T) o between and the scores (Q K^T) o between
        kk, qk = _transposed_times(k, k), _transposed_times(q, k)
        by_beta = d_system * _turned(beta)
        d_between = (by_beta * kk + d_scores * qk) * between
        both = jnp.concatenate([by_beta * between, d_scores * between], axis=0).astype(dtype)
        d_rows = _times_transposed(k, both)                  # [d_k, 2 L]: K^T dA^T | K^T dS^T
        d_cols = dot(jnp.concatenate([k, q], axis=1), both)  # [d_k, L]: K^T dA + Q^T dS

        # through the elementwise operands
        dk_out = _transposed_times(both_chunks(lambda c: dk_out_ref[c, 0, h]), eye)
        dq_in = _transposed_times(both_chunks(lambda c: dq_in_ref[0, c, h]), eye)
        leaving, fed = down(dk_out * k32) * to_end, down(d_k_in * k32)
        # gamma_i gathers a row of between's cotangent, gamma_j gives a column
        d_gamma = (
            _turned(across(d_between)) - down(d_between) + fed * (beta * grown) - leaving
            + down(dq_in * q32) * grown
        )
        d_beta = _turned(across(d_system * kk * between)) + fed * grown + down(d_v_in * v32)
        dq_ref[0, keys, :] = (d_rows[:, steps:] + dq_in * grown).astype(dtype)
        dk_ref[0, keys, :] = (
            d_rows[:, :steps] + d_cols + d_k_in * (beta * grown) + dk_out * to_end
        ).astype(dtype)
        dv_ref[0, values, :] = (d_v_in * beta).astype(dtype)
        mine = _iota(betas.shape, 0) == h
        return tuple(
            jnp.where(mine, mine_row, rows)
            for mine_row, rows in zip((d_beta, d_gamma, leaving), carry)
        )

    d_betas, d_gammas, leavings = _over_heads(heads, head, (jnp.zeros(betas.shape, f32),) * 3)
    # each chunk's last step: what left through k_out and through whole
    step = _iota(betas.shape, 1)
    for c, whole in enumerate(wholes):
        mine = (step >> (size.bit_length() - 1)) == c
        d_last = across(jnp.where(mine, leavings, 0.0)) + dwhole_ref[c, 0] * whole
        d_gammas = d_gammas + jnp.where(step == (c + 1) * size - 1, d_last, 0.0)
    dg_ref[0] = _running_sum(d_gammas, reverse=True, axis=1, period=size)
    dbeta_ref[0] = d_betas


_SCALAR_INPUTS = ("keys", "keys", "values", "steps", "steps")            # q k v g beta
_SCALAR_OPERANDS = ("w", "u", "w", "whole", "q_in", "scores")            # ..., k_out, ...


def _scalar_run(kernel, ins, outs, operands, interpret):
    """One of the scalar rule's three kernels on ``operands``, whose kinds
    ``ins`` names (``outs`` those of its results): a grid step a lane tile of
    steps (two chunks), every block those of every head."""
    batch, h, steps = operands[ins.index("steps")].shape
    size, nc, lanes = _KERNEL_CHUNK, steps // _KERNEL_CHUNK, _LANE_STEPS
    keys = operands[ins.index("keys")]
    values = operands[ins.index("values")] if "values" in ins else keys
    d_k, d_v = keys.shape[1] // h, values.shape[1] // h
    f32, dtype = jnp.float32, keys.dtype
    here, first = (lambda b, n: (b, n)), (lambda b, n: (n, b))  # noqa: E731
    along = lambda rows, dt: (  # noqa: E731 — [B, rows, T], a lane tile of steps
        (batch, rows, steps), dt, (1, rows, lanes), lambda b, n: (b, 0, n)
    )
    kinds = dict(
        keys=along(h * d_k, dtype), values=along(h * d_v, dtype), steps=along(h, f32),
        # every two chunks' T side by side; the scores [b n h c s]
        inverse=((batch, nc // 2, h, size, lanes), f32, (1, 1, h, size, lanes), here),
        scores=((batch, nc, h, size, size), dtype, (1, 2, h, size, size), here),
        q_in=((batch, nc, h, size, d_k), dtype, (1, 2, h, size, d_k), here),
        # the carry's operands, chunks first (k_out as w)
        w=((nc, batch, h, size, d_k), dtype, (2, 1, h, size, d_k), first),
        u=((nc, batch, h, size, d_v), f32, (2, 1, h, size, d_v), first),
        whole=((nc, batch, h, 1), f32, (2, 1, h, 1), first),
    )
    return _chunk_call(kernel, (batch, nc // 2), kinds, ins, outs, operands, interpret)


# jitted, as ``ops/causal_conv.py``'s: a step traces and lowers each body once
@functools.partial(jax.jit, static_argnums=3)
def _scalar_inverse_call(k, g, beta, interpret):
    ins = ("keys", "steps", "steps")
    return _scalar_run(gdn_inverse_kernel, ins, ("inverse",), (k, g, beta), interpret)[0]


@functools.partial(jax.jit, static_argnums=6)
def _scalar_operands_call(q, k, v, g, beta, inverse, interpret):
    ins = (*_SCALAR_INPUTS, "inverse")
    return _scalar_run(
        gdn_operands_kernel, ins, _SCALAR_OPERANDS, (q, k, v, g, beta, inverse), interpret
    )


@functools.partial(jax.jit, static_argnums=12)
def _scalar_backward_call(q, k, v, g, beta, inverse, dw, du, dk_out, dwhole, dq_in, dscores,
                          interpret):
    ins = (*_SCALAR_INPUTS, "inverse", *_SCALAR_OPERANDS)
    operands = (q, k, v, g, beta, inverse, dw, du, dk_out, dwhole, dq_in, dscores)
    return _scalar_run(gdn_backward_kernel, ins, _SCALAR_INPUTS, operands, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scalar_kernels(q, k, v, g, beta, interpret):
    """The scalar rule's chunk-local stage by the kernels, time along the
    lanes: ``q``, ``k`` ``[B, H d_k, T]``, ``v`` ``[B, H d_v, T]`` and float32
    ``g``, ``beta`` ``[B, H, T]``; ``(w, u, k_out, whole, q_in, scores)`` as
    ``_scalar_plain`` lays them out, but ``k_out`` ``[n b h c k]`` and ``q_in``
    ``[b n h c k]`` a tile a head and ``whole`` ``[n b h 1]``."""
    inverse = _scalar_inverse_call(k, g, beta, interpret)
    return _scalar_operands_call(q, k, v, g, beta, inverse, interpret)


def _scalar_kernels_fwd(q, k, v, g, beta, interpret):
    # T apart from the rest and by name, as ``_local_kernels_fwd``
    inverse = checkpoint_name(_scalar_inverse_call(k, g, beta, interpret), INVERSE_NAME)
    operands = _scalar_operands_call(q, k, v, g, beta, inverse, interpret)
    return operands, (q, k, v, g, beta, inverse)


def _scalar_kernels_bwd(interpret, residuals, cotangents):
    return _scalar_backward_call(*residuals, *cotangents, interpret)


_scalar_kernels.defvjp(_scalar_kernels_fwd, _scalar_kernels_bwd)


def kda_rule(q, k, v, g, beta, *, chunk: int = 64, initial_state=None,
             return_final_state: bool = False, interpret: bool = False, caller=None):
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
    for any reference ``R``, and **the rule holds for any finite** ``g <= 0``
    (Kimi Linear's own gate, ``-exp(A_log) softplus(.)``, which no bound
    holds, as well as a safe gate's ``(-5, 0)``) by choosing ``R`` so that
    **no** ``exp`` **of a positive argument is taken anywhere**, forward or
    backward: the **halving** form. The chunk is halved down to single steps;
    a pair ``i > j`` is had at the one level where ``i`` lies in the upper and
    ``j`` in the lower half of the same block, with ``R`` the running sum at
    the lower half's last step, so ``Gamma_i - R <= 0`` and ``R - Gamma_j <=
    0`` both; the diagonal (``i = j``: no decay) is ``q_i . k_i`` itself, and
    a factor that underflows float32 reads 0, as the step-by-step recurrence's
    would. ``log2(C)`` levels, each one ``exp`` of a ``[C, d]`` tile and one
    masked ``[C, d] x [d, C]`` product (:func:`_plain_pairs`, :func:`_halved`).
    (Until PR 51 the rows of a sub-block of 16 steps shared a reference at its
    middle step, which held only while ``|g|`` stayed under 5.5 a step and was
    0.66% of ``ling_3_0_flash_vl.steady``'s step cheaper, 5 ``exp`` a tile for 6
    and 4 strips of 16 rows for 6 whole products: ``bench_results/README.md``
    has both timed.)

    ``W``, ``U``, the carry (:func:`_carried_outputs`, its decay a vector over
    ``d_k``) and the outputs are the scalar rule's with ``exp(Gamma)`` a
    channel; the solve is ``unit_lower_inverse``; what a remat policy saves
    bears the same names (``REMAT_NAMES``). ``caller`` is what the caller says
    of itself for the ``kda_chunks`` instant (``KimiDeltaMixer``: ``gate``,
    ``bound``, ``beta_max``, ``rank``); the rule adds ``pairs`` (``halving``).

    Precision as the scalar rule's: ``g``, its sums, every ``exp``, ``beta``,
    the system, its inverse and the carried state in float32; each matmul
    operand rounded once to ``q``'s dtype, float32 accumulation.

    **Two forms of the chunk-local stage** (from the inputs to the carry's
    operands ``w``, ``u``, ``k_out``, ``whole`` and the output stage's ``q_in``
    and masked scores), one contract. Which runs is decided from what the call
    can see, as ``ops/causal_conv.py`` decides: on a TPU backend, for bfloat16
    ``q``, ``k``, ``v``, a chunk of 64, ``d_k`` and ``d_v`` multiples of 128,
    an even ``H`` and a ``T`` the chunk divides, three Pallas kernels under one
    ``jax.custom_vjp`` (``_local_kernels``); everywhere else (the CPU, float32
    operands, a ragged ``T``, another chunk) the plain form (``_local_plain``),
    which is also the kernels' reference. ``interpret`` runs the kernels in the
    Pallas interpreter (tests on the CPU). The carry and the output stage
    after it decide for themselves in the same way (:func:`_carried_outputs`:
    the walk's ``delta_carry`` and ``delta_carry_back`` over rows whose heads
    lie side by side, as ``kda_operands`` writes ``k_out`` and ``q_in``; an
    odd count of heads, which the chunk-local kernels refuse, they take).

    The kernels hold one chunk of every head in VMEM a grid step, ``chunk``
    rows of the ``[B, T, H d]`` views of the inputs as the mixer hands them
    over (a head is ``d`` lanes of a row, so nothing is transposed on the way
    in), walk the heads in a loop and write each operand in its reader's
    layout (the carry's chunks first). ``kda_inverse`` makes every chunk's
    float32 ``T`` from ``k``, ``g`` and ``beta``; it alone runs the solve, and
    its output bears ``INVERSE_NAME``, so under a policy that saves the name the
    recomputation of a layer runs ``kda_operands`` (everything else, from the
    inputs and ``T``) and not the solve. ``kda_backward`` keeps nothing but the
    inputs and ``T``: it makes the tile's intermediates again in VMEM and
    returns ``dq``, ``dk``, ``dv``, ``dg``, ``dbeta`` from the six operands'
    cotangents, the solve's by ``dA = -T^T dT T^T``.
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
    why_plain = _kernels_refuse(q, k, v, t, chunk, interpret)
    kernels = why_plain is None
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta)
        )
    steps = t + pad
    size, nc = chunk, steps // chunk
    f32, dtype = jnp.float32, q.dtype
    # once a shape and stage: which form the chunk-local stage took, and why
    # where it is the plain one
    note = functools.partial(
        obs_trace.get_tracer().note_once, "kda_chunks", chunk=size,
        pairs="halving", **(caller or {}), chunks=nc, heads=h, d_k=d_k, d_v=d_v,
        state_bytes=4 * h * d_k * d_v, solve=SOLVE,
        saved_bytes=saved_bytes(
            size, nc, h, d_k, d_v, jnp.dtype(dtype).itemsize, batch
        ),
    )
    if kernels:
        note(path="kernel")
    else:
        note(path="plain", why=why_plain)

    # b batch, n chunk, c / s step in a chunk, h head, k key width, v value width
    if kernels:
        flat = lambda a: a.reshape(batch, steps, -1)  # noqa: E731 — a row's heads side by side
        w, u, k_out, whole, q_in, scores = _local_kernels(
            flat(q), flat(k), flat(v), flat(g.astype(f32)), beta.astype(f32), interpret
        )
    else:
        w, u, k_out, whole, q_in, scores, _ = _local_plain(q, k, v, g, beta, size)
        k_out, q_in = k_out.reshape(nc, batch, size, -1), q_in.reshape(batch, steps, -1)

    # k_out and q_in with a row's heads side by side, either way
    o, state = _carried_outputs(
        initial_state, w, u, k_out, whole, q_in, scores, t, interpret
    )
    if return_final_state:
        return o, state
    return o
