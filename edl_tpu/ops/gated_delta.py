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

Only the state is carried sequentially (a ``lax.scan`` over the chunks of two
matmuls a step: ``W S`` and ``K^T V_new``); the outputs of all chunks are then
computed at once from the states the scan emits.

Precision: ``g``, its running sums, every ``exp``, ``beta``, ``A``, ``T`` (its
rounds at ``Precision.HIGHEST``) and the carried state are float32; the other
matmuls take their operands in ``q``'s dtype with float32 accumulation, as
``ssd_scan``'s do. Every ``exp`` is of a difference that is never positive, so
nothing overflows however fast a head forgets. Plain ``jax.numpy`` / ``lax``:
the backward is jax's, but for the inverse's (``unit_lower_inverse``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from edl_tpu.obs import trace as obs_trace

SOLVE = "block_doubling"  # how T is had, as the ``gdn_chunks`` instant names it
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
    inverse = unit_lower_inverse(a)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, ct):
    transposed = jnp.swapaxes(inverse, -1, -2)
    return (-_exact(_exact(transposed, ct), transposed),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


@functools.lru_cache(maxsize=None)
def _note_chunks(chunk, chunks, heads, d_k, d_v):
    """One ``gdn_chunks`` instant in the span ring for each shape the rule
    is traced at."""
    obs_trace.get_tracer().instant(
        "gdn_chunks", chunk=chunk, chunks=chunks, heads=heads, d_k=d_k, d_v=d_v,
        state_bytes=4 * heads * d_k * d_v, solve=SOLVE,
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
    _note_chunks(size, nc, h, d_k, d_v)
    f32, dtype = jnp.float32, q.dtype
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
    def carry(state, inputs):
        w_n, u_n, k_n, whole_n = inputs
        new = u_n - jnp.einsum("bhck,bhkv->bhcv", w_n, state.astype(dtype), **dot)
        new = new.astype(dtype)
        after = whole_n[..., None, None] * state + jnp.einsum(
            "bchk,bhcv->bhkv", k_n, new, **dot
        )
        return after, (state.astype(dtype), new)

    if initial_state is None:
        state = jnp.zeros((batch, h, d_k, d_v), f32)
    else:
        state = initial_state.astype(f32)
    chunks_first = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    state, (entering, new) = jax.lax.scan(
        carry, state, tuple(chunks_first(a) for a in (w, u, k_out, whole))
    )
    entering = jnp.moveaxis(entering, 0, 1)                      # [b n h k v]
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
