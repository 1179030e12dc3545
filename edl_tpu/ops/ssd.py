"""Chunked state-space scan: Mamba-2's recurrence in its dual form (SSD).

The recurrence, per head ``h`` with state ``S`` of ``[P, N]`` (Dao and Gu,
"Transformers are SSMs", arXiv:2405.21060, section 6 and listing 1)::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (x) B_t
    y_t = S_t C_t + D * x_t

is computed a chunk of ``chunk`` steps at a time. Inside a chunk the state
never materialises: ``Y_diag = (L o C B^T)(dt x)`` with ``L_ij = exp(sum of
dt_k A over j < k <= i)``, three matmuls. Each chunk's own final state is one
more matmul, the states are carried from chunk to chunk by a sequential
``lax.scan`` (``T / chunk`` steps of an elementwise update), and what a chunk
inherits reaches its outputs through ``Y_off = exp(cumsum) * C S_in``.

Precision: the log-decays ``dt A``, their running sums, every ``exp`` of
them and the carried state are float32; the matmul operands (``C``, ``B``,
``L o C B^T``, ``dt x`` and the state a chunk reads) are in ``x``'s dtype with
float32 accumulation. Plain ``jax.numpy`` / ``lax``: the backward is jax's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_scan(x, dt, a, b, c, d=None, *, chunk: int = 256, initial_state=None,
             return_final_state: bool = False):
    """``y`` ``[B, T, H, P]`` in ``x``'s dtype (and the final state, float32
    ``[B, H, P, N]``, with ``return_final_state``).

    ``x`` ``[B, T, H, P]``; ``dt`` ``[B, T, H]``, the step sizes, already
    positive (after the softplus); ``a`` ``[H]``, negative; ``b``, ``c``
    ``[B, T, G, N]`` with ``G`` dividing ``H`` (a group's ``B`` and ``C`` are
    shared by its ``H / G`` heads); ``d`` ``[H]`` or None; ``initial_state``
    ``[B, H, P, N]`` or None for zeros. The result does not depend on
    ``chunk`` beyond rounding; a ``T`` that ``chunk`` does not divide is
    padded with steps of size 0, which leave the state as it is.
    """
    batch, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if h % g:
        raise ValueError("ssd_scan: %d heads in %d groups" % (h, g))
    r = h // g
    size = min(chunk, t)
    pad = -t % size
    if pad:
        x, dt, b, c = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b, c)
        )
    nc = (t + pad) // size
    f32, dtype = jnp.float32, x.dtype
    dot = dict(preferred_element_type=f32)

    # everything below: b batch, c chunk, l / s step in a chunk, g group,
    # r head in its group, p head width, n state width
    dt = dt.astype(f32).reshape(batch, nc, size, g, r)
    x = x.reshape(batch, nc, size, g, r, p)
    b = b.reshape(batch, nc, size, g, n)
    c = c.reshape(batch, nc, size, g, n)
    x32 = x.astype(f32)
    dtx = x32 * dt[..., None]
    # running sum of the log-decay inside each chunk, heads before steps
    decay = jnp.cumsum(dt * a.astype(f32).reshape(g, r), axis=2)
    decay = jnp.moveaxis(decay, 2, -1)                           # [b c g r l]

    # inside a chunk
    scores = jnp.einsum("bclgn,bcsgn->bcgls", c, b, **dot)
    causal = jnp.tril(jnp.ones((size, size), bool))
    between = jnp.exp(jnp.where(
        causal, decay[..., :, None] - decay[..., None, :], -jnp.inf
    ))                                                           # [b c g r l s]
    mixing = (between * scores[:, :, :, None]).astype(dtype)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", mixing, dtx.astype(dtype), **dot)

    # a chunk's own contribution to the state at its end
    to_end = jnp.exp(decay[..., -1:] - decay)                    # [b c g r l]
    weighted = (dtx * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(dtype)
    own = jnp.einsum("bclgn,bclgrp->bcgrpn", b, weighted, **dot)

    # from chunk to chunk, in float32
    def carry(state, inputs):
        whole, new = inputs
        return whole[..., None, None] * state + new, state

    if initial_state is None:
        state = jnp.zeros((batch, g, r, p, n), f32)
    else:
        state = initial_state.astype(f32).reshape(batch, g, r, p, n)
    whole = jnp.exp(decay[..., -1])                              # [b c g r]
    state, entering = jax.lax.scan(
        carry, state, (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(own, 1, 0))
    )
    entering = jnp.moveaxis(entering, 0, 1)                      # [b c g r p n]

    # what a chunk inherits, seen through C and decayed to each step
    inherited = jnp.einsum(
        "bclgn,bcgrpn->bclgrp", c, entering.astype(dtype), **dot
    )
    y = y + inherited * jnp.moveaxis(jnp.exp(decay), -1, 2)[..., None]
    if d is not None:
        y = y + d.astype(f32).reshape(g, r, 1) * x32
    y = y.reshape(batch, t + pad, h, p)[:, :t].astype(dtype)
    if return_final_state:
        return y, state.reshape(batch, h, p, n)
    return y
