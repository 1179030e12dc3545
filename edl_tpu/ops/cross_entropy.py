"""A row's softmax cross-entropy and its argmax, from one pass over the logits.

``rows_cross_entropy(logits [..., V] float32, labels [...] int32)`` gives
``(ce [...] float32, best [...] int32)``: ``-log softmax(logits)[label]`` a row,
and the first index of the row's maximum (``jnp.argmax``'s rule on ties). It is
the one owner of that arithmetic: ``train/step.py``'s loss heads and the
multi-token module of ``models/transformer.py`` (which may not import
``train/``) both call it.

No kernel: the forward is spelled so that XLA's own multi-output fusion reads
the ``[N, V]`` float32 logits once after the head's matmul has written them.
Every reduction is over the last axis and a plain monoid (``max``, ``+``,
``+``, ``min``), so the compiler makes them siblings of one fusion::

    m      = max(logits)                               rides the head's matmul
    s      = sum exp(logits - m)
    picked = sum where(col == label, logits - m, 0)    no one-hot array
    best   = min where(logits == m, col, V)            col carried in float32
    ce     = log s - picked

``optax.softmax_cross_entropy(logits, one_hot)`` + ``jnp.argmax`` is the same
mathematics in three passes (the pick needs ``log s`` before it can start, and
an ``s32`` variadic reduce fuses with no sum) and, under autodiff, a fourth
over the cotangent (PERF.md section 6, PR 68). The index is carried in float32
because an ``s32`` ``min`` keeps a pass of its own; it is exact below ``2**24``,
and a wider vocabulary is refused. A row that holds a NaN equals its maximum
nowhere, so its ``best`` is ``V``, which equals no label (``jnp.argmax`` gives
the NaN's index; its ``ce`` is NaN under both).

The backward is ``(softmax - one_hot) * g`` from ``(logits, labels, m + log
s)``: one ``[...]`` float32 vector more than autodiff keeps, no ``[N, V]``
array. ``labels`` and ``best`` take no gradient. A label outside ``[0, V)`` is
a row that scores nothing (``ce`` 0, no gradient), as its all-zero one-hot made
it. Each shape notes itself once a stage in a ``ce_rows`` instant with the
caller's ``site``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from edl_tpu.obs import trace as obs_trace

# float32 holds every whole number below this, so a column index rides in it
MAX_VOCAB = 2**24


def _columns(logits, dtype):
    return jax.lax.broadcasted_iota(dtype, logits.shape, logits.ndim - 1)


def _hit(logits, labels):
    """Where a row's label is the column: the one-hot, never an array."""
    return _columns(logits, jnp.int32) == labels[..., None]


def _scores(labels, vocab):
    """Rows whose label is a column: any other row's one-hot is all zeros, and
    it scores 0 and hands back no gradient, as under optax."""
    return (labels >= 0) & (labels < vocab)


def _forward(logits, labels):
    vocab = logits.shape[-1]
    m = jnp.max(logits, axis=-1, keepdims=True)
    shifted = logits - m
    s = jnp.sum(jnp.exp(shifted), axis=-1)
    picked = jnp.sum(jnp.where(_hit(logits, labels), shifted, 0.0), axis=-1)
    best = jnp.min(
        jnp.where(logits == m, _columns(logits, jnp.float32), jnp.float32(vocab)),
        axis=-1,
    )
    log_s = jnp.log(s)
    ce = jnp.where(_scores(labels, vocab), log_s - picked, 0.0)
    return ce, best.astype(jnp.int32), m[..., 0] + log_s


@jax.custom_vjp
def _rows_cross_entropy(logits, labels):
    ce, best, _ = _forward(logits, labels)
    return ce, best


def _rows_cross_entropy_fwd(logits, labels):
    ce, best, lse = _forward(logits, labels)
    return (ce, best), (logits, labels, lse)


def _rows_cross_entropy_bwd(residuals, cotangents):
    logits, labels, lse = residuals
    g, _ = cotangents
    g = jnp.where(_scores(labels, logits.shape[-1]), g, 0.0)
    softmax = jnp.exp(logits - lse[..., None])
    return (softmax - _hit(logits, labels).astype(logits.dtype)) * g[..., None], None


_rows_cross_entropy.defvjp(_rows_cross_entropy_fwd, _rows_cross_entropy_bwd)


def rows_cross_entropy(logits: jax.Array, labels: jax.Array, *, site: str):
    """``(ce, best)`` of float32 ``logits [..., V]`` against integer ``labels
    [...]``; ``site`` names the caller in the ``ce_rows`` instant. See the
    module's docstring."""
    vocab = logits.shape[-1]
    if vocab >= MAX_VOCAB:
        raise ValueError(
            "rows_cross_entropy: a vocabulary of %d: the argmax's column rides in "
            "float32, exact below %d" % (vocab, MAX_VOCAB)
        )
    if logits.shape[:-1] != labels.shape:
        raise ValueError(
            "rows_cross_entropy: logits %s want labels %s, got %s"
            % (logits.shape, logits.shape[:-1], labels.shape)
        )
    obs_trace.get_tracer().note_once(
        "ce_rows", rows=math.prod(labels.shape), vocab=vocab, site=site
    )
    return _rows_cross_entropy(logits, labels)
