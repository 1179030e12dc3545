"""Gated-delta-rule mixer: the linear-attention layer of a hybrid decoder.

The layer of Yang, Kautz and Hatamizadeh (arXiv:2412.06464) as
``flash-linear-attention``'s ``GatedDeltaNet`` and Hugging Face's Qwen3-Next
lay it out, for the block's input ``x`` ``[B, T, d_model]``, ``H`` heads of
``d_k`` (queries, keys) and ``d_v`` (values)::

    [q | k | v | gate | b | a] = W_in x        widths H d_k | H d_k | H d_v | H d_v | H | H
    [q | k | v] = silu(causal depthwise conv_{d_conv}([q | k | v]))   no bias (ops/causal_conv.py)
    q = q / sqrt(|q|^2 + 1e-6) * d_k^-1/2;  k = k / sqrt(|k|^2 + 1e-6)         per head, float32
    beta = sigmoid(b) * (2 if neg_eigval else 1)     per head: I - beta k k^T then has
                                                     an eigenvalue in (-1, 1), not (0, 1)
    g    = -exp(A_log) * softplus(a + dt_bias)       per head, the log of the decay
    o    = gated_delta_rule(q, k, v, g, beta)        (ops/gated_delta.py)
    o    = RMSNorm_{d_v}(o) * w * silu(gate)         per head, one scale w of d_v for all
    out  = W_out o

The norm comes first and the gate second, each head for itself: not the
Mamba-2 mixer's ``RMSNorm(y * silu(z))`` over the whole width. The three short
convolutions are one over the leading ``2 H d_k + H d_v`` columns of the in
projection's output, read in place by ``causal_conv_silu``.

The device time of its four parts carries the names ``gdn_proj`` (both
projections), ``gdn_conv``, ``gdn_scan`` (the L2 norms, ``beta``, ``g`` and the
chunked rule: on a TPU backend at bfloat16, a chunk of 64, a ``T`` of whole
lane tiles and widths in 16s its chunk-local stage is the Pallas kernels
``gdn_inverse``, ``gdn_operands`` and ``gdn_backward``, and its carry and
output stage one walk over the chunks with the state in VMEM, ``delta_carry``
and ``delta_carry_back``: custom calls of those names in a trace and no loop
between them; ``q``, ``k`` and ``v`` go to the rule as ``[B, T, H, d]``, whose
``[B, H d, T]`` view, the steps minor as the convolution wrote them, the
kernels read in place; everywhere else the plain forms, the carry then two
``lax.scan``) and ``gdn_gate``
(``jax.named_scope``; ``obs/profile.py:step_scopes`` joins them to a trace). Into ``"metrics"`` it
sows ``gdn_decay_mean`` (the mean of ``exp(g)`` over tokens and heads: how fast
the state forgets), ``gdn_beta_mean`` and ``gdn_state_absmax`` (the largest
magnitude in the state after the last step: the health of a rule whose
eigenvalues may be negative), which the step averages over the layers and the
loop exports as ``edl_train_<name>`` gauges; into ``"intermediates"`` the
rule's own inputs.

For a remat policy around the block the rule's ``o`` and final state bear the
name ``gdn_out`` and, inside the rule, what its sequential carry leaves the
name ``gdn_carry`` and every chunk's ``T`` ``gdn_inverse``
(``ops/gated_delta.py:REMAT_NAMES``): a policy that saves them
(``TransformerLM.remat_policy`` ``"save_flash"``) runs the carry once forward
and once in reverse a layer (the walk's two kernels, or the plain form's two
loops) and the solve (``gdn_inverse``) once, and the rest of the chunk-local
stage (``gdn_operands``) again when the backward reaches the rule; one that
saves none runs the forward carry and the solve again when the block is
recomputed.
The in projection's output bears the name ``mixer_in``
(``models/mamba.py:projected``, whole and before the slices): the same policy
hands it to the recomputation, which then runs no ``in_proj`` matmul again.

:class:`KimiDeltaMixer` is Kimi delta attention, the same rule with a log-decay
for every key channel (``ops/gated_delta.py:kda_rule``): separate projections
and convolutions for q, k and v, a sigmoid gate, the scopes ``kda_*`` and the
same names for a remat policy; by its spec the decay's gate is the safe gate
(a lower bound) or Kimi Linear's own unbounded softplus gate, beta lies in (0,
1) or (0, 2), and the decay's and the gate's projections are one full matrix
each or a low-rank pair.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from edl_tpu.models.mamba import _dt_bias_init, projected
from edl_tpu.ops.causal_conv import causal_conv_silu
from edl_tpu.ops.gated_delta import (
    OUT_NAME,
    REMAT_NAMES,
    gated_delta_rule,
    kda_rule,
)

GDN_SCOPES = ("gdn_proj", "gdn_conv", "gdn_scan", "gdn_gate")
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class GatedDeltaSpec:
    """The shape of a :class:`GatedDeltaMixer`, as one hashable field."""

    num_heads: int           # H held HERE, of keys and of values alike (a chip
                             # with a share of a layer's heads gives its own count)
    key_dim: int             # d_k
    value_dim: int           # d_v
    d_conv: int = 4
    chunk: int = 64          # steps a chunk of the rule: a power of two
    neg_eigval: bool = True  # beta in (0, 2) and not (0, 1)


def _unit(m):
    """``m / sqrt(|m|^2 + 1e-6)`` over the last axis."""
    return m * jax.lax.rsqrt(jnp.sum(m * m, axis=-1, keepdims=True) + L2_EPS)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log U(0, 16)`` (from 1e-6, so that the log is finite): the layer's
    own, a spread of decay rates."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-6, 16.0))


class GatedDeltaMixer(nn.Module):
    spec: GatedDeltaSpec
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        s = self.spec
        batch, t, d_model = x.shape
        h, d_k, d_v = s.num_heads, s.key_dim, s.value_dim
        conv_dim = 2 * h * d_k + h * d_v
        f32 = jnp.float32
        dense = lambda width, name: nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=self.dtype, name=name
        )

        with jax.named_scope("gdn_proj"):
            proj, = projected("gdn", dense(conv_dim + h * d_v + 2 * h, "in_proj")(x))
        gate = proj[..., conv_dim:conv_dim + h * d_v]
        b, a = jnp.split(proj[..., conv_dim + h * d_v:].astype(f32), 2, axis=-1)

        with jax.named_scope("gdn_conv"):
            kernel = self.param(
                "conv_kernel",
                lambda key, shape: jax.random.uniform(key, shape, f32, -0.5, 0.5),
                (s.d_conv, conv_dim),
            )
            qkv = causal_conv_silu(proj, kernel, None, offset=0)

        a_log = self.param("A_log", _a_log_init, (h,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,))
        scale = self.param("norm", nn.initializers.ones, (d_v,))

        with jax.named_scope("gdn_scan"):
            q, k, v = jnp.split(qkv, [h * d_k, 2 * h * d_k], axis=-1)
            q = _unit(q.reshape(batch, t, h, d_k).astype(f32)) * d_k ** -0.5
            k = _unit(k.reshape(batch, t, h, d_k).astype(f32))
            q, k = q.astype(self.dtype), k.astype(self.dtype)
            v = v.reshape(batch, t, h, d_v)
            beta = jax.nn.sigmoid(b) * (2.0 if s.neg_eigval else 1.0)
            g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
            # under the block's remat with a policy that saves the names
            # (``"save_flash"``), what survives a layer's forward is ``o``
            # and the final state (``gdn_out``) and, from inside the rule,
            # the 128 float32 states the chunks inherit and ``V_new``
            # (``gdn_carry``) and every chunk's ``T`` (``gdn_inverse``): 0.27
            # GB a layer at 8192 steps of 15 heads. Everything later products
            # read of the sequential carry is then saved, so the block's
            # recomputation does not run the carry again and the carry's own
            # backward reads the states: one walk forward, one in reverse,
            # one solve a layer. The rest of what the rule's backward keeps
            # (a chunk's system, ``w``, ``u``, the scores: 0.6 GB a layer) is
            # recomputed when the backward reaches the rule, and so not held
            # while the gate, the out projection and the block's feed-forward
            # are still unwinding
            rule = jax.checkpoint(
                functools.partial(
                    gated_delta_rule, chunk=s.chunk, return_final_state=True
                ),
                policy=jax.checkpoint_policies.save_only_these_names(*REMAT_NAMES),
            )
            o, state = (
                checkpoint_name(a, OUT_NAME) for a in rule(q, k, v, g, beta)
            )
        self.sow("metrics", "gdn_decay_mean", jnp.mean(jnp.exp(g)))
        self.sow("metrics", "gdn_beta_mean", jnp.mean(beta))
        self.sow("metrics", "gdn_state_absmax", jnp.max(jnp.abs(state)))
        self.sow("intermediates", "rule_inputs", (q, k, v, g, beta))

        with jax.named_scope("gdn_gate"):
            o = o.astype(f32)
            o = o * jax.lax.rsqrt(
                jnp.mean(o * o, axis=-1, keepdims=True) + self.norm_eps
            )
            o = o * scale * nn.silu(gate.reshape(batch, t, h, d_v).astype(f32))
            o = o.reshape(batch, t, h * d_v).astype(self.dtype)

        with jax.named_scope("gdn_proj"):
            return dense(d_model, "out_proj")(o)


# -- Kimi delta attention: a decay for every key channel ---------------------

KDA_SCOPES = ("kda_proj", "kda_conv", "kda_scan", "kda_gate")


@dataclasses.dataclass(frozen=True)
class KimiDeltaSpec:
    """The shape of a :class:`KimiDeltaMixer`, as one hashable field."""

    num_heads: int             # H, of keys and of values alike
    key_dim: int               # d_k: queries, keys and the decay's channels
    value_dim: int             # d_v
    d_conv: int = 4
    chunk: int = 64            # steps a chunk of the rule: a power of two
    # the safe gate: g in (lower_bound, 0) a channel a step. None: Kimi Linear's
    # own gate, -exp(A_log) softplus(.), which no bound holds
    lower_bound: float | None = -5.0
    neg_eigval: bool = False   # beta in (0, 2) and not (0, 1)
    # the decay's and the gate's projections as a pair of matrices through this
    # width (Kimi Linear's own: the head's width). None: one full matrix each
    gate_rank: int | None = None


def _kda_a_log_init(key, shape, dtype=jnp.float32):
    """``log U(1, 16)``: the layer's own (``flash-linear-attention``)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class KimiDeltaMixer(nn.Module):
    """Kimi delta attention (Kimi Linear, arXiv:2510.26692, as
    ``flash-linear-attention``'s ``KimiDeltaAttention`` lays it out), for the
    block's input ``x`` ``[B, T, d_model]``, ``H`` heads of ``d_k`` and
    ``d_v``::

        q, k, v = silu(causal depthwise conv_{d_conv}(x W_{q,k,v}))   three projections, three
                                                                      convolutions, no bias
        q = q / sqrt(|q|^2 + 1e-6) * d_k^-1/2;  k = k / sqrt(|k|^2 + 1e-6)      per head, float32
        beta = sigmoid(x W_b) * (2 if neg_eigval else 1)              per head
        g    = lower_bound * sigmoid(exp(A_log) * (x W_f + dt_bias))  per head AND key channel:
                                                                      the safe gate, g in (lower_bound, 0)
          or   -exp(A_log) * softplus(x W_f + dt_bias)                lower_bound None: Kimi Linear's
                                                                      own gate, any g <= 0
        o    = kda_rule(q, k, v, g, beta)                             (ops/gated_delta.py)
        o    = RMSNorm_{d_v}(o) * w * sigmoid(x W_g)                  per head, one scale w of d_v
        out  = W_o o

    ``A_log`` is one a head, ``dt_bias`` one a key channel. With ``gate_rank``
    None ``W_f`` and ``W_g`` are one full matrix each (Ling's published
    ``no_kda_lora``; leaves ``f_proj``, ``g_proj``); with a rank ``r`` each is
    Kimi Linear's pair ``W_down`` ``[d_model, r]`` then ``W_up`` ``[r, H d]``
    (leaves ``f_down`` / ``f_up`` without a bias, ``g_down`` / ``g_up`` with a
    bias on ``g_up``): on a chip that holds a share of the heads the first
    matrices stay whole and the second are cut by heads. The rule holds for
    any ``g <= 0``, so either gate runs the same program and only a bound that
    is none below zero is refused. No position term.

    Device scopes ``kda_proj`` (the six projections in and the one out),
    ``kda_conv``, ``kda_scan`` (the L2 norms, ``beta``, the gate, the chunked
    rule: on a TPU backend at bfloat16 its chunk-local stage is the Pallas
    kernels ``kda_inverse``, ``kda_operands`` and ``kda_backward`` and its
    carry and output stage the walk's ``delta_carry`` and ``delta_carry_back``,
    custom calls of those names in a trace and no loop between them; ``q``,
    ``k``, ``v`` and ``g`` go to the rule as ``[B, T, H, d]``, whose ``[B, T, H
    d]`` view the kernels read in place) and
    ``kda_gate``. Sown into ``"metrics"``: ``kda_decay_mean`` (the
    mean of ``exp(g)``), ``kda_beta_mean``, ``kda_log_decay_min`` (the most
    negative ``g`` of the step: under -5.5 the rule's form before PR 51, a
    sub-block of 16 steps under one reference, was not finite) and
    ``kda_state_absmax`` (the largest magnitude in the state after the last
    step); into ``"intermediates"`` the rule's own inputs. What a remat policy saves bears the scalar rule's names
    (``REMAT_NAMES``): under ``"save_flash"`` the carry runs once forward and
    once in reverse a layer and the solve (``kda_inverse``) once; the rest
    of the chunk-local stage (``kda_operands``) runs again when the backward
    reaches the rule, to remake the carry's operands. The six in projections'
    outputs bear ``mixer_in`` (``models/mamba.py:projected``; of a low-rank pair
    the second matrix's, ``f`` in float32 as it is): the same policy hands them
    to the block's recomputation, which multiplies only a pair's first
    ``gate_rank`` columns again.
    """

    spec: KimiDeltaSpec
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        s = self.spec
        batch, t, d_model = x.shape
        h, d_k, d_v = s.num_heads, s.key_dim, s.value_dim
        safe = s.lower_bound is not None
        if safe and not s.lower_bound < 0:
            raise ValueError(
                "KimiDeltaMixer: lower_bound %g is no bound below zero" % s.lower_bound
            )
        f32 = jnp.float32
        dense = lambda width, name, **how: nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=self.dtype, name=name, **how
        )
        taps = lambda name, width: self.param(  # noqa: E731
            name, lambda key, shape: jax.random.uniform(key, shape, f32, -0.5, 0.5),
            (s.d_conv, width),
        )

        with jax.named_scope("kda_proj"):
            q, k, v = (
                dense(h * width, name)(x)
                for name, width in (("q_proj", d_k), ("k_proj", d_k), ("v_proj", d_v))
            )
            # the decay's projection leaves its accumulator in float32: rounded
            # to bfloat16 first, a log-decay near the gate's steepest point
            # moves by 0.1 (exp(A_log) up to 16 times a slope of 5 / 4)
            wide = dict(
                dot_general=functools.partial(jax.lax.dot_general, preferred_element_type=f32)
            )
            if s.gate_rank is None:
                f = dense(h * d_k, "f_proj", **wide)(x)
                gate = dense(h * d_v, "g_proj")(x)
            else:
                # the first of each pair whole on a chip with a share of the
                # heads, the second cut by heads; the gate's second with a bias
                f = dense(h * d_k, "f_up", **wide)(dense(s.gate_rank, "f_down")(x))
                gate = nn.Dense(h * d_v, dtype=self.dtype, name="g_up")(
                    dense(s.gate_rank, "g_down")(x)
                )
            b = dense(h, "b_proj")(x)
            # what the six hand on, by name (of a low-rank pair the second
            # matrix's output; ``f`` in the float32 it is in)
            q, k, v, f, gate, b = projected("kda", q, k, v, f, gate, b)
            f = f.reshape(batch, t, h, d_k)
            b = b.astype(f32)

        with jax.named_scope("kda_conv"):
            q, k, v = (
                causal_conv_silu(m, taps(name, m.shape[-1]), None)
                for name, m in (("q_conv", q), ("k_conv", k), ("v_conv", v))
            )

        a_log = self.param("A_log", _kda_a_log_init, (h,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (h, d_k))
        scale = self.param("norm", nn.initializers.ones, (d_v,))

        with jax.named_scope("kda_scan"):
            q = _unit(q.reshape(batch, t, h, d_k).astype(f32)) * d_k ** -0.5
            k = _unit(k.reshape(batch, t, h, d_k).astype(f32))
            q, k = q.astype(self.dtype), k.astype(self.dtype)
            v = v.reshape(batch, t, h, d_v)
            beta = jax.nn.sigmoid(b) * (2.0 if s.neg_eigval else 1.0)
            if safe:
                g = s.lower_bound * jax.nn.sigmoid(jnp.exp(a_log)[:, None] * (f + dt_bias))
            else:
                g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(f + dt_bias)
            # as GatedDeltaMixer's: what survives a layer's forward under a
            # policy that saves the names is the rule's o, its final state, the
            # states the chunks inherit, V_new and every chunk's T
            rule = jax.checkpoint(
                functools.partial(
                    kda_rule, chunk=s.chunk, return_final_state=True,
                    caller=dict(
                        gate="safe" if safe else "softplus", bound=s.lower_bound,
                        beta_max=2.0 if s.neg_eigval else 1.0, rank=s.gate_rank,
                    ),
                ),
                policy=jax.checkpoint_policies.save_only_these_names(*REMAT_NAMES),
            )
            o, state = (
                checkpoint_name(a, OUT_NAME) for a in rule(q, k, v, g, beta)
            )
        self.sow("metrics", "kda_decay_mean", jnp.mean(jnp.exp(g)))
        self.sow("metrics", "kda_beta_mean", jnp.mean(beta))
        self.sow("metrics", "kda_log_decay_min", jnp.min(g))
        self.sow("metrics", "kda_state_absmax", jnp.max(jnp.abs(state)))
        self.sow("intermediates", "rule_inputs", (q, k, v, g, beta))

        with jax.named_scope("kda_gate"):
            o = o.astype(f32)
            o = o * jax.lax.rsqrt(
                jnp.mean(o * o, axis=-1, keepdims=True) + self.norm_eps
            )
            o = o * scale * nn.sigmoid(gate.reshape(batch, t, h, d_v).astype(f32))
            o = o.reshape(batch, t, h * d_v).astype(self.dtype)

        with jax.named_scope("kda_proj"):
            return dense(d_model, "o_proj")(o)
