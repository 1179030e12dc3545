"""Mixture-of-Experts layers.

Net-new versus the reference (no MoE/expert parallelism anywhere in its
tree — SURVEY §2 parallelism inventory). Two layers live here:

- :class:`DroplessMoE` — the expert layer of today's open MoE models
  (OLMoE, and with ``norm_topk_prob`` the Moonlight / Trinity lineage):
  softmax router in float32, top-k of many small SiLU-gated experts,
  **no capacity and no dropped token**. Tokens are sorted by expert and
  the three projections run as grouped matrix multiplications over the
  ragged groups (``edl_tpu.ops.grouped_matmul``); shapes are static
  whatever the imbalance. Sows the load-balancing and router-z losses
  into ``"losses"`` and the busiest expert's relative load into
  ``"metrics"``; ``create_state`` sees both collections in
  ``model.init``'s result, so the train step adds and reports them with
  no flag from the caller. :class:`MoESpec` describes it to
  ``TransformerLM``.
- :class:`SwitchMoE` — the older capacity-bounded layer in the
  dispatch/combine **einsum formulation** (Mesh-TensorFlow / GShard
  lineage): a one-hot ``[B, S, E, C]`` dispatch tensor, tokens dropped
  past capacity, ungated GELU experts, Switch top-1 or GShard top-2
  routing. Its einsums contract token and expert axes, so with expert
  weights sharded over ``ep`` GSPMD inserts the all-to-alls itself. It
  describes no published model and is kept for its expert-parallel
  tests until ROADMAP D6 removes it.

Both keep expert weights with a leading ``[E, ...]`` axis so that
``MOE_EP_RULES`` can shard them over ``ep``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from edl_tpu.ops.grouped_matmul import grouped_matmul


@dataclasses.dataclass(frozen=True)
class MoESpec:
    """The expert layer of a ``TransformerLM``, as one hashable field:
    every block's feed-forward is a :class:`DroplessMoE` of this shape.
    (Which blocks mix by attention and which by a state-space layer is
    ``models/transformer.py:ArchSpec.layer_types``; a model's leading
    dense feed-forward layers would join it there.)"""

    num_experts: int
    top_k: int
    d_ff: int                      # width of ONE expert
    norm_topk_prob: bool = False   # renormalise the k weights to sum 1
    aux_weight: float = 1e-2       # alpha of the load-balancing loss
    z_weight: float = 1e-3         # beta of the router z-loss


def _sum_unsorted(rows, inverse, k):
    """``sum_j rows[inverse[n * k + j]]`` in float32: expert order back to
    (token, choice) order, where a token's ``k`` rows are neighbours, and
    their sum."""
    n = rows.shape[0] // k
    back = rows[inverse].reshape(n, k, rows.shape[-1])
    return jnp.sum(back, axis=1, dtype=jnp.float32)


def _rows_sorted(tokens, order, inverse, k):
    """``tokens[order // k]``: row ``r`` of the result is the token of the
    ``r``-th (token, choice) pair in expert order. Its gradient is
    :func:`_rows_combined`'s forward (a gather and a sum over ``k``
    neighbours), not the scatter-add jax would derive."""

    @jax.custom_vjp
    def take(tokens, order, inverse):
        return tokens[order // k]

    def fwd(tokens, order, inverse):
        return take(tokens, order, inverse), inverse

    def bwd(inverse, grad):
        return _sum_unsorted(grad, inverse, k).astype(grad.dtype), None, None

    take.defvjp(fwd, bwd)
    return take(tokens, order, inverse)


def _scalars_sorted(values, order, inverse):
    """``values[order]`` for a vector, with the gradient ``grad[inverse]``.
    Both are taken as a sort that carries the scalars along (sorting by a
    permutation's inverse applies the permutation): on the v5e a sort of
    131,072 pairs takes 0.1 ms and a gather of as many scalars 1.1-1.7."""

    @jax.custom_vjp
    def take(values, order, inverse):
        return jax.lax.sort((inverse, values), num_keys=1)[1]

    def fwd(values, order, inverse):
        return take(values, order, inverse), order

    def bwd(order, grad):
        return jax.lax.sort((order, grad), num_keys=1)[1], None, None

    take.defvjp(fwd, bwd)
    return take(values, order, inverse)


def _rows_combined(rows, order, inverse, k):
    """``y[n] = sum_j rows[inverse[n * k + j]]`` in float32: the transpose
    of :func:`_rows_sorted`. Its gradient is that function's forward,
    ``grad[order // k]``, so it keeps ``order`` and no row."""

    @jax.custom_vjp
    def combine(rows, order, inverse):
        return _sum_unsorted(rows, inverse, k)

    def fwd(rows, order, inverse):
        return combine(rows, order, inverse), order

    def bwd(order, grad):
        return grad.astype(rows.dtype)[order // k], None, None

    combine.defvjp(fwd, bwd)
    return combine(rows, order, inverse)


class DroplessMoE(nn.Module):
    """Dropless top-k mixture of SiLU-gated experts.

    Per token ``x`` (``[B, S, D]`` in, ``[B, S, D]`` out)::

        p      = softmax(W_r x)                    float32, over E
        w, e   = top_k(p)                          w as it is, or w / sum(w)
        y      = sum_j W_down[e_j] (w_j * silu(W_gate[e_j] x) * W_up[e_j] x)

    The routing weight multiplies the expert's activation, not its output:
    the down projection is linear, so the value is the same, and the
    combine is then a plain permutation-and-sum of rows whose gradient is
    a gather out of ``dy``. It keeps no residual, so the backward neither
    re-runs the down projection nor un-sorts its result only to
    differentiate ``w`` (whose gradient is a row reduction over ``F``
    inside the activation's backward; ``w`` reaches expert order and its
    gradient leaves it as a sort's payload). ``_rows_sorted`` and
    ``_rows_combined`` are each other's transpose, and each one's gradient
    is the other's forward.

    The N*k (token, choice) pairs are sorted by expert; ``gate``, ``up``
    and ``down`` (``[E, D, F]``, ``[E, D, F]``, ``[E, F, D]``, float32
    parameters, computed in ``dtype``) are three grouped matrix
    multiplications over the E ragged groups. Sown:

    - ``"losses"/load_balance`` = ``aux_weight * E * sum_i f_i * P_i`` with
      ``P_i`` the mean of ``p_i`` over the tokens and ``f_i`` the share of
      the N*k **assignments** that went to expert ``i`` (so ``sum f = 1``
      and a uniform router gives ``aux_weight``). Implementations that
      count ``f_i`` as a share of the N tokens (Hugging Face's
      ``load_balancing_loss_func``) are larger by the factor k.
    - ``"losses"/router_z`` = ``z_weight * mean(logsumexp(W_r x)^2)``.
    - ``"metrics"/moe_load_max`` = the busiest expert's assignments over
      the mean (1.0 is perfect balance, E is one expert taking all).
    - ``"intermediates"/top_idx`` and ``/router_logits`` = the chosen
      experts ``[N, k]`` and ``W_r x`` ``[N, E]`` (only when a caller
      asks for the collection: a check against a reference).

    Device-side names: ``moe_route`` (router, top-k, sort, losses),
    ``moe_experts`` (gather, the routing weights and the three grouped
    matmuls), ``moe_combine`` (un-sort and sum over k).
    """

    num_experts: int
    top_k: int
    d_ff: int
    norm_topk_prob: bool = False
    aux_weight: float = 1e-2
    z_weight: float = 1e-3
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, s, d = x.shape
        e, k, f = self.num_experts, self.top_k, self.d_ff
        n = b * s
        tokens = x.reshape(n, d)

        with jax.named_scope("moe_route"):
            logits = nn.Dense(
                e, use_bias=False, dtype=jnp.float32, name="router",
                precision=jax.lax.Precision.HIGHEST,
            )(tokens.astype(jnp.float32))               # [N, E]
            probs = jax.nn.softmax(logits, axis=-1)
            weights, top_idx = jax.lax.top_k(probs, k)  # [N, k]
            if self.norm_topk_prob:
                weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
            flat = top_idx.reshape(n * k)
            order = jnp.argsort(flat)                   # stable: by expert, then pair
            inverse = jnp.argsort(order)
            group_sizes = jnp.sum(
                jax.nn.one_hot(top_idx, e, dtype=jnp.int32), axis=(0, 1)
            )                                           # [E], sums to N*k
            share = group_sizes.astype(jnp.float32) / (n * k)
            self.sow(
                "losses", "load_balance",
                self.aux_weight * e * jnp.sum(share * jnp.mean(probs, axis=0)),
            )
            self.sow(
                "losses", "router_z",
                self.z_weight
                * jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
            )
            self.sow("metrics", "moe_load_max", jnp.max(share) * e)
            self.sow("intermediates", "top_idx", top_idx)
            self.sow("intermediates", "router_logits", logits)

        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=0)
        w_gate = self.param("gate", init, (e, d, f), jnp.float32)
        w_up = self.param("up", init, (e, d, f), jnp.float32)
        w_down = self.param("down", init, (e, f, d), jnp.float32)

        with jax.named_scope("moe_experts"):
            rows = _rows_sorted(tokens.astype(self.dtype), order, inverse, k)
            w_sorted = _scalars_sorted(weights.reshape(n * k), order, inverse)
            gate = grouped_matmul(rows, w_gate.astype(self.dtype), group_sizes)
            up = grouped_matmul(rows, w_up.astype(self.dtype), group_sizes)
            hidden = (
                nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
                * w_sorted[:, None]
            ).astype(self.dtype)                        # rounded once
            out = grouped_matmul(hidden, w_down.astype(self.dtype), group_sizes)

        with jax.named_scope("moe_combine"):
            y = _rows_combined(out, order, inverse, k)  # [N, D], float32
        return y.reshape(b, s, d).astype(x.dtype)


class SwitchMoE(nn.Module):
    """Top-1 routed expert FFN bank (drop-past-capacity, static shapes).

    Input/output: ``[B, S, D]``. Expert weights: ``[E, ...]`` — shard the
    leading axis over ``ep`` (see ``MOE_EP_RULES``).
    """

    num_experts: int = 8
    d_ff: int = 2048
    capacity_factor: float = 1.25
    aux_weight: float = 1e-2
    top_k: int = 1  # 1 = Switch routing; 2 = GShard-style top-2
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, s, d = x.shape
        e, k = self.num_experts, self.top_k
        capacity = max(1, int(self.capacity_factor * k * s / e))

        # -- routing (fp32 for numerics) --------------------------------
        gate_logits = nn.Dense(
            e, use_bias=False, dtype=jnp.float32, name="router"
        )(x.astype(jnp.float32))                      # [B, S, E]
        probs = jax.nn.softmax(gate_logits, axis=-1)
        topk_prob, topk_idx = jax.lax.top_k(probs, k)  # [B, S, k]
        if k > 1:
            # renormalize over the selected experts (GShard combine
            # weights). NOT at k=1: Switch scales by the raw gate prob
            # (y = p_i(x) E_i(x)) — renormalizing would make the combine
            # weight a constant 1.0 and cut the router's task gradient.
            topk_prob = topk_prob / jnp.sum(topk_prob, axis=-1, keepdims=True)
        oh_k = jax.nn.one_hot(topk_idx, e, dtype=jnp.float32)  # [B,S,k,E]

        # queue position per expert, CHOICE-MAJOR (GShard: all 1st choices
        # claim capacity before any 2nd choice), then drop past capacity
        oh_cm = jnp.transpose(oh_k, (0, 2, 1, 3)).reshape(b, k * s, e)
        pos_cm = jnp.cumsum(oh_cm, axis=1) * oh_cm    # [B, k*S, E], 1-based
        pos = jnp.transpose(
            pos_cm.reshape(b, k, s, e), (0, 2, 1, 3)
        )                                              # [B, S, k, E]
        keep = (pos > 0) & (pos <= capacity)
        pos0 = jnp.clip(pos - 1, 0, capacity - 1).astype(jnp.int32)

        # dispatch tensor [B, S, E, C]: sum the per-choice slot one-hots
        dispatch_k = (
            keep[..., None]
            * jax.nn.one_hot(pos0, capacity, dtype=jnp.float32)
        )                                              # [B, S, k, E, C]
        dispatch = jnp.sum(dispatch_k, axis=2)         # [B, S, E, C]
        combine = jnp.sum(
            dispatch_k * topk_prob[..., None, None], axis=2
        )                                              # [B, S, E, C]

        # -- load-balancing aux loss (Switch eq. 4; first choice only) ---
        frac_tokens = jnp.mean(oh_k[:, :, 0], axis=(0, 1))
        frac_probs = jnp.mean(probs, axis=(0, 1))
        aux = self.aux_weight * e * jnp.sum(frac_tokens * frac_probs)
        self.sow("losses", "moe_aux", aux)

        # -- dispatch -> expert FFN -> combine (all einsums; GSPMD turns
        # the token<->expert contractions into ep all-to-alls) -----------
        xd = x.astype(self.dtype)
        expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(self.dtype), xd)

        wi = self.param(
            "wi", nn.initializers.lecun_normal(), (e, d, self.d_ff), jnp.float32
        )
        wo = self.param(
            "wo", nn.initializers.lecun_normal(), (e, self.d_ff, d), jnp.float32
        )
        h = jnp.einsum("ebcd,edf->ebcf", expert_in, wi.astype(self.dtype))
        h = nn.gelu(h)
        expert_out = jnp.einsum("ebcf,efd->ebcd", h, wo.astype(self.dtype))

        out = jnp.einsum(
            "bsec,ebcd->bsd", combine.astype(self.dtype), expert_out
        )
        return out.astype(x.dtype)


# Expert-parallel sharding rules: expert banks split their leading [E] axis
# over ``ep``; the router stays replicated. ``w[io]`` are SwitchMoE's two
# banks, ``gate``/``up``/``down`` DroplessMoE's three.
MOE_EP_RULES = [
    (r".*/moe/(w[io]|gate|up|down)$", P("ep", None, None)),
]
