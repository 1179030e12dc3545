"""Pipeline-parallel TransformerLM: embed → block stages → head.

Stage-splits :class:`~edl_tpu.models.transformer.TransformerLM` over the
``pp`` mesh axis using the GPipe schedule in
:mod:`edl_tpu.parallel.pipeline`:

- the **embedding** runs on rank 0 only (``first_fn`` under ``lax.cond``),
  turning int tokens into the circulating ``[mb, T, D]`` activation;
- the **transformer blocks** are grouped into ``PP`` equal stages; each
  stage's ``L/PP`` blocks are applied by a ``lax.scan`` over their stacked
  params (weights live sharded ``[PP, L/PP, ...]`` on the ``pp`` axis);
- the **final norm + lm_head** run on the last rank only. For training,
  :func:`pipeline_lm_loss` folds the cross-entropy into the last stage so
  only per-example loss scalars ever leave the pipeline — no logits
  broadcast at all.

Net-new capability versus the reference (SURVEY §2: no pipeline
parallelism anywhere in its tree). Combine with ``batch_axis="dp"`` for
dp×pp meshes; grads for the replicated embed/head params are psum'ed
across ranks by the shard_map transpose automatically.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from edl_tpu.models.transformer import (
    Block,
    LMHead,
    RMSNorm,
    TransformerLM,
    _remat_policy,
)
from edl_tpu.parallel.pipeline import pipeline_apply, stack_stage_params


class LMStageParams(NamedTuple):
    """TransformerLM params rearranged for pipeline execution."""

    embed: Any  # {'embedding': [V, D]} — replicated; used by rank 0
    body: Any   # block pytree stacked [PP, L/PP, ...] — shard over pp
    head: Any   # {'ln_f': ..., 'lm_head': ...} — replicated; last rank


def _check_model(model: TransformerLM, pp: int) -> int:
    if model.num_experts > 0 or model.moe is not None:
        raise ValueError(
            "pipeline parallelism requires homogeneous (dense) blocks; "
            "MoE layers change the per-layer param structure"
        )
    if model.arch is not None:
        raise ValueError(
            "pipeline parallelism builds the dense block and an untied "
            "head; a model with an ArchSpec is not split into stages"
        )
    if model.num_layers % pp:
        raise ValueError(
            "num_layers %d not divisible by pp %d" % (model.num_layers, pp)
        )
    return model.num_layers // pp


def split_lm_params(model: TransformerLM, params, pp: int) -> LMStageParams:
    """Rearrange a flat TransformerLM param dict (``state.params``) into
    pipeline form: blocks double-stacked ``[PP, L/PP, ...]``."""
    lps = _check_model(model, pp)
    layers = [params["layer_%d" % i] for i in range(model.num_layers)]
    stages = []
    for s in range(pp):
        group = layers[s * lps:(s + 1) * lps]
        stages.append(jax.tree.map(lambda *ls: jnp.stack(ls, axis=0), *group))
    return LMStageParams(
        embed=params["embed"],
        body=stack_stage_params(stages),
        head={"ln_f": params["ln_f"], "lm_head": params["lm_head"]},
    )


def merge_lm_params(model: TransformerLM, split: LMStageParams):
    """Inverse of :func:`split_lm_params` (checkpoint/eval interop)."""
    pp = jax.tree.leaves(split.body)[0].shape[0]
    lps = _check_model(model, pp)
    out = {
        "embed": split.embed,
        "ln_f": split.head["ln_f"],
        "lm_head": split.head["lm_head"],
    }
    for i in range(model.num_layers):
        s, j = divmod(i, lps)
        out["layer_%d" % i] = jax.tree.map(
            lambda leaf, s=s, j=j: leaf[s, j], split.body
        )
    return out


def _make_fns(model: TransformerLM):
    block = Block(
        model.num_heads, model.d_ff, model.dtype, model.attention_fn,
        num_kv_heads=model.num_kv_heads, norm_eps=model.norm_eps,
        qk_norm=model.qk_norm,
    )
    embed_mod = nn.Embed(model.vocab_size, model.d_model, dtype=model.dtype)
    norm = RMSNorm(model.norm_eps)
    head_mod = LMHead(model.vocab_size)

    def apply_block(bp, h, positions):
        return block.apply({"params": bp}, h, positions)

    if model.remat:
        # same policy contract as the single-device path (nn.remat in
        # TransformerLM.__call__): save_flash keeps the attention
        # kernel's out+lse across the backward
        apply_block = jax.checkpoint(
            apply_block, policy=_remat_policy(model.remat_policy)
        )

    def body_fn(stage_params, h):
        positions = jnp.broadcast_to(
            jnp.arange(h.shape[1])[None, :], h.shape[:2]
        )

        def one(carry, bp):
            return apply_block(bp, carry, positions), None

        h, _ = jax.lax.scan(one, h, stage_params)
        return h

    def first_fn(ep, tokens):
        return embed_mod.apply({"params": ep}, tokens)

    def head_fn(hp, h):
        h = norm.apply({"params": hp["ln_f"]}, h)
        return head_mod.apply({"params": hp["lm_head"]}, h)

    return body_fn, first_fn, head_fn


def pipeline_lm_logits(
    model: TransformerLM,
    split: LMStageParams,
    tokens: jax.Array,
    mesh: Mesh,
    num_microbatches: int,
    axis: str = "pp",
    batch_axis: Optional[str] = None,
) -> jax.Array:
    """Forward pass → logits ``[B, T, V]`` (eval path; the full logits
    tensor is broadcast from the last rank — prefer
    :func:`pipeline_lm_loss` for training)."""
    body_fn, first_fn, head_fn = _make_fns(model)
    return pipeline_apply(
        body_fn, split.body, tokens, mesh, num_microbatches, axis=axis,
        first_fn=first_fn, first_params=split.embed,
        last_fn=head_fn, last_params=split.head,
        batch_axis=batch_axis,
    )


def _make_last_loss(head_fn):
    """Per-example next-token CE on the last rank — THE loss definition
    both the GPipe path and the 1F1B path must share."""

    def last_loss(hp, h, tgt):
        logits = head_fn(hp, h)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgt
        ).mean(axis=-1)  # [mb]

    return last_loss


def pipeline_lm_loss(
    model: TransformerLM,
    split: LMStageParams,
    tokens: jax.Array,
    targets: jax.Array,
    mesh: Mesh,
    num_microbatches: int,
    axis: str = "pp",
    batch_axis: Optional[str] = None,
) -> jax.Array:
    """Mean next-token cross-entropy, computed INSIDE the pipeline: the
    last rank projects to logits and reduces them to a per-example loss,
    so the only cross-stage traffic is activations + [mb] scalars."""
    body_fn, first_fn, head_fn = _make_fns(model)
    last_loss = _make_last_loss(head_fn)

    per_example = pipeline_apply(
        body_fn, split.body, tokens, mesh, num_microbatches, axis=axis,
        first_fn=first_fn, first_params=split.embed,
        last_fn=last_loss, last_params=split.head, last_aux=targets,
        batch_axis=batch_axis,
    )
    return per_example.mean()


def pipeline_lm_1f1b_grads(
    model: TransformerLM,
    split: LMStageParams,
    tokens: jax.Array,
    targets: jax.Array,
    mesh: Mesh,
    num_microbatches: int,
    axis: str = "pp",
    batch_axis: Optional[str] = None,
):
    """(loss, grads-as-LMStageParams) via the memory-bounded 1F1B schedule
    (:mod:`edl_tpu.parallel.pipeline_1f1b`) — same numbers as
    ``jax.value_and_grad`` over :func:`pipeline_lm_loss`, but peak live
    activations stay ~PP per device instead of growing with the
    microbatch count."""
    from edl_tpu.parallel.pipeline_1f1b import pipeline_1f1b_loss_and_grads

    body_fn, first_fn, head_fn = _make_fns(model)
    last_loss = _make_last_loss(head_fn)

    loss, (d_body, d_first, d_last) = pipeline_1f1b_loss_and_grads(
        body_fn, split.body, tokens, mesh, num_microbatches,
        first_fn=first_fn, first_params=split.embed,
        last_loss_fn=last_loss, last_params=split.head,
        last_aux=targets, axis=axis, batch_axis=batch_axis,
    )
    return loss, LMStageParams(embed=d_first, body=d_body, head=d_last)
