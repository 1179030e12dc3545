"""Pipeline parallelism: GPipe fill-drain schedule over the ``pp`` axis.

Net-new versus the reference (SURVEY §2 parallelism inventory: no
TP/PP/SP anywhere in its tree), built the TPU way: each ``pp`` rank holds
one pipeline stage's weights (a stacked ``[PP, ...]`` pytree sharded on
the leading axis); microbatch activations flow rank-to-rank via
``lax.ppermute`` inside a ``lax.scan`` over schedule ticks, so XLA lowers
stage handoff to ICI neighbor exchanges and the backward pipeline falls
out of autodiff (the transpose of ``ppermute`` is the reverse permute).

The schedule is plain GPipe: ``M`` microbatches drain through ``PP``
stages in ``M + PP - 1`` ticks (``pipeline_efficiency`` gives the ideal
``M / (M + PP - 1)`` utilization bound); bubble ticks compute on zeros.
Peak per-device live state is one microbatch activation per tick plus the
stage weights — combine with ``jax.checkpoint`` on the stage fn for long
pipelines.

Beyond the repeated-block body, the schedule supports *non-shape-
preserving* first and last stages (``first_fn``/``last_fn``): the first
rank maps the raw feed (e.g. token ids) into the circulating activation
shape, the last rank maps activations into outputs (e.g. logits, or a
per-example loss so only scalars ever leave the pipeline). Both run
under ``lax.cond`` on the rank index, so only the owning rank pays their
FLOPs. Results are delivered by stacking each rank's output bank on a
pp-sharded leading axis and slicing the last entry — a broadcast of the
real data only, not a ``psum`` over PP-1 banks of zeros.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_efficiency(num_microbatches: int, pp: int) -> float:
    """GPipe ideal utilization: M busy ticks out of M + PP - 1 total."""
    return num_microbatches / (num_microbatches + pp - 1)


def _pipeline_shard(
    body_fn,
    first_fn,
    last_fn,
    num_micro: int,
    axis: str,
    body_params,
    first_params,
    last_params,
    x,
    last_aux,
):
    """Runs on ONE pp rank inside shard_map.

    ``body_params``: this rank's stage weights (leading stage axis
    stripped to size 1 by shard_map; squeezed here). ``x``: [M, mb, ...]
    microbatch feeds (replicated over pp). ``first_params``/``last_params``
    are replicated; their compute is rank-gated by ``lax.cond``.
    ``last_aux``: optional [M, ...] per-microbatch side input handed to
    ``last_fn`` (e.g. targets for an in-pipeline loss).
    """
    pp = jax.lax.psum(1, axis)
    rank = jax.lax.axis_index(axis)
    body_params = jax.tree.map(lambda p: jnp.squeeze(p, axis=0), body_params)
    feed_shape = x.shape[1:]

    feed_sd = jax.ShapeDtypeStruct(feed_shape, x.dtype)
    if first_fn is not None:
        act_sd = jax.eval_shape(first_fn, first_params, feed_sd)
    else:
        act_sd = feed_sd
    if act_sd.shape != feed_shape and first_fn is None:
        raise ValueError("shape-changing input requires first_fn")
    out_sd = jax.eval_shape(body_fn, body_params, act_sd)
    if out_sd.shape != act_sd.shape or out_sd.dtype != act_sd.dtype:
        raise ValueError(
            "body_fn must preserve the activation shape/dtype "
            "(%r -> %r); shape changes belong in first_fn/last_fn"
            % (act_sd, out_sd)
        )
    if last_fn is not None:
        if last_aux is not None:
            aux_sd = jax.ShapeDtypeStruct(last_aux.shape[1:], last_aux.dtype)
            y_sd = jax.eval_shape(last_fn, last_params, act_sd, aux_sd)
        else:
            y_sd = jax.eval_shape(last_fn, last_params, act_sd)
    else:
        y_sd = act_sd

    ticks = num_micro + pp - 1
    fwd_perm = [(i, i + 1) for i in range(pp - 1)]

    def tick(carry, t):
        prev_out, outputs = carry
        # activation arriving from the previous stage this tick
        incoming = jax.lax.ppermute(prev_out, axis, fwd_perm)
        # stage 0 injects microbatch t (zeros once the pipe is draining)
        feed = jax.lax.cond(
            t < num_micro,
            lambda: jax.lax.dynamic_index_in_dim(
                x, jnp.minimum(t, num_micro - 1), keepdims=False
            ),
            lambda: jnp.zeros(feed_shape, x.dtype),
        )
        if first_fn is not None:
            my_input = jax.lax.cond(
                rank == 0,
                lambda: first_fn(first_params, feed),
                lambda: incoming,
            )
        else:
            my_input = jnp.where(rank == 0, feed, incoming)
        out = body_fn(body_params, my_input)
        # the microbatch the LAST rank just finished (valid once >= 0)
        mb_idx = t - (pp - 1)
        if last_fn is not None:
            if last_aux is not None:
                aux = jax.lax.dynamic_index_in_dim(
                    last_aux, jnp.clip(mb_idx, 0, num_micro - 1),
                    keepdims=False,
                )
                mk_y = lambda: last_fn(last_params, out, aux)
            else:
                mk_y = lambda: last_fn(last_params, out)
            y = jax.lax.cond(
                (rank == pp - 1) & (mb_idx >= 0),
                mk_y,
                lambda: jnp.zeros(y_sd.shape, y_sd.dtype),
            )
        else:
            y = out
        outputs = jax.lax.cond(
            mb_idx >= 0,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, jnp.maximum(mb_idx, 0), axis=0
            ),
            lambda o: o,
            outputs,
        )
        return (out, outputs), None

    zeros_out = jnp.zeros(act_sd.shape, act_sd.dtype)
    outputs0 = jnp.zeros((num_micro,) + y_sd.shape, y_sd.dtype)
    (_, outputs), _ = jax.lax.scan(
        tick, (zeros_out, outputs0), jnp.arange(ticks)
    )
    # deliver by stacking banks on a pp-sharded leading axis; the caller
    # slices the last entry, so only the real data is ever broadcast
    # (non-last ranks' banks are dead stores XLA can sink)
    return outputs[None]


def pipeline_apply(
    stage_fn: Callable,
    stacked_params,
    x: jax.Array,
    mesh: Mesh,
    num_microbatches: int,
    axis: str = "pp",
    first_fn: Optional[Callable] = None,
    first_params: Any = None,
    last_fn: Optional[Callable] = None,
    last_params: Any = None,
    last_aux: Optional[jax.Array] = None,
    batch_axis: Optional[str] = None,
):
    """Apply a ``PP``-stage pipeline to ``x``.

    ``stage_fn(stage_params, micro) -> micro`` is the repeated body; it
    must preserve the circulating activation shape. ``stacked_params`` is
    a pytree with leading stage axis ``PP`` (sharded over ``axis``).
    ``x``: [batch, ...]; batch must divide into ``num_microbatches``.

    Optional non-shape-preserving edges:

    - ``first_fn(first_params, micro_feed) -> activation`` runs on rank 0
      only, mapping the raw feed (e.g. int tokens) into the activation
      the body circulates.
    - ``last_fn(last_params, activation[, aux]) -> y`` runs on the last
      rank only (e.g. head projection, or a per-example loss). ``aux``
      is ``last_aux[mb]``, an optional [batch, ...] side input (targets)
      microbatched alongside ``x``.
    - ``batch_axis``: mesh axis to shard the microbatch dimension over
      (data parallelism inside the pipeline; grads for replicated
      first/last params are psum'ed by the shard_map transpose).

    Returns the last stage's outputs, shape ``[batch, *y.shape[1:]]``
    (per-microbatch results are re-flattened when ``last_fn`` keeps the
    microbatch dimension; otherwise ``[M, *y.shape]``).
    """
    if axis not in mesh.shape:
        raise ValueError("mesh has no %r axis (axes: %r)" % (axis, mesh.axis_names))
    batch = x.shape[0]
    if batch % num_microbatches:
        raise ValueError(
            "batch %d not divisible into %d microbatches"
            % (batch, num_microbatches)
        )
    mb = batch // num_microbatches
    micro = x.reshape((num_microbatches, mb) + x.shape[1:])
    aux = None
    if last_aux is not None:
        if last_aux.shape[0] != batch:
            raise ValueError(
                "last_aux batch %d != x batch %d" % (last_aux.shape[0], batch)
            )
        aux = last_aux.reshape(
            (num_microbatches, mb) + last_aux.shape[1:]
        )

    # pre-compute the per-microbatch output shape to build the out_spec
    # (and to sanity-check dp compatibility) before tracing the shard body
    mb_local = mb
    if batch_axis is not None:
        if batch_axis not in mesh.shape:
            raise ValueError(
                "mesh has no %r axis (axes: %r)"
                % (batch_axis, mesh.axis_names)
            )
        if mb % mesh.shape[batch_axis]:
            raise ValueError(
                "microbatch size %d not divisible by %r axis size %d"
                % (mb, batch_axis, mesh.shape[batch_axis])
            )
        mb_local = mb // mesh.shape[batch_axis]
    feed_sd = jax.ShapeDtypeStruct((mb_local,) + x.shape[1:], x.dtype)
    act_sd = (
        jax.eval_shape(first_fn, first_params, feed_sd)
        if first_fn is not None else feed_sd
    )
    if last_fn is not None:
        if aux is not None:
            aux_sd = jax.ShapeDtypeStruct(
                (mb_local,) + last_aux.shape[1:], last_aux.dtype
            )
            y_sd = jax.eval_shape(last_fn, last_params, act_sd, aux_sd)
        else:
            y_sd = jax.eval_shape(last_fn, last_params, act_sd)
    else:
        y_sd = act_sd
    keeps_mb = len(y_sd.shape) >= 1 and y_sd.shape[0] == mb_local
    if batch_axis is not None and not keeps_mb:
        raise ValueError(
            "batch_axis=%r requires last_fn to keep the microbatch "
            "dimension (got per-microbatch shape %r) — return per-example "
            "values (e.g. a [mb] loss vector) so dp shards aren't dropped"
            % (batch_axis, y_sd.shape)
        )

    param_specs = jax.tree.map(
        lambda p: P(axis, *([None] * (p.ndim - 1))), stacked_params
    )
    data_spec = P(None, batch_axis)  # [M, mb, ...]: mb optionally dp-sharded
    out_spec = P(
        axis, None, *([batch_axis] + [None] * (len(y_sd.shape) - 1)
                      if keeps_mb else [None] * len(y_sd.shape))
    )
    fn = partial(
        _pipeline_shard, stage_fn, first_fn, last_fn, num_microbatches, axis
    )
    out = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(param_specs, P(), P(), data_spec, data_spec),
        out_specs=out_spec,
        check_vma=False,
    )(stacked_params, first_params, last_params, micro, aux)
    out = out[-1]  # last rank's bank: [M, *y_shape]
    if out.ndim >= 2 and out.shape[1] == mb:
        return out.reshape((batch,) + out.shape[2:])
    return out


def stack_stage_params(per_stage_params):
    """Stack a list of per-stage pytrees (one per pp rank) into the
    leading-axis form ``pipeline_apply`` expects."""
    return jax.tree.map(
        lambda *leaves: jnp.stack(leaves, axis=0), *per_stage_params
    )
