"""1F1B pipeline schedule: memory-bounded training over the ``pp`` axis.

GPipe (``edl_tpu.parallel.pipeline``) runs all forwards then lets
autodiff run all backwards, so per-device live activations grow with the
microbatch count M. The 1F1B schedule (Megatron's non-interleaved
pipeline) interleaves: after a warmup of ``PP-1-r`` forwards, rank ``r``
alternates one-forward-one-backward, so at most ~PP microbatch
activations are ever live per device — M can grow (shrinking the bubble,
``(PP-1)/(M+PP-1)``) without growing memory.

Because the backward IS part of the schedule, this module computes
``(loss, grads)`` directly (the Megatron shape) instead of being
differentiable: each backward tick runs ``jax.vjp`` over the composite
stage (recompute-based, so residual stash = one activation per in-flight
microbatch), gradients accumulate in place, and cotangents ride
``lax.ppermute`` one rank backward per tick.

Tick algebra (validated exhaustively in a schedule simulator up to PP=8,
M=33 before this was written — collisions, dependencies, and the mod-PP
stash reuse are all proven):

    F_m^r = r + m              (fill: m < PP-1-r)
    F_m^r = 2m + r             (steady: m >= PP-1-r)
    B_m^r = 2PP - 1 - r + 2m
    total ticks = 2(M + PP - 1); at most one op per (tick, rank);
    activations stash at slot m %% PP; cotangents always arrive exactly
    on their consuming tick.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _schedule(t, r, pp: int, num_micro: int):
    """Decode rank ``r``'s op at tick ``t``: (has_f, m_f, has_b, m_b)."""
    tr = t - r
    fill = (tr >= 0) & (t < pp - 1) & (tr < num_micro)
    m_steady = tr // 2
    steady = (
        (tr >= 0) & (tr % 2 == 0)
        & (m_steady >= pp - 1 - r) & (m_steady < num_micro)
    )
    has_f = fill | steady
    m_f = jnp.where(fill, tr, m_steady)
    tb = t - (2 * pp - 1 - r)
    has_b = (tb >= 0) & (tb % 2 == 0) & (tb // 2 < num_micro)
    m_b = tb // 2
    return has_f, jnp.clip(m_f, 0, num_micro - 1), has_b, jnp.clip(
        m_b, 0, num_micro - 1
    )


def _1f1b_shard(
    body_fn,
    first_fn,
    last_loss_fn,
    num_micro: int,
    axis: str,
    batch_axis,  # optional dp axis: grads/loss psum over it here
    batch_scale,  # 1 / (global example count) — the loss-mean seed
    body_params,
    first_params,
    last_params,
    feeds,
    aux,
):
    pp = jax.lax.psum(1, axis)
    rank = jax.lax.axis_index(axis)
    body_params = jax.tree.map(lambda p: jnp.squeeze(p, axis=0), body_params)
    # non-cyclic: the wraparound edges would ship a full activation-sized
    # tensor every tick to ranks that discard it (missing pairs read as
    # zeros, which both receive paths treat correctly)
    fwd_perm = [(i, i + 1) for i in range(pp - 1)]
    bwd_perm = [(i + 1, i) for i in range(pp - 1)]

    feed_sd = jax.ShapeDtypeStruct(feeds.shape[1:], feeds.dtype)
    act_sd = jax.eval_shape(first_fn, first_params, feed_sd)
    mb = feeds.shape[1]

    def composite(body_p, first_p, last_p, act_in, feed, aux_m):
        """One rank's full stage: edge-in -> body -> edge-out. rank is
        closed over; lax.cond keeps the edges on their owning ranks."""
        x = jax.lax.cond(
            rank == 0,
            lambda: first_fn(first_p, feed),
            lambda: act_in,
        )
        y = body_fn(body_p, x)
        per_ex = jax.lax.cond(
            rank == pp - 1,
            lambda: last_loss_fn(last_p, y, aux_m),
            lambda: jnp.zeros((mb,), jnp.float32),
        )
        return y, per_ex

    zero_act = jnp.zeros(act_sd.shape, act_sd.dtype)
    zeros_body = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), body_params)
    zeros_first = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), first_params)
    zeros_last = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), last_params)

    carry = dict(
        in_stash=jnp.zeros((pp,) + act_sd.shape, act_sd.dtype),
        res_stash=jnp.zeros((pp,) + act_sd.shape, act_sd.dtype),
        recv_act=zero_act,
        recv_cot=jnp.zeros(act_sd.shape, act_sd.dtype),
        d_body=zeros_body,
        d_first=zeros_first,
        d_last=zeros_last,
        loss_sum=jnp.zeros((), jnp.float32),
    )

    def tick(t, c):
        # 1. bank an activation that arrived this tick (sender = rank-1's
        #    F at t-1); receives happen before this tick's own op
        s_has_f, s_m, _, _ = _schedule(t - 1, rank - 1, pp, num_micro)
        arrived = s_has_f & (rank > 0)
        slot = s_m % pp
        in_stash = jax.lax.cond(
            arrived,
            lambda: jax.lax.dynamic_update_index_in_dim(
                c["in_stash"], c["recv_act"], slot, axis=0
            ),
            lambda: c["in_stash"],
        )

        has_f, m_f, has_b, m_b = _schedule(t, rank, pp, num_micro)

        # 2. forward op
        def do_f():
            feed = jax.lax.dynamic_index_in_dim(feeds, m_f, keepdims=False)
            aux_m = jax.lax.dynamic_index_in_dim(aux, m_f, keepdims=False)
            act_in = jax.lax.dynamic_index_in_dim(
                in_stash, m_f % pp, keepdims=False
            )
            y, per_ex = composite(
                body_params, first_params, last_params, act_in, feed, aux_m
            )
            res = jax.lax.dynamic_update_index_in_dim(
                c["res_stash"], act_in, m_f % pp, axis=0
            )
            return y, res, jnp.sum(per_ex) * batch_scale

        def no_f():
            return zero_act, c["res_stash"], jnp.zeros((), jnp.float32)

        send_act, res_stash, loss_add = jax.lax.cond(has_f, do_f, no_f)

        # 3. backward op (recompute-vjp over the composite stage)
        def do_b():
            feed = jax.lax.dynamic_index_in_dim(feeds, m_b, keepdims=False)
            aux_m = jax.lax.dynamic_index_in_dim(aux, m_b, keepdims=False)
            act_in = jax.lax.dynamic_index_in_dim(
                res_stash, m_b % pp, keepdims=False
            )
            _, vjp_fn = jax.vjp(
                lambda bp, fp, lp, a: composite(bp, fp, lp, a, feed, aux_m),
                body_params, first_params, last_params, act_in,
            )
            cot_y = jnp.where(
                rank == pp - 1, jnp.zeros_like(c["recv_cot"]), c["recv_cot"]
            )
            seed = jnp.where(
                rank == pp - 1,
                jnp.full((mb,), batch_scale, jnp.float32),
                jnp.zeros((mb,), jnp.float32),
            )
            db, df, dl, dact = vjp_fn((cot_y, seed))
            return db, df, dl, dact.astype(act_sd.dtype)

        def no_b():
            return (
                zeros_body, zeros_first, zeros_last,
                jnp.zeros(act_sd.shape, act_sd.dtype),
            )

        db, df, dl, send_cot = jax.lax.cond(has_b, do_b, no_b)
        add = lambda acc, g: jax.tree.map(
            lambda a, b: a + b.astype(a.dtype), acc, g
        )
        return dict(
            in_stash=in_stash,
            res_stash=res_stash,
            recv_act=jax.lax.ppermute(send_act, axis, fwd_perm),
            recv_cot=jax.lax.ppermute(send_cot, axis, bwd_perm),
            d_body=add(c["d_body"], db),
            d_first=add(c["d_first"], df),
            d_last=add(c["d_last"], dl),
            loss_sum=c["loss_sum"] + loss_add,
        )

    ticks = 2 * (num_micro + pp - 1)
    c = jax.lax.fori_loop(0, ticks, tick, carry)

    # reductions: pp makes edge grads/loss whole (they live on one rank);
    # dp sums the per-shard contributions (each already scaled by the
    # GLOBAL example count, so sum = mean over the full batch)
    axes_all = (axis,) + ((batch_axis,) if batch_axis else ())
    loss = jax.lax.psum(c["loss_sum"], axes_all)
    d_first = jax.tree.map(lambda g: jax.lax.psum(g, axes_all), c["d_first"])
    d_last = jax.tree.map(lambda g: jax.lax.psum(g, axes_all), c["d_last"])
    d_body = c["d_body"]
    if batch_axis:
        d_body = jax.tree.map(
            lambda g: jax.lax.psum(g, batch_axis), d_body
        )
    d_body = jax.tree.map(lambda g: g[None], d_body)  # re-add pp axis
    return loss, d_body, d_first, d_last


def pipeline_1f1b_loss_and_grads(
    body_fn: Callable,
    stacked_params,
    x: jax.Array,
    mesh: Mesh,
    num_microbatches: int,
    first_fn: Callable,
    first_params: Any,
    last_loss_fn: Callable,
    last_params: Any,
    last_aux: jax.Array,
    axis: str = "pp",
    batch_axis: Optional[str] = None,
):
    """Run the 1F1B schedule; returns ``(loss, (d_body, d_first, d_last))``.

    Same stage contract as :func:`edl_tpu.parallel.pipeline.pipeline_apply`
    with ``first_fn``/``last_fn`` mandatory and ``last_loss_fn(last_p, y,
    aux) -> [mb]`` per-example losses (the loss IS computed in-pipeline;
    this function is the gradient computation, not differentiable again).
    Requires ``num_microbatches >= PP``.
    """
    if axis not in mesh.shape:
        raise ValueError(
            "mesh has no %r axis (axes: %r)" % (axis, mesh.axis_names)
        )
    if batch_axis is not None and batch_axis not in mesh.shape:
        raise ValueError(
            "mesh has no %r axis (axes: %r)" % (batch_axis, mesh.axis_names)
        )
    pp = mesh.shape[axis]
    if num_microbatches < pp:
        raise ValueError(
            "1F1B needs num_microbatches >= pp (%d < %d)"
            % (num_microbatches, pp)
        )
    batch = x.shape[0]
    if batch % num_microbatches:
        raise ValueError(
            "batch %d not divisible into %d microbatches"
            % (batch, num_microbatches)
        )
    if last_aux.shape[0] != batch:
        raise ValueError(
            "last_aux batch %d != x batch %d" % (last_aux.shape[0], batch)
        )
    mb = batch // num_microbatches
    if batch_axis is not None and mb % mesh.shape[batch_axis]:
        raise ValueError(
            "microbatch %d not divisible by %r" % (mb, batch_axis)
        )
    micro = x.reshape((num_microbatches, mb) + x.shape[1:])
    aux = last_aux.reshape((num_microbatches, mb) + last_aux.shape[1:])

    # mean over EVERY example globally (dp shards included: each shard's
    # per-example sums are scaled by the GLOBAL count, then psum'ed)
    batch_scale = 1.0 / (num_microbatches * mb)

    param_specs = jax.tree.map(
        lambda p: P(axis, *([None] * (p.ndim - 1))), stacked_params
    )
    rep = lambda tree: jax.tree.map(lambda p: P(), tree)
    data_spec = P(None, batch_axis)

    fn = partial(
        _1f1b_shard, body_fn, first_fn, last_loss_fn, num_microbatches,
        axis, batch_axis, batch_scale,
    )
    loss, d_body, d_first, d_last = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            param_specs, rep(first_params), rep(last_params),
            data_spec, data_spec,
        ),
        out_specs=(P(), param_specs, rep(first_params), rep(last_params)),
        check_vma=False,
    )(stacked_params, first_params, last_params, micro, aux)
    return loss, (d_body, d_first, d_last)
