"""Train-step builders: jitted SPMD steps over a mesh.

The numeric heart the reference leaves to Paddle fleet
(``fleet.distributed_optimizer`` wrapping Momentum + NCCL allreduce,
reference train_with_fleet.py:326, 367-377) — here a single jitted function:
parameters live replicated (or fsdp-sharded) on the mesh, batches arrive
dp-sharded, and the gradient all-reduce is inserted by XLA from the
sharding algebra. bf16 compute happens inside the model (see models/);
parameters, BN statistics and optimizer state stay fp32 — the TPU-native
equivalent of the reference's AMP + loss-scaling flags
(train_with_fleet.py:68-73), no loss scaling needed for bf16.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import core, struct

from edl_tpu.obs import numerics as obs_numerics
from edl_tpu.obs import trace as obs_trace
from edl_tpu.ops.cross_entropy import rows_cross_entropy


class TrainState(struct.PyTreeNode):
    """Model + optimizer state (flax-style). ``batch_stats`` is the model's
    collection of that name: what a step computes from its own batch and
    keeps without a gradient — BatchNorm's running moments, an expert
    router's balancing bias (``models/moe.py``). The step hands it to the
    model as mutable and stores what comes back; it is saved, restored and
    replicated with the rest."""

    step: jnp.ndarray
    apply_fn: Callable = struct.field(pytree_node=False)
    params: core.FrozenDict
    tx: optax.GradientTransformation = struct.field(pytree_node=False)
    opt_state: optax.OptState
    batch_stats: Optional[core.FrozenDict] = None
    # step metrics that come from what the model sows at every call
    # (``sown_metric_names``), as ``model.init`` showed them. Static: the
    # values are a step's by-products, never state, so no leaf here or in
    # a checkpoint holds them
    sown: Tuple[str, ...] = struct.field(pytree_node=False, default=())

    def apply_gradients(self, grads, **updates) -> "TrainState":
        param_updates, new_opt_state = self.tx.update(
            grads, self.opt_state, self.params
        )
        new_params = optax.apply_updates(self.params, param_updates)
        return self.replace(
            step=self.step + 1,
            params=new_params,
            opt_state=new_opt_state,
            **updates,
        )


AUX_LOSS = "aux_loss"

#: Which matrices' weight gradients leave the optimizer's fusion
#: (``grads_apart``): both dimensions at least MIN_WIDTH, fewer than
#: MAX_ELEMENTS elements. Set from every operation of the six LM cells listed
#: once fused and once apart (PERF.md section 6, PR 38). What decides is how
#: far under its matmul's rate XLA's fused form runs (apart is the matmul at
#: 85-92% of peak plus a pass of half its time, so it wins under 60%), and
#: that is not a function of a leaf's size: at hidden 2048 every matrix
#: measured (``[2048, 6144]`` to ``[2048, 25024]``) runs fused at 64-78% and
#: loses 0.1-0.5 ms apart, at 3840 and 4096 the fused form reads 31-56% from
#: ``[2880, 3840]`` (1.94 ms fused, 1.06 + 0.46 apart) to ``[4096, 14336]``
#: (11.2-14.1 behind a plain first half, 5.1-5.9 + 3.1 apart), and the
#: vocabulary's matrices from 103 M elements up run fused at 75-92%, so there
#: the float32 round trip only costs (4.0-4.2 ms each at 131 M).
GRAD_APART_MIN_WIDTH = 2560
GRAD_APART_MAX_ELEMENTS = 76 << 20


def taken_apart(leaf) -> bool:
    """Whether ``grads_apart`` takes this gradient leaf: a float matrix
    (rank 2: an expert bank's gradient leaves a custom call and is apart
    already) of at least ``GRAD_APART_MIN_WIDTH`` rows and columns and fewer
    than ``GRAD_APART_MAX_ELEMENTS`` elements. A rule on the leaf's shape
    and dtype alone."""
    return (
        len(leaf.shape) == 2
        and jnp.issubdtype(leaf.dtype, jnp.floating)
        and min(leaf.shape) >= GRAD_APART_MIN_WIDTH
        and leaf.size < GRAD_APART_MAX_ELEMENTS
    )


def grads_apart(grads):
    """``grads`` with every leaf the rule takes (``taken_apart``) behind an
    ``optimization_barrier`` of its own: the identity, and a fence. Left to
    itself XLA fuses the update, the half-batch mean and the numerics
    bundle's norms into the matmul that produces a weight gradient, and
    where that fused form tiles badly the matmul runs at a third of peak.
    Behind the barrier dW is written once by a plain matmul and everything
    that reads it — both of its readers must read THIS tree — is one
    elementwise-and-reduce pass over ``g, p, m, v`` at the HBM's rate. One
    barrier a leaf, never one over the tree: that would hold every
    gradient live at once."""
    leaves = jax.tree_util.tree_leaves(grads)
    sizes = [4 * leaf.size for leaf in leaves if taken_apart(leaf)]
    # once a tree a step is traced over, and stage: how many of its leaves
    # the rule took, and their float32 bytes
    obs_trace.get_tracer().note_once(
        "grad_apart", leaves=len(sizes), of=len(leaves), bytes=sum(sizes),
        largest_bytes=max(sizes, default=0), min_width=GRAD_APART_MIN_WIDTH,
        max_elements=GRAD_APART_MAX_ELEMENTS,
    )
    return jax.tree_util.tree_map(
        lambda g: jax.lax.optimization_barrier(g) if taken_apart(g) else g, grads
    )


def sown_metric_names(variables) -> Tuple[str, ...]:
    """The step metrics a model's sown collections give, from
    ``model.init``'s result: ``"aux_loss"`` if it sows into ``"losses"``
    (every leaf there is added to the objective, their sum reported), and
    the name of every leaf it sows into ``"metrics"`` (reported as the
    mean over the modules that sowed that name)."""
    names = [AUX_LOSS] if "losses" in variables else []
    return tuple(names + list(_sown_metrics(variables.get("metrics", {}))))


def create_state(
    model,
    rng: jax.Array,
    sample_input,
    tx: optax.GradientTransformation,
    shardings: Any = None,
    **init_kwargs,
) -> TrainState:
    """Build the train state as the output of ONE jitted program.

    ``model.init``, ``tx.init`` and the step counter are traced once and
    compiled with ``shardings`` as the program's output shardings, so
    every leaf is born where it lives: no op-by-op init, no forward pass
    on device 0 (it is dead code under jit), no copy of the state from
    one device to the mesh afterwards. ``shardings`` is a pytree prefix
    of the ``TrainState`` (one ``Sharding`` for every leaf, or one per
    leaf), or a function from the abstract state (``jax.eval_shape``'s
    tree) to such a prefix; ``None`` leaves placement to jax.

    ``sample_input`` gives shapes and dtypes only: the trace sees zeros
    of them, so no sample bytes are baked into the program or reach the
    device. ``rng`` is an argument of the program, not a constant in it:
    one compile serves every seed. The values do not depend on
    ``shardings`` (jax's threefry is partitionable).
    """

    def init_state(rng):
        zeros = jax.tree.map(
            lambda x: jnp.zeros(jnp.shape(x), jnp.result_type(x)), sample_input
        )
        variables = model.init(rng, zeros, **init_kwargs)
        params = variables["params"]
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            apply_fn=model.apply,
            params=params,
            tx=tx,
            opt_state=tx.init(params),
            batch_stats=variables.get("batch_stats"),
            sown=sown_metric_names(variables),
        )

    if callable(shardings):
        shardings = shardings(jax.eval_shape(init_state, rng))
    return jax.jit(init_state, out_shardings=shardings)(rng)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array) -> Tuple[jax.Array, Dict]:
    ce, best = rows_cross_entropy(logits, labels, site="cross_entropy_loss")
    return ce.mean(), {"accuracy": (best == labels).mean()}


def make_cross_entropy_loss(report_top_k: Optional[int] = None):
    """CE loss head with opt-in top-k accuracy reporting.

    ``report_top_k=5`` adds the acc5 the reference reports in every
    benchmark table (README.md:68-72, 144-147). Opt-in, NOT part of
    ``cross_entropy_loss``: LM heads route vocab-sized logits through the
    shared CE head every step, and a per-token top-k over the vocab is
    pure hot-path cost for a metric nothing reads there. Skipped when the
    class count is <= k (top-k of k classes is identically 1.0).
    """

    def head(logits: jax.Array, labels: jax.Array) -> Tuple[jax.Array, Dict]:
        loss, metrics = cross_entropy_loss(logits, labels)
        if report_top_k and logits.shape[-1] > report_top_k:
            _, idx = jax.lax.top_k(logits, report_top_k)
            metrics = {
                **metrics,
                "top%d" % report_top_k: jnp.any(
                    idx == labels[..., None], axis=-1
                ).mean(),
            }
        return loss, metrics

    return head


def mse_loss(preds: jax.Array, targets: jax.Array) -> Tuple[jax.Array, Dict]:
    return jnp.mean((preds - targets) ** 2), {}


def make_kd_loss(alpha: float = 0.5, temperature: float = 1.0):
    """Knowledge-distillation loss head for ``make_train_step``.

    The batch target is ``(labels, teacher_logits)`` — the shape the
    distill pipeline yields (original fields + teacher predictions
    appended, reference distill_reader.py:351) and what the co-located
    fused step produces. Objective: ``(1-alpha)*CE(labels) +
    alpha*T^2*KL(teacher_T || student_T)`` (Hinton et al. 2015); the
    ``T^2`` keeps soft-target gradient magnitude independent of T.
    """

    def kd_loss(logits: jax.Array, y) -> Tuple[jax.Array, Dict]:
        labels, teacher_logits = y
        t = jax.nn.log_softmax(teacher_logits.astype(jnp.float32) / temperature)
        s = jax.nn.log_softmax(logits / temperature)
        kl = jnp.sum(jnp.exp(t) * (t - s), axis=-1).mean()
        hard = optax.softmax_cross_entropy(
            logits, jax.nn.one_hot(labels, logits.shape[-1])
        ).mean()
        loss = (1.0 - alpha) * hard + alpha * (temperature**2) * kl
        accuracy = (jnp.argmax(logits, -1) == labels).mean()
        return loss, {"accuracy": accuracy, "kd_kl": kl, "hard_ce": hard}

    return kd_loss


def make_block_diffusion_loss():
    """The block-diffusion objective as a loss head for ``make_train_step``
    (BD3-LM, arXiv:2503.09573, equation 8 under the linear schedule; the
    model is ``TransformerLM`` under ``ArchSpec.block_diffusion``, whose
    logits ``[B, L, vocab]`` are the noised half's).

    The batch target is the pair ``(labels [B, L] int32, weights [B, L]
    float32)`` that ``data/block_diffusion.py:noised`` yields: the clean ids,
    and ``1 / t`` of a position's block where the position was masked, 0 where
    it was not. Objective: ``sum(weights * CE(logits, labels)) / (B L)``, a
    masked position predicting its own token (no shift). Metrics:
    ``bd_masked_share`` (positions with a weight above 0 over all),
    ``bd_masked_ce`` (the unweighted mean cross-entropy over them) and
    ``accuracy`` over them. The first two are the head's ``gauges``: the train
    loop publishes them as ``edl_train_<name>`` beside what the model sows."""

    def bd_loss(logits: jax.Array, y) -> Tuple[jax.Array, Dict]:
        labels, weights = y
        ce, best = rows_cross_entropy(logits, labels, site="block_diffusion_loss")
        weights = weights.astype(jnp.float32)
        scored = weights > 0
        count = jnp.maximum(jnp.sum(scored), 1)
        right = best == labels
        return jnp.sum(weights * ce) / ce.size, {
            "accuracy": jnp.sum(scored & right) / count,
            "bd_masked_share": jnp.mean(scored),
            "bd_masked_ce": jnp.sum(jnp.where(scored, ce, 0.0)) / count,
        }

    bd_loss.gauges = ("bd_masked_share", "bd_masked_ce")
    return bd_loss


def make_train_step(
    loss_head: Callable[[jax.Array, jax.Array], Tuple[jax.Array, Dict]],
    apply_kwargs: Optional[Dict[str, Any]] = None,
    donate: bool = True,
    aux_losses: Optional[bool] = None,
    numerics: bool = False,
):
    """Build ``step(state, (x, y)) -> (state, metrics)``.

    ``apply_kwargs`` are forwarded to the model (e.g. ``{"train": True}``
    for models with batch norm / dropout).

    A model that ``sow``s into ``"losses"`` (e.g. an expert layer's
    load-balancing and router-z terms) has every such leaf added to the
    objective, their sum reported as ``metrics["aux_loss"]``; what it
    sows into ``"metrics"`` is reported under its own name. The step
    learns this from ``state.sown``, which ``create_state`` fills from
    ``model.init``'s result: no caller sets a flag, and a model that sows
    nothing traces the step it always traced. ``aux_losses`` is an
    override for a hand-built state: ``True`` collects ``"losses"``
    whatever ``state.sown`` says, ``False`` collects nothing. Under the
    numerics plane the sown metrics also ride the bundle (``"sown"``), so
    the probe's throttled fetch publishes them as gauges.

    ``numerics=True`` fuses the numerics-plane bundle (obs/numerics)
    into the step: metrics gains a reserved ``METRICS_KEY`` entry of
    on-device scalars the caller must pop and hand to
    ``NumericsProbe.on_step`` (never aggregate it). When the batch is
    statically splittable — every leaf batched with the same even
    leading dim, no batch_stats, nothing sown — and
    ``EDL_NUMERICS_GNS`` is not ``0``, the gradient is computed as the
    mean of two half-batch gradients instead of one full-batch pass:
    identical to the full-batch gradient for mean-reduced loss heads
    over equal halves, same FLOP count, one jit — and the two half
    norms feed the gradient-noise-scale estimator for free. A model with
    sown losses is never split: a load-balancing term is a product of two
    batch means (assignments routed x mean router probability), so the
    mean of two half-batch gradients is NOT the full-batch gradient and
    the split's contract does not hold.

    The step program names its phases (``jax.named_scope``: metadata
    only, the HLO and its fusions are what they were): ``forward`` is
    the model and the loss head, so that jax writes ``jvp(forward)`` and
    ``transpose(jvp(forward))`` — forward and backward, recomputation
    under the latter — into every operation's ``op_name``; ``grad_mean``
    the half-batch averaging; ``optimizer`` the update; ``numerics`` the
    bundle. ``obs/profile.py:step_phases`` reads them back from the
    compiled step; ``train/aot.py:STEP_SCOPES_KEY`` lists them for the
    compile cache's key (bump it when a scope moves).
    """
    kwargs = dict(apply_kwargs or {})
    # env read at BUILD time, outside the traced step (jit purity): the
    # GNS knob shapes the trace like donate/aux_losses do
    want_gns = numerics and os.environ.get("EDL_NUMERICS_GNS", "1") != "0"

    def step(state: TrainState, batch):
        x, y = batch
        if aux_losses is None:
            sown = tuple(state.sown)
        else:
            sown = (AUX_LOSS,) if aux_losses else ()
        collections = ["losses"] if AUX_LOSS in sown else []
        if any(name != AUX_LOSS for name in sown):
            collections.append("metrics")

        @jax.named_scope("forward")
        def loss_fn(params, bx, by):
            variables = {"params": params}
            mutable = []
            if state.batch_stats is not None:
                variables["batch_stats"] = state.batch_stats
                mutable.append("batch_stats")
            mutable.extend(collections)
            if mutable:
                outputs, mutated = state.apply_fn(
                    variables, bx, mutable=mutable, **kwargs
                )
                new_stats = mutated.get("batch_stats")
            else:
                outputs = state.apply_fn(variables, bx, **kwargs)
                mutated, new_stats = {}, None
            loss, metrics = loss_head(outputs, by)
            if "metrics" in collections:
                metrics = {**metrics, **_sown_metrics(mutated.get("metrics", {}))}
            if "losses" in collections:
                # always emit the metric so callers see a stable structure
                aux = sum(
                    (
                        jnp.sum(jnp.asarray(leaf))
                        for leaf in jax.tree.leaves(mutated.get("losses", {}))
                    ),
                    start=jnp.zeros((), jnp.float32),
                )
                loss = loss + aux
                metrics = {**metrics, AUX_LOSS: aux}
            return loss, (metrics, new_stats)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        # the half-batch split is decided STATICALLY at trace time from
        # concrete leaf shapes: no runtime branch reaches the schedule
        batch_size = None
        if want_gns and state.batch_stats is None and not sown:
            leaves = jax.tree_util.tree_leaves(batch)
            dims = set()
            splittable = bool(leaves)
            for leaf in leaves:
                if hasattr(leaf, "shape") and getattr(leaf, "ndim", 0) >= 1:
                    dims.add(leaf.shape[0])
                else:
                    splittable = False  # an unbatched leaf cannot be halved
            if splittable and len(dims) == 1:
                b = dims.pop()
                if b >= 2 and b % 2 == 0:
                    batch_size = b
        halves = None
        if batch_size is not None:
            h = batch_size // 2
            x1, y1 = jax.tree_util.tree_map(lambda a: a[:h], (x, y))
            x2, y2 = jax.tree_util.tree_map(lambda a: a[h:], (x, y))
            (l1, (m1, _)), g1 = grad_fn(state.params, x1, y1)
            (l2, (m2, _)), g2 = grad_fn(state.params, x2, y2)
            with jax.named_scope("grad_mean"):
                # each half's, not the mean's: ``half_sq`` reads g2 alone
                g1, g2 = grads_apart(g1), grads_apart(g2)
                loss = (l1 + l2) / 2.0
                grads = jax.tree_util.tree_map(
                    lambda a, c: (a + c) / 2.0, g1, g2
                )
                metrics = jax.tree_util.tree_map(
                    lambda a, c: (a + c) / 2.0, m1, m2
                )
            new_stats = None
            halves = (g1, g2)
        else:
            (loss, (metrics, new_stats)), grads = grad_fn(state.params, x, y)
        updates = {}
        if new_stats is not None:
            updates["batch_stats"] = new_stats
        with jax.named_scope("optimizer"):
            if halves is None:
                grads = grads_apart(grads)
            new_state = state.apply_gradients(grads, **updates)
        metrics = {"loss": loss, **metrics}
        if numerics:
            with jax.named_scope("numerics"):
                metrics[obs_numerics.METRICS_KEY] = obs_numerics.device_bundle(
                    loss, grads, state.params, new_state.params,
                    halves=halves, batch=batch_size,
                )
                if sown:
                    metrics[obs_numerics.METRICS_KEY]["sown"] = {
                        name: metrics[name] for name in sown
                    }
        return new_state, metrics

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def _sown_metrics(collection) -> Dict[str, jax.Array]:
    """``{name: mean over the modules that sowed it}`` from a ``"metrics"``
    collection (``{module path...: {name: (value,)}}``)."""
    by_name: Dict[str, list] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(collection):
        names = [k.key for k in path if hasattr(k, "key")]
        by_name.setdefault(names[-1], []).append(jnp.asarray(leaf, jnp.float32))
    return {name: jnp.mean(jnp.stack(v)) for name, v in by_name.items()}


def _masked_reduce(loss_head, outputs, y, mask, context: str):
    """Shared ragged-batch reduction for the masked train/eval steps.

    vmaps ``loss_head`` per row (enforcing the per-example-mean contract
    at trace time), then reduces loss and metrics over valid rows only.
    Returns ``(loss, metrics, n_valid)`` with ``n_valid`` the GLOBAL
    valid-row count — under SPMD the sums span every process's rows, so
    the quotient is the true global mean."""
    losses, metrics = jax.vmap(loss_head)(outputs, y)
    b = mask.shape[0]
    for name, v in [("loss", losses), *metrics.items()]:
        if v.shape != (b,):
            raise ValueError(
                "masked %s requires per-example loss heads: %r has "
                "shape %s under vmap, expected (%d,)"
                % (context, name, v.shape, b)
            )
    w = mask.astype(jnp.float32)
    n_valid = jnp.sum(w)
    denom = jnp.maximum(n_valid, 1.0)
    loss = jnp.sum(losses.astype(jnp.float32) * w) / denom
    out_metrics = {
        name: jnp.sum(v.astype(jnp.float32) * w) / denom
        for name, v in metrics.items()
    }
    return loss, out_metrics, n_valid


def make_masked_train_step(
    loss_head: Callable[[jax.Array, jax.Array], Tuple[jax.Array, Dict]],
    apply_kwargs: Optional[Dict[str, Any]] = None,
    donate: bool = True,
):
    """Sync-SGD step over a PADDED global batch: ``step(state, (x, y),
    mask) -> (state, metrics, n_valid)``.

    The ragged-tail TRAIN twin of :func:`make_masked_eval_step`, built
    for elastic data-layer feeds where workers pull *uneven* record
    shares (``data/dispatcher.py`` task stealing): every process steps
    at the same static shape — one compilation, one collective schedule
    — and contributes only its valid rows. The loss is the sum of
    per-example losses over valid rows divided by the GLOBAL valid
    count, so the gradient equals plain sync-SGD over exactly the valid
    rows; a worker whose share ran dry participates with an all-pad
    (zero-weight) batch instead of hanging the collective. Requires
    per-example-mean loss heads (same contract as the masked eval step,
    enforced at trace time).
    """
    kwargs = dict(apply_kwargs or {})

    def step(state: TrainState, batch, mask):
        x, y = batch

        @jax.named_scope("forward")
        def loss_fn(params):
            variables = {"params": params}
            if state.batch_stats is not None:
                raise ValueError(
                    "masked train step does not support batch_stats "
                    "models: pad rows would pollute the running BN "
                    "statistics"
                )
            outputs = state.apply_fn(variables, x, **kwargs)
            loss, out_metrics, n_valid = _masked_reduce(
                loss_head, outputs, y, mask, "train"
            )
            return loss, (out_metrics, n_valid)

        (loss, (metrics, n_valid)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        with jax.named_scope("optimizer"):
            new_state = state.apply_gradients(grads)
        return new_state, {"loss": loss, **metrics}, n_valid

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_eval_step(
    loss_head: Callable[[jax.Array, jax.Array], Tuple[jax.Array, Dict]],
    apply_kwargs: Optional[Dict[str, Any]] = None,
):
    kwargs = dict(apply_kwargs or {})

    def step(state: TrainState, batch):
        x, y = batch
        variables = {"params": state.params}
        if state.batch_stats is not None:
            variables["batch_stats"] = state.batch_stats
        outputs = state.apply_fn(variables, x, **kwargs)
        loss, metrics = loss_head(outputs, y)
        return {"loss": loss, **metrics}

    # edl: donate-ok(eval re-reads the same TrainState every batch)
    return jax.jit(step)


def make_masked_eval_step(
    loss_head: Callable[[jax.Array, jax.Array], Tuple[jax.Array, Dict]],
    apply_kwargs: Optional[Dict[str, Any]] = None,
):
    """Eval step for a PADDED batch: ``step(state, batch, mask)``.

    Runs at the same static batch shape as every full batch — the ragged
    tail never changes shapes, so multi-process stages with sharded
    params see one uniform compilation and one uniform collective
    schedule. Pad rows are excluded by computing the loss head per row
    (``vmap``) and reducing under ``mask``; works for any head whose
    loss/metrics are per-example means (CE, top-k, KD, MSE). Returns
    ``(metrics, n_valid)`` with ``n_valid`` the GLOBAL valid-row count —
    the right weight for accumulating across batches.
    """
    kwargs = dict(apply_kwargs or {})

    def step(state: TrainState, batch, mask):
        x, y = batch
        variables = {"params": state.params}
        if state.batch_stats is not None:
            variables["batch_stats"] = state.batch_stats
        outputs = state.apply_fn(variables, x, **kwargs)
        # trace-time guard inside _masked_reduce: a head with batch-level
        # semantics (global top-k, batch-normalized reduction) yields
        # non-[batch] shapes under vmap and would silently disagree with
        # make_eval_step on the ragged tail
        loss, out_metrics, n_valid = _masked_reduce(
            loss_head, outputs, y, mask, "eval"
        )
        return {"loss": loss, **out_metrics}, n_valid

    # edl: donate-ok(eval re-reads the same TrainState every batch)
    return jax.jit(step)
