"""Mixture-of-Experts layers.

Net-new versus the reference (no MoE/expert parallelism anywhere in its
tree — SURVEY §2 parallelism inventory). Two layers live here:

- :class:`DroplessMoE` — the expert layer of today's open MoE models:
  a float32 router with softmax scores (OLMoE) or sigmoid scores, a
  balancing bias, normalised and scaled weights and a shared expert
  (the DeepSeek-V3 line as Trinity-Mini's ``afmoe`` code writes it),
  top-k of many small experts, gated with three matrices each (SiLU, or
  SmallThinker's ReLU) or ungated with two and a squared ReLU (Nemotron-H's),
  routed from what they read or from the block's own input, reading and writing
  the model's width or a narrower latent between two shared projections
  (LatentMoE), **no capacity and no dropped
  token**, and optionally **one chip's share of the experts** (``held``:
  it routes over all of them and computes what its own give). Tokens are
  sorted by expert and an expert's projections run as grouped matrix
  multiplications over the ragged groups
  (``edl_tpu.ops.grouped_matmul``); shapes are static whatever the
  imbalance. Sows the load-balancing and router-z losses into
  ``"losses"`` (where it has any) and the loads into ``"metrics"``;
  ``create_state`` sees the collections in ``model.init``'s result, so
  the train step adds and reports them with no flag from the caller.
  :class:`MoESpec` describes it to ``TransformerLM``.
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
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from edl_tpu.obs import trace as obs_trace
from edl_tpu.ops.grouped_matmul import (
    SEGMENT_TILE, grouped_matmul, rows_summed_by_segment,
)

# The ``checkpoint_name``s of what a remat policy may keep of a
# :class:`DroplessMoE` (its docstring says which tensors, and their bytes);
# ``models/transformer.py:_remat_policy`` saves both
ROUTE_NAME = "moe_route"
HELD_NAME = "moe_held"
REMAT_NAMES = (ROUTE_NAME, HELD_NAME)

# an expert's activation by name; "relu2" is the squared ReLU of Nemotron-H,
# "relu" the gate of SmallThinker's experts (a gated expert's is one of GATES)
ACTIVATIONS = {
    "silu": nn.silu, "relu": nn.relu, "relu2": lambda a: jnp.square(nn.relu(a)),
}
GATES = ("silu", "relu")
# what the router reads: the feed-forward's own normed input, or the block's
# input as the block received it, before its first norm and its mixer
ROUTE_FROM = ("ff_input", "block_input")


@dataclasses.dataclass(frozen=True)
class MoESpec:
    """The expert layer of a ``TransformerLM``, as one hashable field: the
    feed-forward of every block past the leading
    ``models/transformer.py:ArchSpec.dense_layers`` is a
    :class:`DroplessMoE` of this shape, whose fields these are. (Which
    blocks mix by attention, over which window, and which by a state-space
    layer is ``ArchSpec.layer_types``, and there a block whose one branch
    is this layer.) The defaults are OLMoE's layer: softmax scores, every
    expert held here, no bias, no shared expert, SiLU-gated experts of three
    matrices at the model's width, routed from what they read."""

    num_experts: int
    top_k: int
    d_ff: int                      # width of ONE expert
    norm_topk_prob: bool = False   # renormalise the k weights to sum 1
    norm_topk_eps: float = 1e-20   # ... over (their sum + this)
    aux_weight: float = 1e-2       # alpha of the load-balancing loss; 0: none
    z_weight: float = 1e-3         # beta of the router z-loss; 0: none
    score_func: str = "softmax"    # or "sigmoid": each expert on its own
    route_scale: float = 1.0       # the k weights times this, at the end
    bias_rate: float = 0.0         # > 0: a balancing bias under the choice
    shared_d_ff: int = 0           # > 0: a shared expert of this width
    held: Optional[Tuple[int, int]] = None  # (first, count) held here; None: all
    n_group: int = 1               # > 1: the experts in this many equal groups,
    topk_group: int = 1            # ... the choice inside the best topk_group
    gated: bool = True             # False: two matrices an expert, W_down act(W_up x)
    activation: str = "silu"       # a gated expert's: "silu" or "relu"; ungated also "relu2"
    latent: int = 0                # > 0: the routed experts read and write this width
    route_from: str = "ff_input"   # or "block_input": the router reads the block's input


def _sum_unsorted(rows, order, inverse, k, live=None, dtype=jnp.float32):
    """``sum_j rows[inverse[n * k + j]]``, summed in float32 and rounded once to
    ``dtype``: expert order back to (token, choice) order, where a token's
    ``k`` rows are neighbours, and their sum. With ``live`` only the pairs
    sorted before it count (the others' experts are held elsewhere and their
    rows are nobody's).

    Handed a held share's buffer (``live`` and fewer rows than pairs; ``order``
    then names the pair of each of its rows) it reads those ``m`` rows alone,
    as a sum by token (``ops/grouped_matmul.py:rows_summed_by_segment``): the
    same value, with no array of ``N k`` rows gathered, written or read."""
    n = inverse.shape[0] // k
    m = rows.shape[0]
    if live is not None and m < inverse.shape[0]:
        token = jnp.where(jnp.arange(m) < live, order // k, n)  # n: nobody's
        return rows_summed_by_segment(rows, token, n, dtype)
    if live is None:
        back = rows[inverse]
    else:
        back = jnp.where(
            (inverse < live)[:, None],
            rows[jnp.minimum(inverse, m - 1)], 0,
        )
    back = back.reshape(n, k, rows.shape[-1])
    return jnp.sum(back, axis=1, dtype=jnp.float32).astype(dtype)


def _rows_sorted(tokens, order, inverse, k, live=None):
    """``tokens[order // k]``: row ``r`` of the result is the token of the
    ``r``-th (token, choice) pair in expert order (``order`` may be that
    order's first rows only). Its gradient is :func:`_rows_combined`'s
    forward (a gather and a sum over ``k`` neighbours, or over a buffer
    its rows' sum by token), not the scatter-add jax would derive."""

    @jax.custom_vjp
    def take(tokens, order, inverse, live):
        return tokens[order // k]

    def fwd(tokens, order, inverse, live):
        return take(tokens, order, inverse, live), (order, inverse, live)

    def bwd(residuals, grad):
        order, inverse, live = residuals
        return _sum_unsorted(grad, order, inverse, k, live, grad.dtype), None, None, None

    take.defvjp(fwd, bwd)
    return take(tokens, order, inverse, live)


def _top_k_kept(scores, k):
    """``lax.top_k(scores, k)`` along the last axis, values and indices both
    under ``ROUTE_NAME``, with the values' gradient (each one's, back at its
    index) read off the NAMED indices: jax's own rule reads the primitive's
    unnamed output, so a recomputation that was handed the indices would run
    the ``top_k`` again for them."""

    @jax.custom_vjp
    def top(scores):
        return tuple(jax.lax.top_k(scores, k))

    def fwd(scores):
        values, idx = top(scores)
        idx = checkpoint_name(idx, ROUTE_NAME)
        return (checkpoint_name(values, ROUTE_NAME), idx), idx

    def bwd(idx, grads):
        return (_sent_home(grads[0], idx, scores.shape[-1]),)

    top.defvjp(fwd, bwd)
    return top(scores)


def _chosen(idx, experts):
    """``[.., k, E]`` bool: ``idx[.., j] == e``. Never an array in memory: the
    one reduce that reads it takes it into its fusion."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1,) * idx.ndim + (experts,), idx.ndim)
    return idx[..., None] == lanes


def _sent_home(grad, idx, experts):
    """``d scores[.., e] = sum_j where(idx[.., j] == e, grad[.., j], 0)``: each
    chosen score's gradient back at its index, by comparison. A ``top_k``'s
    indices are distinct a row, so a sum has at most one term and the values
    are the scatter-add's into zeros; XLA makes it one fusion that writes
    ``[.., E]`` once (a scatter on the TPU walks its indices). Device-side name
    of both directions: ``picked``, under the caller's."""
    with jax.named_scope("picked"):
        return jnp.sum(jnp.where(_chosen(idx, experts), grad[..., None], 0), axis=-2)


def _picked(scores, idx):
    """``scores[.., idx[.., j]]`` (``jnp.take_along_axis`` along the last
    axis) for a ``top_k``'s indices, read off the row by comparison:
    ``max_e where(idx[.., j] == e, scores[.., e], -inf)``, one score among
    ``-inf`` (a maximum and not a sum of one term among zeros: XLA folds a sum
    of such sums, the weights' normaliser, into one reduction over ``(j, e)``
    and adds a token's ``k`` weights in another order), so the gather's values
    to the bit whatever stands around it, and :func:`_sent_home` their
    gradient's way back. One fusion a direction that reads or writes
    ``[.., E]`` once and holds no ``[.., k, E]`` array in memory: 0.16 and 0.11
    ms a layer on the v5e at 8192 x 512 and top-22, where XLA's gather read
    1.84 and its transpose, a scatter-add into zeros, 1.56 (PERF.md section 5,
    PR 65)."""
    experts = scores.shape[-1]

    @jax.custom_vjp
    def pick(scores, idx):
        with jax.named_scope("picked"):
            return jnp.max(
                jnp.where(_chosen(idx, experts), scores[..., None, :], -jnp.inf), axis=-1
            )

    def fwd(scores, idx):
        return pick(scores, idx), idx

    def bwd(idx, grad):
        return _sent_home(grad, idx, experts), None

    pick.defvjp(fwd, bwd)
    return pick(scores, idx)


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


def _rows_combined(rows, order, inverse, k, live=None):
    """``y[n] = sum_j rows[inverse[n * k + j]]`` in float32: the transpose
    of :func:`_rows_sorted` (:func:`_sum_unsorted`: over a buffer, its rows'
    sum by token). Its gradient is that function's forward,
    ``grad[order // k]``, so it keeps ``order`` and no row."""

    @jax.custom_vjp
    def combine(rows, order, inverse, live):
        return _sum_unsorted(rows, order, inverse, k, live)

    def fwd(rows, order, inverse, live):
        return combine(rows, order, inverse, live), order

    def bwd(order, grad):
        return grad.astype(rows.dtype)[order // k], None, None, None

    combine.defvjp(fwd, bwd)
    return combine(rows, order, inverse, live)


@jax.custom_vjp
def _as_stored(x):
    """``x`` behind an ``optimization_barrier``, forward only: the array as it
    stands in memory, rounded to its dtype once. A block's input is the sum of
    two bfloat16 arrays, and XLA makes such a sum again inside each fusion that
    reads it, rounding it to bfloat16 in one and (excess precision) not in
    another: the router's matmul and the sown ``router_in`` then read numbers
    2^-9 apart (on the chip 1.3e-3 of the largest logit, a third of what a
    bfloat16 router reads; PERF.md section 6, PR 59). The block's checkpoint
    keeps this array anyway; the chip read the barrier at 0.29 ms a layer. The
    cotangent goes through as it is."""
    return jax.lax.optimization_barrier(x)


_as_stored.defvjp(lambda x: (_as_stored(x), None), lambda _, ct: (ct,))


class UngatedMLP(nn.Module):
    """``W_down act(W_up x)``: the two-matrix feed-forward, no gate and no
    bias (Nemotron-H's, with ``"relu2"``)."""

    d_ff: int
    dtype: Any = jnp.bfloat16
    activation: str = "relu2"

    @nn.compact
    def __call__(self, x):
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        hidden = ACTIVATIONS[self.activation](dense(self.d_ff, name="up")(x))
        return dense(x.shape[-1], name="down")(hidden)


class DroplessMoE(nn.Module):
    """Dropless top-k mixture of small experts, gated (SiLU or ReLU) or ungated.

    Per token ``x`` (``[B, S, D]`` in, ``[B, S, D]`` out)::

        s      = softmax(W_r x)  or  sigmoid(W_r x)     float32, over E
        e      = top_k(s + b)                      b: the bias, if any; with ``n_group > 1``
                                                   inside a token's best ``topk_group`` groups
        w      = s[e], then w / (sum(w) + norm_topk_eps) and w * route_scale, as asked
        y      = sum_j W_down[e_j] (w_j * silu(W_gate[e_j] x) * W_up[e_j] x)
                 + shared(x)                       if there is a shared expert

    With the defaults this is OLMoE's layer; with sigmoid scores, the bias,
    normalised and scaled weights and a shared expert it is the layer of
    the DeepSeek-V3 line as Trinity's ``afmoe`` code writes it.

    **The route's own operand** (``route_from = "block_input"``, and then
    ``__call__(x, route_x)``; SmallThinker, arXiv:2507.20984: a router placed
    before attention): ``W_r`` reads ``route_x`` (the residual stream as the
    block received it, un-normed) where everything above says ``W_r x``;
    logits, scores, choice, weights, sort, losses and gauges follow from it,
    and the experts (the latent, the shared expert) read ``x`` as before. The
    route's gradient reaches ``route_x`` and not ``x``. The parameter tree is
    the same. ``"ff_input"`` takes no second operand and is the layer above.

    **A ReLU gate** (``activation = "relu"`` on a gated expert):
    ``W_down[e] (w * relu(W_gate[e] x) * W_up[e] x)``, and
    ``"metrics"/moe_gate_dead`` = the share of the gate's pre-activations, over
    the rows of held experts, that the ReLU zeroes (0.5 at a fresh start; the
    columns a later kernel could skip). A shared expert under it is asked by no
    model and raises, as ``"relu2"`` on a gated expert does.

    **Ungated** (``gated=False``): an expert is two matrices,
    ``W_down[e] (w * act(W_up[e] x))`` with ``act`` the ``activation``
    (``"relu2"``: the squared ReLU of Nemotron-H, arXiv:2504.03624), two
    grouped matmuls a pass where a gated expert has three, and the shared
    expert, if any, is of the same ungated form (:class:`UngatedMLP`).

    **The latent** (``latent = L > 0``; Nemotron-3's LatentMoE): the routed
    experts read and write an ``L``-wide latent between two projections that
    all experts share::

        u = W_latent_down x                        [D, L], before the dispatch
        r = sum_j W_down[e_j] (w_j * act(W_up[e_j] u))      banks [count, L, F] / [count, F, L]
        y = W_latent_up r + shared(x)              [L, D], after the combine

    The router, the bias and the shared expert are on the full-width ``x``.
    ``W_latent_up`` is linear, so a chip's part ``W_latent_up r_held`` of the
    routed result sums with the other chips' to the whole layer's.

    **Groups** (``n_group > 1``; DeepSeek-V3, arXiv:2412.19437, section
    2.1.2's node-limited routing): the ``E`` experts lie in ``n_group`` equal
    groups in order (a group is a host); a group's score is the sum of its two
    best ``s + b``, a token keeps its best ``topk_group`` groups and the top-k
    is taken inside them. The weights are the chosen experts' ``s`` as before.
    ``n_group = 1`` is the ungrouped layer, instruction for instruction.

    **The bias** (``bias_rate > 0``) enters the choice and not the weight,
    carries no gradient and is moved by the step's own counts ``c_i`` of
    assignments (Wang et al., arXiv:2408.15664)::

        delta = bias_rate * sign(mean(c) - c);   b <- b + delta - mean(delta)

    It lives in ``"batch_stats"`` (``router_bias``, ``[E]`` float32, zeros
    at first): what a step computes from its own batch and keeps without a
    gradient, as BatchNorm's moments are. The forward uses the ``b`` it was
    given and, where the collection is mutable (the train step), leaves the
    moved one behind, so the bias rides ``TrainState`` and every checkpoint
    with no word about it in ``train/``.

    **The share** (``held = (first, count)``): the layer holds experts
    ``first .. first + count - 1`` of the ``num_experts`` and nothing of the
    others; its banks are ``[count, ...]``. The router, the bias, the top-k
    and the weights are over all ``num_experts``. The pairs whose expert is
    held sort to the front by expert, the others behind them; the three
    grouped matmuls run over the ``count`` groups (Megablox visits no tile
    past the groups' sum), and only the first ``live`` rows are summed back.
    A pair whose expert is held elsewhere adds nothing here: ``y`` is this
    chip's part of the routed result, plus the shared expert's. **The work
    follows the rows held, not N * k**, as far as static shapes allow: the
    gathers, the activation and the combine run over an expert-ordered
    buffer of twice the balanced share (``2 * N * k * count / E`` rows,
    16,384 of 65,536 at Trinity-Mini's share) whenever the step's ``live``
    rows fit it, and over the whole ``N * k`` when they do not (one
    ``lax.cond`` on ``live``, both sizes compiled): every pair whose expert
    is held is computed, whatever the imbalance, and ``moe_rows_dropped``
    says so. On the buffer the combine, and its transpose as the gather's
    gradient, is a sum by token over the buffer's ``m`` rows (the rows sorted
    by token, a tile of 128 tokens one ragged group of a ``tgmm`` on the TPU:
    ``ops/grouped_matmul.py:rows_summed_by_segment``); with every pair's row
    at hand (the large branch, or every expert held) it is the gather of all
    ``N * k`` and a sum over ``k`` neighbours, as it was. The shapes say which;
    ``"metrics"/moe_buffer_taken`` says how often. This is what expert
    parallelism asks of a layer; on one chip it runs without its exchange.

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
    parameters, computed in ``dtype``; no ``gate`` where the experts are
    ungated, ``L`` for ``D`` where they work in a latent) are three (two)
    grouped matrix multiplications over the E ragged groups. Sown:

    - ``"losses"/load_balance`` = ``aux_weight * E * sum_i f_i * P_i`` with
      ``P_i`` the mean of ``s_i`` over the tokens and ``f_i`` the share of
      the N*k **assignments** that went to expert ``i`` (so ``sum f = 1``
      and a uniform router gives ``aux_weight``). Implementations that
      count ``f_i`` as a share of the N tokens (Hugging Face's
      ``load_balancing_loss_func``) are larger by the factor k.
    - ``"losses"/router_z`` = ``z_weight * mean(logsumexp(W_r x)^2)``.
      Neither is sown at weight 0: the objective then has no auxiliary
      term and the trainer reports none.
    - ``"metrics"/moe_load_max`` = the busiest expert's assignments over
      the mean (1.0 is perfect balance, E is one expert taking all).
    - with groups, ``"metrics"/moe_groups_live`` = the groups that hold at
      least one of a token's ``k`` experts, the mean over tokens
      (``topk_group`` where the limit binds).
    - with a bias, ``"metrics"/moe_bias_absmax`` = the largest ``|b|`` of
      the bias the choice was made under (how far the balancing has had to
      lean; zero at first).
    - with a share, ``"metrics"/moe_rows_held`` = the share of the N*k
      assignments that fell on held experts (``count / E`` when balanced),
      ``/moe_held_load_max`` = the busiest held expert's rows over the mean
      ``N * k / E``, ``/moe_rows_dropped`` = assignments to held experts
      less rows the grouped matmuls cover (0: the buffer holds them all),
      and where the share has a buffer smaller than ``N * k``
      ``/moe_buffer_taken`` = 1.0 in a step whose ``live`` rows fit it (the
      buffer branch ran), else 0.0; the step averages the layers'.
    - ``"intermediates"/top_idx``, ``/router_logits`` and ``/router_in`` =
      the chosen experts ``[N, k]``, ``W_r x`` ``[N, E]`` and the router's
      own float32 operand ``x`` ``[N, D]``; with a latent ``/routed_latent`` =
      ``r`` ``[N, L]``, this chip's part of the routed sum before
      ``W_latent_up`` (only when a caller asks for the collection: a check
      against a reference).

    **Under a policy that keeps names** (``REMAT_NAMES``; every LM cell's
    ``"save_flash"`` does): the route's results the rest of the layer reads
    bear ``moe_route`` (the float32 logits, ``top_idx``, ``order``,
    ``inverse``, ``counts``, and the chosen experts' scores ``[N, k]`` before
    they are normalised (the ``top_k``'s own values, or a gather out of the
    scores that took 0.75 ms a layer of Ling's step when it ran again):
    4 N E + 16 N k + 4 E bytes, 16.8 MB + 1.0 MB at 8192 tokens, 512 experts
    and top-8), and the ``gate`` / ``up`` products over the held
    experts' buffer ``moe_held`` (2 m F bytes each). A block's recomputation
    handed them makes the scores, the normalised weights and the losses again
    (elementwise on ``[N, E]`` and ``[N, k]``), gathers the buffer's
    rows and sorts the weights, and runs no router matmul, no ``top_k``, no
    ``argsort`` and no grouped matmul of the buffer branch. The whole-``N k``
    form bears no name (537 MB a product in OLMoE's layer), and the large
    branch of the ``cond`` goes on keeping nothing.

    Device-side names: ``moe_route`` (router, scores, bias, top-k, sort,
    losses), ``moe_latent`` (both of the latent's projections),
    ``moe_experts`` (gather, the routing weights and the grouped matmuls),
    ``moe_combine`` (un-sort and sum over k), ``moe_shared`` (the shared
    expert). Each traced shape leaves one ``moe_shape`` instant in the span
    ring (``experts``, ``held``, ``top_k``, ``pairs``, ``buffer_rows``,
    ``latent``, ``width``, ``gated``, ``activation``, ``route_from``,
    ``combine_rows``: the rows a combine pass gathers on the layer's usual
    path, ``combine_tile``: the tokens a group of the buffer's sum, 0
    where there is no buffer, and ``picked``: how a chosen score is read off
    ``[N, E]``, ``"compare"``: :func:`_picked`).
    """

    num_experts: int
    top_k: int
    d_ff: int
    norm_topk_prob: bool = False
    norm_topk_eps: float = 1e-20
    aux_weight: float = 1e-2
    z_weight: float = 1e-3
    score_func: str = "softmax"
    route_scale: float = 1.0
    bias_rate: float = 0.0
    shared_d_ff: int = 0
    held: Optional[Tuple[int, int]] = None
    n_group: int = 1
    topk_group: int = 1
    gated: bool = True
    activation: str = "silu"
    latent: int = 0
    route_from: str = "ff_input"
    dtype: Any = jnp.bfloat16

    def _inside_kept_groups(self, choice):
        """``choice`` [N, E] with the experts outside a token's best
        ``topk_group`` of the ``n_group`` groups at -inf; a group's score is
        the sum of its two best entries."""
        n, e = choice.shape
        groups, kept = self.n_group, self.topk_group
        if e % groups or not 1 <= kept <= groups or e // groups < 2:
            raise ValueError(
                "%d experts in %d groups, the best %d kept" % (e, groups, kept)
            )
        if kept * (e // groups) < self.top_k:
            raise ValueError("top_k %d outgrows %d kept groups" % (self.top_k, kept))
        best_two, _ = jax.lax.top_k(choice.reshape(n, groups, e // groups), 2)
        _, best = jax.lax.top_k(jnp.sum(best_two, axis=-1), kept)    # [N, kept]
        keep = jnp.any(jax.nn.one_hot(best, groups, dtype=bool), axis=1)
        return jnp.where(jnp.repeat(keep, e // groups, axis=1), choice, -jnp.inf)

    @nn.compact
    def __call__(self, x: jax.Array, route_x: Optional[jax.Array] = None) -> jax.Array:
        b, s, d = x.shape
        e, k, f = self.num_experts, self.top_k, self.d_ff
        n = b * s
        tokens = x.reshape(n, d)
        first, count = self.held or (0, e)
        if first < 0 or count < 1 or first + count > e:
            raise ValueError("held %r is no part of %d experts" % (self.held, e))
        if self.activation not in ACTIVATIONS or (self.gated and self.activation not in GATES):
            raise ValueError(
                "activation %r: a gated expert's gate is one of %s, an ungated one "
                "takes one of %s"
                % (self.activation, ", ".join(GATES), ", ".join(sorted(ACTIVATIONS)))
            )
        relu_gate = self.gated and self.activation == "relu"
        if relu_gate and self.shared_d_ff:
            raise ValueError("a shared expert under a ReLU gate: no model asks for one")
        if self.route_from not in ROUTE_FROM:
            raise ValueError(
                "route_from %r: one of %s" % (self.route_from, ", ".join(ROUTE_FROM))
            )
        if (self.route_from == "block_input") != (route_x is not None):
            raise ValueError(
                "route_from %r and %s second operand"
                % (self.route_from, "no" if route_x is None else "a")
            )
        act = ACTIVATIONS[self.activation]
        # rows of the expert-ordered buffer a step usually needs: twice the
        # held experts' balanced share of the N * k pairs, in whole sublanes
        buffer = min(n * k, -(-2 * n * k * count // (8 * e)) * 8)
        obs_trace.get_tracer().note_once(
            "moe_shape", experts=e, held=count, top_k=k, pairs=n * k,
            buffer_rows=buffer, latent=self.latent, width=f, gated=self.gated,
            activation=self.activation, route_from=self.route_from,
            # the rows a combine pass gathers, and the tokens a group of its sum
            combine_rows=buffer, combine_tile=SEGMENT_TILE if buffer < n * k else 0,
            picked="compare",  # how a chosen score is read and its gradient sent back
        )

        with jax.named_scope("moe_route"):
            # by name, for a policy that keeps them (``REMAT_NAMES``): the rest
            # of the route is elementwise on [N, E] or [N, k] and is made again
            kept = partial(checkpoint_name, name=ROUTE_NAME)
            router_in = (
                tokens if route_x is None else _as_stored(route_x).reshape(n, d)
            ).astype(jnp.float32)
            logits = kept(nn.Dense(
                e, use_bias=False, dtype=jnp.float32, name="router",
                precision=jax.lax.Precision.HIGHEST,
            )(router_in))                               # [N, E]
            if self.score_func == "softmax":
                probs = jax.nn.softmax(logits, axis=-1)
            elif self.score_func == "sigmoid":
                probs = jax.nn.sigmoid(logits)
            else:
                raise ValueError("unknown score_func %r" % (self.score_func,))
            if self.bias_rate > 0:
                bias = self.variable(
                    "batch_stats", "router_bias", jnp.zeros, (e,), jnp.float32
                )
                self.sow("metrics", "moe_bias_absmax", jnp.max(jnp.abs(bias.value)))
                choice = probs + bias.value
            else:
                choice = probs
            if self.n_group > 1:
                choice = self._inside_kept_groups(choice)
            if choice is probs:
                weights, top_idx = _top_k_kept(probs, k)    # [N, k]
            else:  # chosen by one quantity, weighted by the scores
                top_idx = kept(jax.lax.top_k(choice, k)[1])
                weights = kept(_picked(probs, top_idx))
            if self.n_group > 1:
                hit = jnp.any(jax.nn.one_hot(
                    top_idx // (e // self.n_group), self.n_group, dtype=bool
                ), axis=1)
                self.sow("metrics", "moe_groups_live", jnp.mean(jnp.sum(hit, axis=-1)))
            if self.norm_topk_prob:
                weights = weights / (
                    jnp.sum(weights, axis=-1, keepdims=True) + self.norm_topk_eps
                )
            if self.route_scale != 1.0:
                weights = weights * self.route_scale
            if self.held is None:
                flat = top_idx.reshape(n * k)
            else:
                # held experts by their place in the bank, every other pair
                # behind them in one group that is nobody's
                here = (top_idx >= first) & (top_idx < first + count)
                flat = jnp.where(here, top_idx - first, count).reshape(n * k)
            order = kept(jnp.argsort(flat))             # stable: by expert, then pair
            inverse = kept(jnp.argsort(order))
            counts = kept(jnp.sum(
                jax.nn.one_hot(top_idx, e, dtype=jnp.int32), axis=(0, 1)
            ))                                          # [E], sums to N*k
            if (
                self.bias_rate > 0 and not self.is_initializing()
                and self.is_mutable_collection("batch_stats")
            ):
                load = counts.astype(jnp.float32)
                delta = self.bias_rate * jnp.sign(jnp.mean(load) - load)
                bias.value = bias.value + delta - jnp.mean(delta)
            if self.held is None:
                group_sizes, live = counts, None
            else:
                group_sizes = counts[first:first + count]
                live = jnp.sum(group_sizes)
                self.sow("metrics", "moe_rows_held", live / (n * k))
                self.sow(
                    "metrics", "moe_held_load_max",
                    jnp.max(group_sizes) * (e / (n * k)),
                )
                covered = jnp.where(live <= buffer, buffer, n * k)
                self.sow(
                    "metrics", "moe_rows_dropped",
                    jnp.sum(here) - jnp.minimum(live, covered),
                )
            share = counts.astype(jnp.float32) / (n * k)
            if self.aux_weight:
                self.sow(
                    "losses", "load_balance",
                    self.aux_weight * e * jnp.sum(share * jnp.mean(probs, axis=0)),
                )
            if self.z_weight:
                self.sow(
                    "losses", "router_z",
                    self.z_weight
                    * jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
                )
            self.sow("metrics", "moe_load_max", jnp.max(share) * e)
            self.sow("intermediates", "top_idx", top_idx)
            self.sow("intermediates", "router_logits", logits)
            # the float32 operand itself: XLA may keep the norm's float32
            # result under it and never round to ``dtype`` (excess precision)
            self.sow("intermediates", "router_in", router_in)

        if self.latent:
            with jax.named_scope("moe_latent"):
                tokens = nn.Dense(
                    self.latent, use_bias=False, dtype=self.dtype, name="latent_down"
                )(tokens)
        width = tokens.shape[-1]  # what a routed expert reads and writes
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=0)
        banks = tuple(
            self.param(name, init, (count, width, f), jnp.float32)
            for name in (("gate", "up") if self.gated else ("up",))
        ) + (self.param("down", init, (count, f, width), jnp.float32),)

        def routed(m, tokens, weights, *banks):
            """The held experts' part of the layer from the first ``m`` rows
            in expert order: every row when all experts are held, else a
            buffer that holds the ``live`` rows of held experts. ``banks``:
            ``gate`` (a gated expert's), ``up`` and ``down``. Beside it, under
            a ReLU gate, the share of the held rows' gate values it zeroes."""
            first_rows = order if m == n * k else order[:m]
            with jax.named_scope("moe_experts"):
                rows = _rows_sorted(
                    tokens.astype(self.dtype), first_rows, inverse, k, live
                )
                w_sorted = _scalars_sorted(weights.reshape(n * k), order, inverse)
                if m < n * k:
                    w_sorted = w_sorted[:m]
                into = [
                    grouped_matmul(rows, bank.astype(self.dtype), group_sizes)
                    for bank in banks[:-1]
                ]
                if m < n * k:  # the held experts' buffer alone: [m, F] each
                    into = [checkpoint_name(a, HELD_NAME) for a in into]
                if live is not None:
                    # Megablox writes no row past the groups' sum, forward or
                    # backward: what lies there is whatever the memory held.
                    # Zeros instead, on the way in and (the select's
                    # transpose) on the way back, or one NaN there reaches
                    # the router through the weights' gradient
                    nobodys = (jnp.arange(m) >= live)[:, None]
                    into = [jnp.where(nobodys, 0, a) for a in into]
                hidden = act(into[0].astype(jnp.float32))
                dead = None
                if relu_gate:  # nobody's rows are zeros: they count as not alive
                    alive = jnp.sum(hidden > 0, dtype=jnp.float32)
                    held_rows = m if live is None else jnp.maximum(live, 1)
                    dead = 1.0 - alive / (held_rows * f)
                if self.gated:
                    hidden = hidden * into[1].astype(jnp.float32)
                hidden = (hidden * w_sorted[:, None]).astype(self.dtype)  # rounded once
                if live is not None:
                    hidden = jnp.where(nobodys, 0, hidden)  # for its cotangent's rows
                out = grouped_matmul(hidden, banks[-1].astype(self.dtype), group_sizes)
            with jax.named_scope("moe_combine"):
                # [N, width], float32
                return _rows_combined(out, first_rows, inverse, k, live), dead

        operands = (tokens, weights, *banks)
        if buffer < n * k:
            # the whole N * k only for a step whose held rows outgrow the
            # buffer; it keeps nothing for its backward (which computes it
            # again), so the step's memory is the usual path's
            self.sow("metrics", "moe_buffer_taken", (live <= buffer).astype(jnp.float32))
            y, dead = jax.lax.cond(
                live <= buffer, partial(routed, buffer),
                jax.checkpoint(partial(routed, n * k)), *operands,
            )
        else:
            y, dead = routed(n * k, *operands)
        if dead is not None:
            self.sow("metrics", "moe_gate_dead", dead)
        if self.latent:
            # this chip's part of the routed sum, in the latent (a check's)
            self.sow("intermediates", "routed_latent", y)
            with jax.named_scope("moe_latent"):
                y = nn.Dense(d, use_bias=False, dtype=self.dtype, name="latent_up")(
                    y.astype(self.dtype)
                )
        y = y.reshape(b, s, d).astype(x.dtype)
        if self.shared_d_ff:
            from edl_tpu.models.transformer import SwiGLU  # imports this module

            with jax.named_scope("moe_shared"):
                shared = (
                    SwiGLU(self.shared_d_ff, self.dtype, name="shared") if self.gated
                    else UngatedMLP(self.shared_d_ff, self.dtype, self.activation, name="shared")
                )
                y = y + shared(x)
        return y


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
    def __call__(self, x: jax.Array, route_x: Optional[jax.Array] = None) -> jax.Array:
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
# over ``ep``; the router stays replicated, and so do the latent's two
# projections and the shared expert (``moe/latent_*/kernel``,
# ``moe/shared/*/kernel``: no bank, no rule). ``w[io]`` are SwitchMoE's two
# banks, ``gate``/``up``/``down`` DroplessMoE's three (``up``/``down`` alone
# where its experts are ungated).
MOE_EP_RULES = [
    (r".*/moe/(w[io]|gate|up|down)$", P("ep", None, None)),
]
