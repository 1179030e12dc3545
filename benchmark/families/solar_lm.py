"""Family ``solar_lm``: the program's ``TransformerLM`` as one chip's share of a
Kimi-delta-attention / gated grouped-query-attention hybrid with sigmoid-routed
experts in every layer (upstage's Solar-Open2 line): by ``gqa_layers`` a block's
mixer is softmax attention without a position term whose heads' outputs pass a
sigmoid gate (``models/transformer.py:Attention`` with ``attn_gate``, through
the flash kernels) or Kimi delta attention **as Kimi Linear publishes it**
(``models/gated_delta.py:KimiDeltaMixer`` with the softplus gate that no bound
holds, beta in (0, 2) and a low-rank pair for the decay and for the gate, over
``ops/gated_delta.py:kda_rule``'s halving form); a norm before each branch;
every layer's feed-forward the sigmoid-scored experts under a balancing bias,
**the experts this chip holds** (``models/moe.py:DroplessMoE(held=...)``)
beside a shared one; an untied head over a slice of the vocabulary. Built from
a file that keeps the published ``config.json`` keys.

See ``resnet_vd.py`` for what a family is. The token generator is
``transformer_lm.py``'s (uniform ids of the held slice); the routing comparison
is ``kda_lm.py``'s without the groups (the reference computes with the
program's choice and each choice is judged against the reference's own).
"""

from __future__ import annotations

import numpy as np

from benchmark.families.afmoe_lm import (  # noqa: F401 — the family's interface
    BIAS_ABS_TOL,
    BIAS_MEAN_TOL,
    ROUTER_ARITHMETIC_REL_TOL,
)
from benchmark.families.kda_lm import RULE_ARGS
from benchmark.families.moe_lm import (  # noqa: F401 — the family's interface
    GMM_REL_TOL,
    MOE_TRACE_KERNELS,
    grouped_matmul_vs_reference,
)
from benchmark.families.sparse_lm import starts_like_a_trained_one
from benchmark.families.ssm_lm import _rel, _rms_rel
from benchmark.families.transformer_lm import (  # noqa: F401 — the family's interface
    KERNEL_REL_TOL,
    LOSS_REL_TOL,
    TRACE_KERNELS,
    _items,
    kernel_vs_reference,
)
from benchmark.families.transformer_lm import host_batches as _host_batches

# Every limit below lies between two readings: the largest the program gave on
# the chip over this PR's seeds (TPU v5 lite; PERF.md section 6, PR 51: the
# cell's own traffic on seeds 3000005831-834 and the final runs, and freshly
# drawn parameters on seed 3000005811), and what the same program reads in the
# nearest precision below, ``float8_e4m3fn``: at the cell's own size on the
# chip (``bench_results/solar_precision_below.py``, seed 3000005811) and at a
# width of 256 on the CPU (``benchmark/tests/test_solar_lm.py``). The 8-bit
# program hands its kernels bfloat16 operands (they and the carry take no 8-bit
# float). The stream's three limits were set anew with the start of
# ``started``: under a table of rms 1.0 both readings are a third of what they
# were under the table as drawn (0.035 and 0.47 for the logits), and the old
# limits (0.17, 0.15, 0.54) would have let the 8-bit program's logits pass.
#
# Logits of the program (bfloat16 operands, float32 accumulation, float32
# logits) against the float32 reference computed with the program's own choice
# of experts, as max |difference| over max |reference| over every token: 0.0045
# to 0.0049 after the cell's window and 0.0124 fresh on the chip, 0.015 to
# 0.019 at the toy's width; in 8 bits 0.159 on the chip, 0.22 to 0.25 at the
# toy's width. The limit is 3.6 times the largest sound reading and between a
# third and a fifth of the 8-bit ones.
LOGITS_REL_TOL = 0.045
# The router's logits of the program against the reference's, layer by layer,
# as max |difference| over max |reference|: a float32 router whose input is a
# bfloat16 residual stream, four routers deep: 0.0066 to 0.0074 after the
# window and 0.0102 fresh on the chip, 0.015 to 0.018 at the toy's width; in 8
# bits 0.136 on the chip, 0.19 to 0.22 at the toy's width.
ROUTER_LOGITS_REL_TOL = 0.04
# Tokens whose choice of experts may differ from the one the reference makes
# for itself on the same stream, in the expert layer where most do, by
# ``afmoe_lm.py``'s rule: a flip is right only where the reference's margin (the
# 8th's lead over the 9th of ``s + b``) is at most twice the largest difference
# between the token's own program and reference scores; any other difference
# fails the check as ``tokens_misrouted``. 320 sigmoid scores lie two and a half
# times as densely as Trinity's 128: 9.4 to 9.8% of the tokens flip in the
# fourth expert layer after the window and 9.7% fresh on the chip, none
# misrouted (the widest flipped margin 0.0038 of a score), 7 to 10% at the toy's
# width; in 8 bits 80.4% on the chip, 77 to 78% at the toy's width.
ROUTE_FLIP_LIMIT = 0.3
# What the program's first linear layer hands its rule (q, k, v, beta, from the
# trained parameters on the normed embedding) against the reference's float32
# forms of the same, each as max |difference| over max |reference|, the largest
# of the four. What differs is the rounding of the projections' operands and
# outputs and of the convolutions' results to bfloat16: 0.0052 to 0.0062 on
# the chip; an 8-bit mixer reads 0.06 and more (``test_solar_lm.py``). A beta
# left in (0, 1), a dropped SiLU or L2 norm is off by a half or the whole.
RULE_INPUTS_REL_TOL = 0.02
# The log-decay g of that layer, likewise: -exp(A_log) softplus(f + dt_bias)
# with f through a low-rank pair whose first product is rounded to bfloat16 and
# whose second keeps a float32 accumulator. The softplus has no steepest point
# a gate's bound would give (its slope is at most 1, times exp(A_log) up to
# 16): 0.0023 to 0.0039 on the chip. The safe gate in its place (g held above
# -5) reads 0.8 and more, a full-rank matrix in the pair's place the whole
# value: the limit is thirteen times the chip's largest and a sixteenth of
# either fault.
RULE_DECAY_REL_TOL = 0.05
# The chunked rule alone against the step-by-step recurrence (float32, on the
# host) at the step's own shape: on the inputs the program made (log-decays
# down to -25 to -43 a step in a fresh layer: no window of this cell lies
# inside what the rule's form before PR 51 could run), and on drawn inputs whose
# log-decays reach -30 a step on a quarter of the channels (-68 to -84 at the
# gate's tail) and whose beta passes 1 (``drawn_rule_inputs``). The output as
# max |difference| over max |reference|, the final state as root-mean-square
# difference over root-mean-square reference. What differs is the rounding of
# the chunk's matmul operands to bfloat16, the decayed keys' among them: 0.0048
# to 0.0083 and 0.0026 to 0.0035 on the chip. The rule's form before PR 51 (a
# sub-block of 16 steps under one reference) is not finite on either; the
# scalar rule standing in reads over 0.15.
RULE_REL_TOL = 0.03
STATE_RMS_TOL = 0.02
# The rule once more on the same inputs widened to float32 at the highest
# matmul precision: what is left is the precision of the exponents (every
# factor of the halving form is at most 1), of the solve and of the carried
# state. On the chip 1.8e-6 to 2.2e-5 and 1.7e-6 to 9.6e-6; a state carried in
# bfloat16 reads 7.8e-4 and more (``test_solar_lm.py``).
EXACT_REL_TOL = 1.5e-4
EXACT_STATE_RMS_TOL = 1.5e-4

DRAWN_HEADS = 8  # heads of the drawn run: the host walks every step of each
DRAWN_DEEP_SHARE = 0.25  # of the drawn channel-steps, log-decays in (-30, -5)


def layers(config, kind):
    from benchmark.reference.solar_lm import layer_kinds

    return sum(k == kind for k in layer_kinds(config))


def kda_spec(config):
    from edl_tpu.models import KimiDeltaSpec

    linear = config["linear_attn_config"]
    if config["kda_use_full_proj"] or not config["kda_allow_neg_eigval"]:
        raise ValueError("solar_lm: low-rank pairs and beta in (0, 2), as published")
    if linear["num_kv_heads"] is not None:
        raise ValueError("solar_lm: as many key heads as heads")
    return KimiDeltaSpec(
        num_heads=linear["num_heads"], key_dim=linear["head_dim"],
        value_dim=linear["head_dim"], d_conv=linear["short_conv_kernel_size"],
        chunk=config["train"]["rule_chunk"], lower_bound=None, neg_eigval=True,
        gate_rank=linear["head_dim"],
    )


def arch_spec(config):
    from edl_tpu.models import ArchSpec

    if config["use_rope"] or not config["use_gqa_gate"]:
        raise ValueError("solar_lm: no rotation and a gate on the heads' outputs, as published")
    if config["first_k_dense_replace"]:
        raise ValueError("solar_lm: every layer an expert layer, as published")
    kinds = {"softmax": "attention", "linear": "kda"}
    from benchmark.reference.solar_lm import layer_kinds

    return ArchSpec(
        layer_types=tuple(kinds[kind] for kind in layer_kinds(config)),
        kda=kda_spec(config), head_dim=config["head_dim"], rope=False,
        attn_gate=True, dense_layers=0,
    )


def moe_spec(config):
    from edl_tpu.models import MoESpec

    share = config["share"]
    if config["n_shared_experts"] != 1:
        raise ValueError("solar_lm: one shared expert, as published")
    return MoESpec(
        num_experts=share["router_experts"], top_k=config["num_experts_per_tok"],
        d_ff=config["moe_intermediate_size"], norm_topk_prob=config["norm_topk_prob"],
        aux_weight=0.0, z_weight=0.0, score_func="sigmoid",
        route_scale=config["routed_scaling_factor"],
        bias_rate=config["train"]["expert_bias_rate"],
        shared_d_ff=config["moe_intermediate_size"],
        held=(share["experts_first"], config["n_routed_experts"]),
    )


def host_batches(config, global_batch, seed, n_batches=None):
    """``transformer_lm.py``'s uniform ids of the held slice, and
    ``train.distinct_batches`` of them: more than a run dispatches (10 steps of
    warm-up, 12 traced, a window of 30 s at over 200 ms a step), so **no batch
    comes twice**, as in the pre-training the cell stands for. With the two
    batches of the other LM cells this model learns both by heart inside the
    warm-up (loss 0.0003), and what it learns with them is to route them to
    the 8 experts that are held, the only ones whose output reaches its loss:
    the step's length then follows the seed (PERF.md section 6, PR 51)."""
    if n_batches is None:
        n_batches = config["train"]["distinct_batches"]
    return _host_batches(config, global_batch, seed, n_batches=n_batches)


def started(lm, start):
    """``lm`` (the program's ``TransformerLM`` class) with its first values
    changed and nothing else, Keye's way (PR 41: ``sparse_lm.py``): **the model
    this cell's traffic converges to, as far as initial values can say it.**

    - ``embedding_rms``: the table's rows at that rms a value (drawn at
      ``d_model ** -0.5`` the table is lost under the first mixer's output, the
      stream is one direction where the routers read it and every token reads
      the same 320 scores).
    - ``head_rms``: the head's values at that rms; **0.0 is the optimum of ids
      that cannot be learnt** (uniform logits, a loss of ``ln vocab_size``). A
      head as drawn starts the loss half a unit above it, and what AdamW at
      4e-4 then learns for a hundred steps is to shrink the logits, one
      direction for every token: past step 130 the deeper layers' routers send
      every token to the same eight experts, a held one among them in three
      runs of four (PERF.md section 6, PR 51). From the optimum the gradients
      are the batches' noise, as a trained model's are; every parameter, the
      head too, is trained from the first step on. Absent: as the class draws
      it (what a check of the equations wants: a zero head reads 0 = 0).
    """
    import flax.linen as nn
    import jax

    held = starts_like_a_trained_one(lm, start["embedding_rms"])
    if start.get("head_rms") is None:
        return held

    class StartedLM(held):
        @nn.nowrap
        def init(self, *args, **kwargs):
            variables = super().init(*args, **kwargs)
            params = dict(variables["params"])
            params["lm_head"] = jax.tree.map(
                lambda a: a * (start["head_rms"] * self.d_model ** 0.5), params["lm_head"]
            )
            return {**variables, "params": params}

    return StartedLM


def as_drawn(config):
    """``config`` with the head as the class draws it: for a comparison of
    equations on freshly drawn parameters (the tests, the precision-below
    control), where the start's zero head would compare nothing."""
    train = dict(config["train"])
    train["start"] = {k: v for k, v in train["start"].items() if k != "head_rms"}
    return dict(config, train=train)


def build(config, global_batch, seed):
    import jax.numpy as jnp
    import optax

    from edl_tpu.models import TransformerLM
    from edl_tpu.train import cross_entropy_loss

    train = config["train"]
    if train["compute_dtype"] not in ("bfloat16", "float32"):
        raise ValueError("solar_lm: compute_dtype %r" % train["compute_dtype"])
    model = started(TransformerLM, train["start"])(
        dtype=getattr(jnp, train["compute_dtype"]),
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        num_layers=config["num_hidden_layers"], d_ff=config["intermediate_size"],
        remat=train["remat"], remat_policy=train["remat_policy"],
        norm_eps=config["rms_norm_eps"], moe=moe_spec(config), arch=arch_spec(config),
    )
    opt = train["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError("solar_lm: unknown optimizer %r" % opt["name"])

    def lm_loss(logits, targets):
        return cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
        )

    return {
        "model": model,
        "optimizer": optax.adamw(opt["lr"]),
        "loss": lm_loss,
        "sample_input": np.zeros((global_batch, train["seq_len"]), np.int32),
        "apply_kwargs": None,
        "items_per_step": global_batch * train["seq_len"],
    }


def kda_mixer_params(config):
    """The matrices of one linear layer: q, k, v and the out projection (hidden
    x H d each), the decay's and the gate's pairs (hidden x d, then d x H d)
    and beta's (hidden x H)."""
    linear = config["linear_attn_config"]
    d, h, hd = config["hidden_size"], linear["num_heads"], linear["head_dim"]
    return 4 * d * h * hd + 2 * (d * hd + hd * h * hd) + d * h


def gqa_mixer_params(config):
    """The matrices of the softmax layer: q, the gate and the out projection
    at the query heads' width, k and v at the key heads'."""
    d, hd = config["hidden_size"], config["head_dim"]
    return 3 * d * config["num_attention_heads"] * hd + 2 * d * config["num_key_value_heads"] * hd


def routed_experts_a_token(config):
    """Expert matmuls a token meets HERE, expected under balanced routing: its
    ``num_experts_per_tok`` choices fall on the held ``n_routed_experts`` of
    the ``router_experts`` with that share (8 x 8 / 320 = 1/5)."""
    return (
        config["num_experts_per_tok"] * config["n_routed_experts"]
        / config["share"]["router_experts"]
    )


def matmul_params(config):
    """Parameters that multiply every token on this chip: each layer's mixer,
    in every layer the router (at its whole width), the shared expert and the
    expected ``routed_experts_a_token`` routed ones, and the head over the
    slice."""
    d, fe = config["hidden_size"], config["moe_intermediate_size"]
    expert_layer = (
        d * config["share"]["router_experts"] + 3 * d * fe
        + routed_experts_a_token(config) * 3 * d * fe
    )
    return (
        layers(config, "linear") * kda_mixer_params(config)
        + layers(config, "softmax") * gqa_mixer_params(config)
        + config["num_hidden_layers"] * expert_layer
        + d * config["vocab_size"]
    )


def rule_forward_flops_per_token(config):
    """The chunked rule's products for one token of one layer, forward, a
    multiply-add as 2, at a chunk of 64 (``kda_lm``'s count): in a chunk ``K
    K^T`` and ``Q K^T`` with the decay inside the contraction (half of each
    masked away: C d_k each), ``W`` and ``U`` through a triangular ``T`` (C d_k
    and C d_v), the scores times ``V_new`` (C d_v); against the state ``W S``,
    ``Q S`` and ``K^T V_new`` (2 d_k d_v each); the solve as forward
    substitution would do it (C^2 / 3 a token). **Of the work the equations
    need, whatever implements it**: the exponentials, the running sums and the
    norms are elementwise and count for nothing, and what the halving form
    spends on a level's pairs outside their block is not needed work."""
    linear = config["linear_attn_config"]
    chunk, d = 64, linear["head_dim"]
    head = chunk * 5 * d + 6 * d * d + chunk * chunk / 3.0
    return head * linear["num_heads"]


def attention_forward_flops(config, sequences):
    """The softmax layers' causal attention forward over ``sequences``
    sequences: T^2 / 2 visible pairs a query head, 2 head_dim operations a pair
    for the scores and as many for the values."""
    t = config["train"]["seq_len"]
    return (
        2.0 * sequences * config["num_attention_heads"] * (t * t / 2.0)
        * 2 * config["head_dim"] * layers(config, "softmax")
    )


def flops_per_item(config):
    """As ``transformer_lm.flops_per_item``: 6 per matrix-multiplied parameter
    a token meets (the routed experts at their expected share), three times the
    softmax layers' attention forward, three times the chunked rule's forward
    of the linear layers. Recomputation under remat, the convolutions, norms,
    gates, the softmax, the sort and the optimizer are not counted."""
    t = config["train"]["seq_len"]
    return (
        6.0 * matmul_params(config)
        + 3.0 * attention_forward_flops(config, 1) / t
        + 3.0 * rule_forward_flops_per_token(config) * layers(config, "linear")
    )


def kernel_flops(config, sequences):
    """What the softmax layers' flash kernels execute: the forward's two
    products and the fused backward's five (the scores, ``dP``, ``dV``, ``dK``,
    ``dQ``), over visible pairs: 2 x 7 head_dim a pair."""
    t = config["train"]["seq_len"]
    pairs = sequences * config["num_attention_heads"] * t * t / 2.0
    return 2.0 * pairs * 7 * config["head_dim"] * layers(config, "softmax")


def kda_scan_flops(config, tokens):
    """What the rules have to compute for ``tokens`` tokens, all linear layers,
    forward and backward (the backward of a matmul is two). What remat
    computes a second time is not needed work."""
    return 3.0 * rule_forward_flops_per_token(config) * tokens * layers(config, "linear")


def kda_scan_bytes(config, tokens):
    """The least HBM traffic of that work: the forward reads q, k, v
    (bfloat16), g (float32, a value a key channel) and beta (float32) and
    writes o; the backward reads them and ``do`` and writes the five
    gradients. Nothing between has to touch HBM."""
    linear = config["linear_attn_config"]
    h, d = linear["num_heads"], linear["head_dim"]
    inputs = 2 * 3 * h * d + 4 * h * d + 4 * h
    forward = inputs + 2 * h * d
    backward = inputs + 2 * h * d + inputs
    return float(forward + backward) * tokens * layers(config, "linear")


def moe_kernel_flops(config, tokens):
    """What the grouped matmuls have to compute for ``tokens`` tokens, all
    expert layers: gate, up and down over the rows that fall on held experts
    (``routed_experts_a_token`` a token, expected), forward and both gradients."""
    rows = tokens * routed_experts_a_token(config)
    return (
        6.0 * 3 * rows * config["hidden_size"] * config["moe_intermediate_size"]
        * config["num_hidden_layers"]
    )


def moe_kernel_bytes(config, tokens):
    """The least HBM traffic of that work (``moe_lm.moe_kernel_bytes`` over the
    held rows and the held banks)."""
    rows = tokens * routed_experts_a_token(config)
    d, f, e = config["hidden_size"], config["moe_intermediate_size"], config["n_routed_experts"]
    return 9.0 * (rows * d * 2 + rows * f * 2 + e * d * f * 2) * config["num_hidden_layers"]


def check(config, state, seed):
    """On one seeded sequence, with the trained parameters and the trained
    bias: logits and the cross-entropy against the plain reference computed
    with the program's choice of experts (``kda_lm.py``'s form); the router's
    logits, scores and choices layer by layer and token by token; the bias the
    program leaves behind against the reference's rule on the program's counts;
    what the first linear layer hands its rule, and the chunked rule alone
    against the step-by-step recurrence on exactly that and on drawn inputs
    whose log-decays reach -30 a step and whose beta passes 1; then the flash
    kernels against dense float32 attention at the step's own grouped shape and
    the grouped matmul at the held rows' shape."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import solar_lm as reference
    from edl_tpu.train import cross_entropy_loss

    n = config["check"]["sample_items"]
    t = _items(config, seed + 7, n)
    # run.py hands over plain arrays on one device: no second copy of 10 GB
    params, stats, apply_fn = state.params, state.batch_stats, state.apply_fn
    del state
    tokens, targets = t[:, :-1], t[:, 1:]
    expert_layers = range(config["num_hidden_layers"])

    @jax.jit
    def program(params, stats, tokens, targets):
        logits, left = apply_fn(
            {"params": params, "batch_stats": stats}, tokens,
            mutable=["intermediates", "batch_stats", "metrics"],
        )
        ce, _ = cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
        )
        seen = [left["intermediates"]["layer_%d" % i]["moe"] for i in expert_layers]
        sown = [left["metrics"]["layer_%d" % i]["moe"] for i in expert_layers]
        return logits, ce, {
            "experts": jnp.stack([p["top_idx"][0] for p in seen]),
            "router_logits": jnp.stack([p["router_logits"][0] for p in seen]),
            "router_in": jnp.stack([p["router_in"][0] for p in seen]),
            "bias_after": jnp.stack([
                left["batch_stats"]["layer_%d" % i]["moe"]["router_bias"]
                for i in expert_layers
            ]),
            "rows_held": jnp.stack([p["moe_rows_held"][0] for p in sown]),
            "rows_dropped": jnp.stack([p["moe_rows_dropped"][0] for p in sown]),
        }

    @jax.jit
    def plain(params, stats, tokens, targets, chosen):
        logits, info = reference.forward(config, params, stats, tokens, chosen)
        return logits, reference.cross_entropy(logits, targets), info

    @jax.jit
    def rule(stats, experts):  # the reference's rule on the PROGRAM's counts
        e = config["share"]["router_experts"]
        return jnp.stack([
            reference.bias_update(
                config, stats["layer_%d" % i]["moe"]["router_bias"],
                jnp.zeros((e,), jnp.int32).at[experts[j].reshape(-1)].add(1),
            )
            for j, i in enumerate(expert_layers)
        ])

    got_logits, got_ce, routed = program(params, stats, tokens, targets)
    with jax.default_matmul_precision("highest"):
        want_logits, want_ce, info = plain(
            params, stats, tokens, targets, routed["experts"]
        )
    bias = jnp.stack([stats["layer_%d" % i]["moe"]["router_bias"] for i in expert_layers])
    bias_err = float(jnp.max(jnp.abs(routed["bias_after"] - rule(stats, routed["experts"]))))
    bias_mean = float(jnp.max(jnp.abs(jnp.mean(bias, axis=-1))))
    differs = jnp.any(
        jnp.sort(routed["experts"], axis=-1) != jnp.sort(info["experts"], axis=-1),
        axis=-1,
    )
    moved_logits = jnp.max(jnp.abs(routed["router_logits"] - info["router_logits"]), axis=-1)
    router_rel = float(jnp.max(moved_logits) / jnp.max(jnp.abs(info["router_logits"])))
    moved = jnp.max(
        jnp.abs(jax.nn.sigmoid(routed["router_logits"]) - info["scores"]), axis=-1
    )
    # the router's arithmetic on its own input, and what a bfloat16 router
    # reads there: the precision below the stated one, which has to fail
    weights = jnp.stack([
        params["layer_%d" % i]["moe"]["router"]["kernel"] for i in expert_layers
    ])
    fed = routed["router_in"].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        exact = jnp.einsum("lnd,lde->lne", fed, weights)
        coarse = jnp.einsum(
            "lnd,lde->lne", fed, weights.astype(jnp.bfloat16).astype(jnp.float32)
        ).astype(jnp.bfloat16).astype(jnp.float32)
    largest = jnp.max(jnp.abs(exact))
    arithmetic_rel = float(jnp.max(jnp.abs(routed["router_logits"] - exact)) / largest)
    arithmetic_rel_bf16 = float(jnp.max(jnp.abs(coarse - exact)) / largest)
    del fed, exact, coarse, weights
    misrouted = int(jnp.sum(differs & (info["margin"] > 2.0 * moved)))
    flips_a_layer = [float(v) for v in jnp.mean(differs, axis=-1)]
    flip_share = max(flips_a_layer)  # judged: the layer where most tokens flip
    widest_flip = float(jnp.max(jnp.where(differs, info["margin"], 0.0)))
    rel = float(jnp.max(jnp.abs(got_logits - want_logits)) / jnp.max(jnp.abs(want_logits)))
    finite = bool(jnp.isfinite(got_logits).all())
    rows_held = [float(v) for v in routed["rows_held"]]
    rows_dropped = float(jnp.sum(routed["rows_dropped"]))
    del got_logits, want_logits, stats, info, routed
    loss_rel = abs(float(got_ce) - float(want_ce)) / abs(float(want_ce))

    # the first linear layer's mixer on the normed embedding: its own norm's
    # scale, whatever stream the layers before it leave it in the model
    first = reference.layer_kinds(config).index("linear")
    x = jnp.asarray(params["embed"]["embedding"])[tokens[:1]].astype(jnp.bfloat16)
    scale = jnp.asarray(params["layer_%d" % first]["ln1"]["scale"])
    x32 = x.astype(jnp.float32)
    x = (x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + config["rms_norm_eps"]
    ) * scale).astype(jnp.bfloat16)
    made = rule_vs_reference(config, params["layer_%d" % first]["kda"], x)
    del params, x, x32
    drawn = rule_vs_reference(config, None, None, seed=seed)
    b, steps = config["train"]["batch_per_chip"], config["train"]["seq_len"]
    kernel = kernel_vs_reference(
        seed % (2 ** 31), b, config["num_attention_heads"],
        config["num_key_value_heads"], steps, config["head_dim"],
    )
    held_rows = dict(
        config, num_experts=config["n_routed_experts"], num_experts_per_tok=1,
        intermediate_size=config["moe_intermediate_size"],
    )  # the held groups of b * T * k / E rows expected: what the held experts see
    gmm = grouped_matmul_vs_reference(
        held_rows, seed, int(b * steps * routed_experts_a_token(config))
    )
    ok = (
        finite and rel <= LOGITS_REL_TOL and loss_rel <= LOSS_REL_TOL
        and router_rel <= ROUTER_LOGITS_REL_TOL
        and arithmetic_rel <= ROUTER_ARITHMETIC_REL_TOL
        and misrouted == 0 and flip_share <= ROUTE_FLIP_LIMIT
        and bias_err <= BIAS_ABS_TOL and bias_mean <= BIAS_MEAN_TOL
        and rows_dropped == 0
        and made["inputs_rel_err"] <= RULE_INPUTS_REL_TOL
        and made["decay_rel_err"] <= RULE_DECAY_REL_TOL
        and all(
            r["rel_err"] <= RULE_REL_TOL and r["state_rms_err"] <= STATE_RMS_TOL
            and r["exact_rel_err"] <= EXACT_REL_TOL
            and r["exact_state_rms_err"] <= EXACT_STATE_RMS_TOL
            for r in (made, drawn)
        )
        and kernel["max_rel_err"] <= KERNEL_REL_TOL
        and gmm["max_rel_err"] <= GMM_REL_TOL
    )
    return {
        "ok": bool(ok), "logits_rel_err": rel, "logits_rel_tol": LOGITS_REL_TOL,
        "loss": float(got_ce), "reference_loss": float(want_ce),
        "loss_rel_err": loss_rel, "loss_rel_tol": LOSS_REL_TOL,
        "router_logits_rel_err": router_rel,
        "router_logits_rel_tol": ROUTER_LOGITS_REL_TOL,
        "router_arithmetic_rel_err": arithmetic_rel,
        "router_arithmetic_rel_tol": ROUTER_ARITHMETIC_REL_TOL,
        "router_arithmetic_rel_err_of_a_bfloat16_router": arithmetic_rel_bf16,
        "router_scores_abs_err": float(jnp.max(moved)),
        "flipped_share": flip_share, "flipped_limit": ROUTE_FLIP_LIMIT,
        "flipped_share_by_layer": flips_a_layer,
        "widest_flipped_margin": widest_flip, "tokens_misrouted": misrouted,
        "bias_abs_err": bias_err, "bias_abs_tol": BIAS_ABS_TOL,
        "bias_mean": bias_mean, "bias_mean_tol": BIAS_MEAN_TOL,
        "bias_abs_max": float(jnp.max(jnp.abs(bias))),
        "rows_held": rows_held, "rows_dropped": rows_dropped,
        "sample_items": n, "rule": made, "rule_drawn": drawn,
        "rule_inputs_rel_tol": RULE_INPUTS_REL_TOL,
        "rule_decay_rel_tol": RULE_DECAY_REL_TOL, "rule_rel_tol": RULE_REL_TOL,
        "state_rms_tol": STATE_RMS_TOL, "exact_rel_tol": EXACT_REL_TOL,
        "exact_state_rms_tol": EXACT_STATE_RMS_TOL,
        "kernel": kernel, "kernel_rel_tol": KERNEL_REL_TOL,
        "grouped_matmul": gmm, "grouped_matmul_rel_tol": GMM_REL_TOL,
    }


def drawn_rule_inputs(config, seed, t, heads=DRAWN_HEADS):
    """Seeded inputs of the rule at the layer's widths that cover what the
    softplus gate can give: a channel's ``g`` is ``-16 softplus(z)`` with ``z``
    spread over (-8, 0) by channel plus noise a step (a head drawn at ``A_log =
    log 16``: decays from 0.995 a step down to e^-11), ``DRAWN_DEEP_SHARE`` of
    the channel-steps lie in (-30, -5) whatever ``z`` says, every fourth head
    spends steps ``t/4 .. t/4 + 32`` at -30 on every channel (half a chunk:
    e^-960 over the run), and ``beta`` is ``2 sigmoid`` of a wide normal, past
    1 on half the steps."""
    import jax
    import jax.numpy as jnp

    d = config["linear_attn_config"]["head_dim"]
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 7)
    unit = lambda m: m / jnp.sqrt(jnp.sum(m * m, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (1, t, heads, d))) * d ** -0.5
    k = unit(jax.random.normal(keys[1], (1, t, heads, d)))
    v = jax.random.normal(keys[2], (1, t, heads, d))
    z = jnp.linspace(-8.0, 0.0, d) + jax.random.normal(keys[3], (1, t, heads, d))
    g = -16.0 * jax.nn.softplus(z)
    deep = jax.random.uniform(keys[4], g.shape) < DRAWN_DEEP_SHARE
    g = jnp.where(deep, -jax.random.uniform(keys[5], g.shape, minval=5.0, maxval=30.0), g)
    at_bound = (jnp.arange(t) >= t // 4) & (jnp.arange(t) < t // 4 + 32)
    g = jnp.where(
        at_bound[None, :, None, None] & (jnp.arange(heads) % 4 == 0)[None, None, :, None],
        -30.0, g,
    )
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(keys[6], (1, t, heads)))
    bf16 = lambda a: a.astype(jnp.bfloat16)  # noqa: E731
    return bf16(q), bf16(k), bf16(v), g, beta


def rule_vs_reference(config, kda_params, x, mixer=None, rule=None, seed=None):
    """``kda_lm.rule_vs_reference`` for this family's layer: the rule's inputs
    as the program's ``KimiDeltaMixer`` makes them from the parameters
    ``kda_params`` of ``layer_i/kda`` on the block's normed input ``x`` ``[1,
    T, hidden]`` (bfloat16) against ``reference.rule_inputs`` (or, with
    ``seed`` and no parameters, ``drawn_rule_inputs``); then ``kda_rule`` at
    the configuration's chunk, with no bound stated, on those inputs against
    the float32 recurrence, output and final state, as the step runs it
    (bfloat16 operands) and once more with the inputs widened to float32 at the
    highest matmul precision. ``mixer`` and ``rule`` replace the program's (the
    tests' wrong programs)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import solar_lm as reference
    from edl_tpu.models import KimiDeltaMixer
    from edl_tpu.ops import kda_rule

    spec = kda_spec(config)
    inputs = {}
    if kda_params is None:
        args = drawn_rule_inputs(config, seed, config["train"]["seq_len"])
    else:
        if mixer is None:
            mixer = KimiDeltaMixer(spec, jnp.bfloat16, config["rms_norm_eps"]).apply

        @jax.jit
        def made(p, x):
            _, sown = mixer({"params": p}, x, mutable=["intermediates", "metrics"])
            return sown["intermediates"]["rule_inputs"][0]

        args = made(kda_params, x)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda p, x: reference.rule_inputs(config, p, x)[:5])(
                kda_params, x
            )
        inputs = {name: _rel(a, r) for name, a, r in zip(RULE_ARGS, args, want)}
        del want

    rule = rule or kda_rule
    run = jax.jit(lambda *a: rule(*a, chunk=spec.chunk, return_final_state=True))
    got_o, got_state = run(*args)
    wide = [a.astype(jnp.float32) for a in args]
    with jax.default_matmul_precision("highest"):
        exact_o, exact_state = run(*wide)
    # on the host, as ``gdn_lm.py``: the chip's float32 exp reads low by 1e-6
    # of its value near 1, which 8192 sequential steps compound
    host = jax.devices("cpu")[0]
    want_o, want_state = jax.jit(reference.recurrence)(*jax.device_put(wide, host))
    return {
        "shape": [list(a.shape) for a in args[:3]], "chunk": spec.chunk,
        "decay_mean": float(jnp.mean(jnp.exp(wide[3]))),
        "log_decay_min": float(jnp.min(wide[3])),
        "beta_max": float(jnp.max(wide[4])),
        "inputs_rel_err": max((e for name, e in inputs.items() if name != "g"), default=0.0),
        "decay_rel_err": inputs.get("g", 0.0), "inputs": inputs,
        "rel_err": _rel(got_o, want_o),
        "state_rms_err": _rms_rel(got_state, want_state),
        "exact_rel_err": _rel(exact_o, want_o),
        "exact_state_rms_err": _rms_rel(exact_state, want_state),
    }
