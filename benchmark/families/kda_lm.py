"""Family ``kda_lm``: the program's ``TransformerLM`` as one chip's share of a
Kimi-delta-attention / latent-attention hybrid with grouped expert routing
(inclusionAI's Ling-3.0-flash line): by ``layer_types`` a block's mixer is Kimi
delta attention (``models/gated_delta.py:KimiDeltaMixer`` over
``ops/gated_delta.py:kda_rule`` and ``ops/causal_conv.py``) or multi-head latent
attention (``models/transformer.py:LatentAttention``: keys of 192 and values of
128 through the grid-pipelined flash kernels); a norm before each branch; the
leading layers a dense SwiGLU, the others sigmoid-scored experts chosen inside
the best groups under a balancing bias, **the experts this chip holds**
(``models/moe.py:DroplessMoE(held=..., n_group=...)``) beside a shared one; an
untied head over a slice of the vocabulary. Built from a file that keeps the
published ``config.json`` keys.

See ``resnet_vd.py`` for what a family is. The token generator is
``transformer_lm.py``'s (uniform ids of the held slice); the routing comparison
is ``lfm2_lm.py``'s (the reference computes with the program's choice and each
choice is judged against the reference's own), with the groups in the margin.
"""

from __future__ import annotations

import numpy as np

from benchmark.families.afmoe_lm import (  # noqa: F401 — the family's interface
    BIAS_ABS_TOL,
    BIAS_MEAN_TOL,
    ROUTER_ARITHMETIC_REL_TOL,
)
from benchmark.families.moe_lm import (  # noqa: F401 — the family's interface
    GMM_REL_TOL,
    MOE_TRACE_KERNELS,
    grouped_matmul_vs_reference,
)
from benchmark.families.ssm_lm import _rel, _rms_rel
from benchmark.families.transformer_lm import (  # noqa: F401 — the family's interface
    KERNEL_REL_TOL,
    LOSS_REL_TOL,
    _items,
    host_batches,
)

# The attention kernels in a device trace: the latent layer puts its attention
# call under the scope ``attn_mla`` and XLA names the custom calls after it
# (``%attn_mla.N``: forward and the fused backward). Every string has to be in
# the operation's HLO instruction.
TRACE_KERNELS = ("%attn_mla", " custom-call(")
# Every limit below lies between two readings: the largest the program gave on
# the chip over this PR's seeds (TPU v5 lite, the cell's own traffic; PERF.md
# section 6, PR 45, has each run), and what the same program reads in the
# nearest precision below, ``float8_e4m3fn`` (``benchmark/tests/test_kda_lm.py``,
# the cell's six layers at a width of 256 on the CPU), about the geometric
# middle of the two. No 8-bit float holds the rule's decayed operands at all
# (e^+-40; ``float8_e4m3fn`` ends at 448), so an 8-bit program's logits are not
# finite, which fails the check by itself; the 8-bit readings here are of a
# program that hands its rule bfloat16 operands.
#
# Logits of the program (bfloat16 operands, float32 accumulation, float32
# logits) against the float32 reference computed with the program's own choice
# of experts, as max |difference| over max |reference| over every token: 0.033
# to 0.041 on the chip over sixteen seeds, 0.93 in 8 bits.
LOGITS_REL_TOL = 0.2
# The router's logits of the program against the reference's, layer by layer,
# as max |difference| over max |reference|: a float32 router whose input is a
# bfloat16 residual stream, five routers deep: 0.035 to 0.043 on the chip, 0.85
# in 8 bits.
ROUTER_LOGITS_REL_TOL = 0.18
# Tokens whose choice of experts may differ from the one the reference makes
# for itself on the same stream, in the expert layer where most do, by
# ``afmoe_lm.py``'s rule with the groups in it: a flip is right only where the
# reference's margin (the smaller of the 8th's lead over the 9th of ``s + b``
# inside the kept groups and half the 4th group's lead over the 5th) is at most
# twice the largest difference between the token's own program and reference
# scores; any other difference fails the check as ``tokens_misrouted``. 512
# sigmoid scores lie four times as densely as Trinity's 128 and a token has a
# group's edge to cross as well: 17.8 to 19.4% of the tokens flip in the first
# expert layer and 36.6 to 38.8% in the fifth on the chip, none misrouted (the
# widest flipped margin 0.020 of a score); in 8 bits 88 to 99%.
ROUTE_FLIP_LIMIT = 0.62
# What the program's first layer hands its rule (q, k, v, beta, from the
# trained parameters on the normed embedding) against the reference's float32
# forms of the same, each as max |difference| over max |reference|, the largest
# of the four. What differs is the rounding of the projections' operands and
# outputs and of the convolutions' results to bfloat16: 0.0052 to 0.0062 on
# the chip; an 8-bit mixer reads 0.06 to 0.10. A dropped SiLU or L2 norm is off
# by the whole value.
RULE_INPUTS_REL_TOL = 0.02
# The log-decay g of that layer, likewise. Its projection keeps a float32
# accumulator, but the weights' rounding to bfloat16 moves ``f`` by 1e-3, and
# where the safe gate is steepest ``exp(A_log)`` up to 16 times a slope of 5/4
# makes 0.1 of that, a fiftieth of the gate's range of 5: 0.015 to 0.025 on the
# chip. A gate without its lower bound reads 0.80 and one decay a head where the
# layer has one a channel 0.92 (``test_kda_lm.py``): the limit is three times
# the chip's largest and a tenth of either fault.
RULE_DECAY_REL_TOL = 0.08
# The chunked rule alone against the step-by-step recurrence (float32, on the
# host) at the step's own shape: on the inputs the program made, and on drawn
# inputs whose log-decays cover the safe gate's whole range (-5, 0) with runs
# at its lower bound (a fresh layer's decays lie near 0). The output as max
# |difference| over max |reference|, the final state as root-mean-square
# difference over root-mean-square reference. What differs is the rounding of
# the chunk's matmul operands to bfloat16, the decayed keys' among them:
# 0.0037 to 0.0078 and 0.0027 to 0.0033 on the chip. The scalar rule standing
# in (a head's mean decay for every channel) reads over 0.15 on the drawn inputs.
RULE_REL_TOL = 0.03
STATE_RMS_TOL = 0.02
# The rule once more on the same inputs widened to float32 at the highest
# matmul precision: what is left is the precision of the exponents (a sub-block's
# factors reach e^+-40), of the solve and of the carried state. On the chip
# 3e-6 to 1.9e-5 and 1.3e-6 to 5.4e-6; a state carried in bfloat16 reads over
# 1e-3 (``test_kda_lm.py``). With the sub-blocks' reference at their first step
# (factors to e^+-80) the chip read 2.7e-3 on the drawn inputs: at the highest
# precision the MXU takes a float32 operand as three bfloat16 pieces, and the
# lower two of 1e-36 fall under the smallest normal number.
EXACT_REL_TOL = 3e-4
EXACT_STATE_RMS_TOL = 3e-4


def layers(config, kind):
    return sum(k == kind for k in config["layer_types"])


def kda_spec(config):
    from edl_tpu.models import KimiDeltaSpec

    if not (config["kda_safe_gate"] and config["no_kda_lora"] and config["linear_silu"]):
        raise ValueError("kda_lm: the safe gate, full-rank gates and SiLU, as published")
    if config["group_norm_size"] != 1 or config["num_kv_heads_for_linear_attn"]:
        raise ValueError("kda_lm: a norm a head and as many key heads as heads")
    return KimiDeltaSpec(
        num_heads=config["num_attention_heads"], key_dim=config["head_dim"],
        value_dim=config["head_dim"], d_conv=config["short_conv_kernel_size"],
        chunk=config["train"]["rule_chunk"], lower_bound=float(config["kda_lower_bound"]),
    )


def latent_spec(config):
    from edl_tpu.models import LatentAttentionSpec

    if config["q_lora_rank"] is not None or config["use_mla_nope"]:
        raise ValueError("kda_lm: no query rank and a rotated part, as published")
    if config["rotary_dim"] != config["qk_rope_head_dim"]:
        raise ValueError("kda_lm: the rotation covers the rotated part")
    if config["gated_attention_proj_granularity_type"] != "head_wise":
        raise ValueError("kda_lm: a gate a head, as published")
    return LatentAttentionSpec(
        kv_lora_rank=config["kv_lora_rank"], qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"], v_head_dim=config["v_head_dim"],
        head_gate=True,
    )


def arch_spec(config):
    from edl_tpu.models import ArchSpec

    kinds = {"linear_attention": "kda", "full_attention": "latent_attention"}
    return ArchSpec(
        layer_types=tuple(kinds[kind] for kind in config["layer_types"]),
        kda=kda_spec(config), latent_attention=latent_spec(config),
        head_dim=config["head_dim"], rope_theta=float(config["rope_theta"]),
        dense_layers=config["first_k_dense_replace"],
    )


def moe_spec(config):
    from edl_tpu.models import MoESpec

    share = config["share"]
    if config["score_function"] != "sigmoid" or not config["moe_router_enable_expert_bias"]:
        raise ValueError("kda_lm: sigmoid scores under a bias, as published")
    return MoESpec(
        num_experts=share["router_experts"], top_k=config["num_experts_per_tok"],
        d_ff=config["moe_intermediate_size"], norm_topk_prob=config["norm_topk_prob"],
        aux_weight=0.0, z_weight=0.0, score_func="sigmoid",
        route_scale=config["routed_scaling_factor"],
        bias_rate=config["train"]["expert_bias_rate"],
        shared_d_ff=config["moe_shared_expert_intermediate_size"],
        held=(share["experts_first"], config["num_experts"]),
        n_group=config["n_group"], topk_group=config["topk_group"],
    )


def build(config, global_batch, seed):
    import jax.numpy as jnp
    import optax

    from edl_tpu.models import TransformerLM
    from edl_tpu.train import cross_entropy_loss

    train = config["train"]
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("kda_lm: layer_types does not list num_hidden_layers layers")
    if train["compute_dtype"] not in ("bfloat16", "float32"):
        raise ValueError("kda_lm: compute_dtype %r" % train["compute_dtype"])
    model = TransformerLM(
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
        raise ValueError("kda_lm: unknown optimizer %r" % opt["name"])

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
    """The matrices of one KDA layer: q, k, v, the decay's, the gate's and the
    out projection (hidden x H d each) and beta's (hidden x H)."""
    d, h, hd = config["hidden_size"], config["num_attention_heads"], config["head_dim"]
    return 6 * d * h * hd + d * h


def mla_mixer_params(config):
    """The matrices of the latent layer: q, the latent with the shared rotated
    key, its up projection to keys and values, the out projection, the gate."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rot, dv = (
        config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    )
    rank = config["kv_lora_rank"]
    return (
        d * h * (nope + rot) + d * (rank + rot) + rank * h * (nope + dv)
        + h * dv * d + d * h
    )


def routed_experts_a_token(config):
    """Expert matmuls a token meets HERE, expected under balanced routing: its
    ``num_experts_per_tok`` choices fall on the held ``num_experts`` of the
    ``router_experts`` with that share (8 x 8 / 512 = 1/8)."""
    return (
        config["num_experts_per_tok"] * config["num_experts"]
        / config["share"]["router_experts"]
    )


def matmul_params(config):
    """Parameters that multiply every token on this chip: each layer's mixer,
    the dense layers' SwiGLU, in an expert layer the router (at its whole
    width), the shared expert and the expected ``routed_experts_a_token``
    routed ones, and the head over the slice."""
    d, fe = config["hidden_size"], config["moe_intermediate_size"]
    dense = config["first_k_dense_replace"]
    expert_layer = (
        d * config["share"]["router_experts"]
        + 3 * d * config["moe_shared_expert_intermediate_size"]
        + routed_experts_a_token(config) * 3 * d * fe
    )
    return (
        layers(config, "linear_attention") * kda_mixer_params(config)
        + layers(config, "full_attention") * mla_mixer_params(config)
        + dense * 3 * d * config["intermediate_size"]
        + (config["num_hidden_layers"] - dense) * expert_layer
        + d * config["vocab_size"]
    )


def rule_forward_flops_per_token(config):
    """The chunked rule's products for one token of one layer, forward, a
    multiply-add as 2, at the source's chunk of 64 (``gdn_lm``'s count: the
    decay a channel changes no product's shape): in a chunk ``K K^T`` and ``Q
    K^T`` with the decay inside the contraction (half of each masked away: C
    d_k each), ``W`` and ``U`` through a triangular ``T`` (C d_k and C d_v),
    the scores times ``V_new`` (C d_v); against the state ``W S``, ``Q S`` and
    ``K^T V_new`` (2 d_k d_v each); the solve as forward substitution would do
    it (C^2 / 3 a token). The exponentials, the running sums and the norms are
    elementwise and count for nothing; what the sub-blocks spend on columns the
    mask drops and the doubling on blocks of zeros is not needed work."""
    chunk, d = 64, config["head_dim"]
    head = chunk * 5 * d + 6 * d * d + chunk * chunk / 3.0
    return head * config["num_attention_heads"]


def attention_forward_flops(config, sequences):
    """The latent layers' causal attention forward over ``sequences``
    sequences: T^2 / 2 visible pairs a head, 2 (nope + rope) operations a pair
    for the scores and 2 v_head_dim for the values."""
    t = config["train"]["seq_len"]
    widths = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"]
    return (
        2.0 * sequences * config["num_attention_heads"] * (t * t / 2.0) * widths
        * layers(config, "full_attention")
    )


def flops_per_item(config):
    """As ``transformer_lm.flops_per_item``: 6 per matrix-multiplied parameter
    a token meets (the routed experts at their expected share), three times the
    latent layers' attention forward, three times the chunked rule's forward of
    the KDA layers. Recomputation under remat, the convolutions, norms, gates,
    the rotation, the softmax, the sort and the optimizer are not counted."""
    t = config["train"]["seq_len"]
    return (
        6.0 * matmul_params(config)
        + 3.0 * attention_forward_flops(config, 1) / t
        + 3.0 * rule_forward_flops_per_token(config) * layers(config, "linear_attention")
    )


def kernel_flops(config, sequences):
    """What the latent layers' flash kernels execute: the forward's two
    products and the fused backward's five (the scores, ``dP``, ``dV``, ``dK``,
    ``dQ``), over visible pairs: 2 (4 d_qk + 3 d_v) a pair."""
    t = config["train"]["seq_len"]
    d_qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    pairs = sequences * config["num_attention_heads"] * t * t / 2.0
    return (
        2.0 * pairs * (4 * d_qk + 3 * config["v_head_dim"])
        * layers(config, "full_attention")
    )


def kda_scan_flops(config, tokens):
    """What the rules have to compute for ``tokens`` tokens, all KDA layers,
    forward and backward (the backward of a matmul is two). What remat
    computes a second time is not needed work."""
    return (
        3.0 * rule_forward_flops_per_token(config) * tokens
        * layers(config, "linear_attention")
    )


def kda_scan_bytes(config, tokens):
    """The least HBM traffic of that work: the forward reads q, k, v
    (bfloat16), g (float32, a value a key channel) and beta (float32) and
    writes o; the backward reads them and ``do`` and writes the five
    gradients. Nothing between has to touch HBM."""
    h, d = config["num_attention_heads"], config["head_dim"]
    inputs = 2 * 3 * h * d + 4 * h * d + 4 * h
    forward = inputs + 2 * h * d
    backward = inputs + 2 * h * d + inputs
    return float(forward + backward) * tokens * layers(config, "linear_attention")


def moe_kernel_flops(config, tokens):
    """What the grouped matmuls have to compute for ``tokens`` tokens, all
    expert layers: gate, up and down over the rows that fall on held experts
    (``routed_experts_a_token`` a token, expected), forward and both gradients."""
    rows = tokens * routed_experts_a_token(config)
    expert_layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    return (
        6.0 * 3 * rows * config["hidden_size"] * config["moe_intermediate_size"]
        * expert_layers
    )


def moe_kernel_bytes(config, tokens):
    """The least HBM traffic of that work (``moe_lm.moe_kernel_bytes`` over the
    held rows and the held banks)."""
    rows = tokens * routed_experts_a_token(config)
    d, f, e = config["hidden_size"], config["moe_intermediate_size"], config["num_experts"]
    expert_layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    return 9.0 * (rows * d * 2 + rows * f * 2 + e * d * f * 2) * expert_layers


def check(config, state, seed):
    """On one seeded sequence, with the trained parameters and the trained
    bias: logits and the cross-entropy against the plain reference computed
    with the program's choice of experts (``lfm2_lm.py``'s form: a rule and an
    attention layer carry a token's other expert into its neighbours'
    streams); the router's logits, scores and choices layer by layer and token
    by token, the groups in the margin; the bias the program leaves behind
    against the reference's rule on the program's counts; what the first
    layer hands its rule, and the chunked rule alone against the step-by-step
    recurrence on exactly that and on drawn inputs over the gate's whole range;
    then the two-width flash kernels against dense float32 attention and the
    grouped matmul at the held rows' shape."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import kda_lm as reference
    from edl_tpu.train import cross_entropy_loss

    n = config["check"]["sample_items"]
    t = _items(config, seed + 7, n)
    # run.py hands over plain arrays on one device: no second copy of 9 GB
    params, stats, apply_fn = state.params, state.batch_stats, state.apply_fn
    del state
    tokens, targets = t[:, :-1], t[:, 1:]
    expert_layers = range(config["first_k_dense_replace"], config["num_hidden_layers"])

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
            "groups_live": jnp.stack([p["moe_groups_live"][0] for p in sown]),
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
    groups_live = [float(v) for v in routed["groups_live"]]
    # the program's gauge against a count of the groups its own choice touches
    group_of = np.asarray(routed["experts"]) // (
        config["share"]["router_experts"] // config["n_group"]
    )
    touched = (group_of[..., None] == np.arange(config["n_group"])).any(axis=2)
    groups_live_err = float(np.max(np.abs(
        np.asarray(routed["groups_live"]) - touched.sum(axis=-1).mean(axis=-1)
    )))
    del got_logits, want_logits, stats, info, routed
    loss_rel = abs(float(got_ce) - float(want_ce)) / abs(float(want_ce))

    if config["layer_types"][0] != "linear_attention":
        raise ValueError("kda_lm: the rule's check reads layer 0's input, the normed embedding")
    x = jnp.asarray(params["embed"]["embedding"])[tokens[:1]].astype(jnp.bfloat16)
    scale = jnp.asarray(params["layer_0"]["ln1"]["scale"])
    x32 = x.astype(jnp.float32)
    x = (x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + config["rms_norm_eps"]
    ) * scale).astype(jnp.bfloat16)
    made = rule_vs_reference(config, params["layer_0"]["kda"], x)
    del params, x, x32
    drawn = rule_vs_reference(config, None, None, seed=seed)
    b, steps = config["train"]["batch_per_chip"], config["train"]["seq_len"]
    kernel = mla_kernel_vs_reference(
        seed, b, config["num_attention_heads"], steps,
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"], config["v_head_dim"],
    )
    held_rows = dict(
        config, num_experts_per_tok=1, intermediate_size=config["moe_intermediate_size"]
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
        and rows_dropped == 0 and groups_live_err <= 1e-5
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
        "groups_live": groups_live, "groups_live_abs_err": groups_live_err,
        "sample_items": n, "rule": made, "rule_drawn": drawn,
        "rule_inputs_rel_tol": RULE_INPUTS_REL_TOL,
        "rule_decay_rel_tol": RULE_DECAY_REL_TOL, "rule_rel_tol": RULE_REL_TOL,
        "state_rms_tol": STATE_RMS_TOL, "exact_rel_tol": EXACT_REL_TOL,
        "exact_state_rms_tol": EXACT_STATE_RMS_TOL,
        "kernel": kernel, "kernel_rel_tol": KERNEL_REL_TOL,
        "grouped_matmul": gmm, "grouped_matmul_rel_tol": GMM_REL_TOL,
    }


RULE_ARGS = ("q", "k", "v", "g", "beta")
DRAWN_HEADS = 8  # heads of the drawn run: the host walks every step of each


def drawn_rule_inputs(config, seed, t, heads=DRAWN_HEADS):
    """Seeded inputs of the rule at the layer's widths whose log-decays cover
    the safe gate's range: a channel's ``g`` is ``lower_bound * sigmoid(z)``
    with ``z`` spread over (-6, 6) by channel plus noise a step, and every
    fourth head spends steps ``t/4 .. t/4 + 32`` at the bound itself (two whole
    sub-blocks: the exponents' extreme)."""
    import jax
    import jax.numpy as jnp

    d, low = config["head_dim"], float(config["kda_lower_bound"])
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 5)
    unit = lambda m: m / jnp.sqrt(jnp.sum(m * m, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (1, t, heads, d))) * d ** -0.5
    k = unit(jax.random.normal(keys[1], (1, t, heads, d)))
    v = jax.random.normal(keys[2], (1, t, heads, d))
    z = jnp.linspace(-6.0, 6.0, d) + jax.random.normal(keys[3], (1, t, heads, d))
    g = low * jax.nn.sigmoid(z)
    at_bound = (jnp.arange(t) >= t // 4) & (jnp.arange(t) < t // 4 + 32)
    g = jnp.where(
        at_bound[None, :, None, None] & (jnp.arange(heads) % 4 == 0)[None, None, :, None],
        low * (1 - 1e-6), g,
    )
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, t, heads)))
    bf16 = lambda a: a.astype(jnp.bfloat16)  # noqa: E731
    return bf16(q), bf16(k), bf16(v), g, beta


def rule_vs_reference(config, kda_params, x, mixer=None, rule=None, seed=None):
    """The rule's inputs as the program's ``KimiDeltaMixer`` makes them from
    the parameters ``kda_params`` of ``layer_i/kda`` on the block's normed
    input ``x`` ``[1, T, hidden]`` (bfloat16) against ``reference.rule_inputs``
    (or, with ``seed`` and no parameters, ``drawn_rule_inputs``); then
    ``kda_rule`` at the configuration's chunk on those inputs against the
    float32 recurrence, output and final state, as the step runs it (bfloat16
    operands) and once more with the inputs widened to float32 at the highest
    matmul precision. ``mixer`` and ``rule`` replace the program's (the tests'
    wrong programs)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import kda_lm as reference
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
        "inputs_rel_err": max((e for name, e in inputs.items() if name != "g"), default=0.0),
        "decay_rel_err": inputs.get("g", 0.0), "inputs": inputs,
        "rel_err": _rel(got_o, want_o),
        "state_rms_err": _rms_rel(got_state, want_state),
        "exact_rel_err": _rel(exact_o, want_o),
        "exact_state_rms_err": _rms_rel(exact_state, want_state),
    }


def mla_kernel_vs_reference(seed, b, h, t, d_qk, d_v, attn=None):
    """``ops.attention.attention`` as the latent layer calls it (value and
    q/k/v gradients, causal, keys of ``d_qk`` and values of ``d_v``, bfloat16,
    scores times ``d_qk^-1/2``) against the reference's dense float32 attention
    on the same inputs, a few heads at a time."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.kda_lm import HEADS_AT_ONCE, dense_causal_attention
    from edl_tpu.ops import attention

    attn = attn or attention
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 4)
    q = jax.random.normal(keys[0], (b, h, t, d_qk), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, h, t, d_qk), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, h, t, d_v), jnp.bfloat16)
    w = jax.random.normal(keys[3], (b, h, t, d_v), jnp.bfloat16)  # cotangent
    scale = d_qk ** -0.5

    def value_and_grads(fn):
        def run(q, k, v, w):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out, *vjp(w.astype(out.dtype)))
        return jax.jit(run)

    got = value_and_grads(lambda q, k, v: attn(q, k, v, causal=True, scale=scale))(q, k, v, w)
    ref_fn = value_and_grads(lambda q, k, v: dense_causal_attention(q, k, v, scale))
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    parts = []
    with jax.default_matmul_precision("highest"):
        for first in range(0, h, HEADS_AT_ONCE):
            heads = slice(first, first + HEADS_AT_ONCE)
            parts.append(ref_fn(*(f32(m[:, heads]) for m in (q, k, v, w))))
    want = [jnp.concatenate(part, axis=1) for part in zip(*parts)]
    errs = {name: _rel(a, r) for name, a, r in zip(("out", "dq", "dk", "dv"), got, want)}
    return {"shape": [b, h, t, d_qk, d_v], "max_rel_err": max(errs.values()), **errs}
