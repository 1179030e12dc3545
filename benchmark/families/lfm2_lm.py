"""Family ``lfm2_lm``: the program's ``TransformerLM`` as one chip's share of an
``lfm2_moe`` decoder (Liquid AI's LFM2 line) — by ``layer_types`` a block's
mixer is a gated short convolution (``models/short_conv.py`` over
``ops/causal_conv.py:gated_causal_conv``) or full causal attention over
per-head QK norms and rotary positions at the configuration's own base; a norm
before each branch; leading dense SwiGLU layers, then expert layers: sigmoid
scores over all the model's experts, a balancing bias that a step moves without
a gradient, top-k weights normalised over their sum plus 1e-6, **the experts
this chip holds** (``models/moe.py:DroplessMoE(held=...)`` over
``ops/grouped_matmul.py``) and no shared expert; a tied head over a slice of
the vocabulary — built from a file that keeps the published ``config.json``
keys.

See ``resnet_vd.py`` for what a family is. The token generator is
``transformer_lm.py``'s (uniform ids of the held slice); the routing comparison
and the flash kernels' follow ``afmoe_lm.py``'s, whose limits this family shares
where the reason is the same.
"""

from __future__ import annotations

import numpy as np

from benchmark.families.afmoe_lm import (  # noqa: F401 — the family's interface
    BIAS_ABS_TOL,
    BIAS_MEAN_TOL,
    ROUTER_ARITHMETIC_REL_TOL,
    kernel_vs_reference,
)
from benchmark.families.moe_lm import (  # noqa: F401 — the family's interface
    GMM_REL_TOL,
    MOE_TRACE_KERNELS,
    grouped_matmul_vs_reference,
)
from benchmark.families.ssm_lm import _rel
from benchmark.families.transformer_lm import (  # noqa: F401 — the family's interface
    KERNEL_REL_TOL,
    LOSS_REL_TOL,
    TRACE_KERNELS,
    _items,
    host_batches,
)

# Logits of the program (bfloat16 operands, float32 accumulation, float32
# logits) against the float32 reference computed with the program's own choice
# of experts (``reference.mixture``), as max |difference| over max |reference|
# over every token. Nine pre-norm layers deep where ``transformer_lm.py``'s
# 0.03 serves two: every branch adds its 2^-9-a-value rounding to a residual
# stream that the next norm rescales. Measured 0.025..0.031 over fifteen seeds
# on the chip (my chip runs, PR 37). The same program in float8_e4m3fn (2^-4 a
# value, the nearest precision below) reads 0.55, and bfloat16 0.033..0.038 as
# on the chip, at a width of 256 with the cell's nine layers and 8 of 64
# experts top-4 (sandbox, ``benchmark/tests/test_lfm2_lm.py``).
LOGITS_REL_TOL = 0.08
# The router's logits of the program against the reference's, layer by layer,
# as max |difference| over max |reference|: a float32 router whose input is a
# bfloat16 residual stream, here eight routers deep where Trinity's limit of
# 0.03 serves four. Measured 0.022..0.029 over fifteen seeds on the chip (my
# chip runs, PR 37); the 8-bit program of the test above reads 0.48, its
# bfloat16 0.029..0.034.
ROUTER_LOGITS_REL_TOL = 0.08
# Tokens whose choice of experts may differ from the one the reference makes
# for itself on the same stream, in the expert layer where most do (the last:
# the stream's rounding grows with depth), by ``afmoe_lm.py``'s rule: a flip is
# right only where the reference's 4th of ``s + b`` stands above its 5th by at
# most twice the largest difference between the token's own program and
# reference scores; any other difference fails the check as
# ``tokens_misrouted``. 64 sigmoid scores of a fresh router lie densely:
# measured 10.2..11.8% of the tokens in the last layer, 3.4..4.1% in the first,
# 46..47% in some layer of the eight and 13..14% on a held expert in some layer
# (fifteen seeds on the chip, PR 37). The 8-bit program of the test above flips
# 90% in its last layer and 53% in its first, its bfloat16 10..12% and
# 3.5..3.8%.
ROUTE_FLIP_LIMIT = 0.25
# The gated convolution at the step's own shape (``[B, T, 3 x hidden]``
# bfloat16 in, ``[B, T, hidden]`` out) against the reference's shifted products
# in float32 on the same inputs: the value and the gradients of the three
# thirds, each as max |difference| over max |reference|. The stated arithmetic
# is float32 with ONE rounding of each result to bfloat16: half a unit in the
# last of 8 bits, at most 2^-8 = 0.0039 of the value itself, so of the largest
# (measured 0.0021..0.0035 over seventeen seeds on the chip, PR 37). The same
# function on bfloat16 gates, taps and sums (the precision below) rounds five
# times on the way: 0.0094 at this shape, 0.0070 to 0.0083 at [2048, 256]
# (sandbox, ``benchmark/tests/test_lfm2_lm.py``).
CONV_REL_TOL = 0.005
# The taps' gradient of that comparison, a float32 sum of B x T products a
# channel a tap: 2.4e-7..3.8e-7 on the chip (PR 37). With bfloat16 products
# under the sum the 2^-9 a term does not average out of a random walk's end:
# 0.012..0.017 (sandbox, same test).
CONV_TAPS_REL_TOL = 1e-4
# The program's rotation (``models/transformer.py:rope`` at the configuration's
# base, positions 0 .. T - 1, bfloat16 in and out) against the rotation written
# out in float64 on the host, as max |difference| over max |reference| over the
# LAST 128 positions (up to 8191, where an angle is largest and float32 has the
# fewest bits left of it: 8191 radians to 2^-11). What differs is that float32
# angle (5e-4 radians) and the one rounding of the result (2^-9): measured
# 0.0027..0.0036 on the chip (seventeen seeds, PR 37). A base of 10,000 where
# the configuration says 1,000,000 turns every pair but the first by another
# angle: 1.4..1.6.
ROPE_REL_TOL = 0.01
ROPE_LAST_POSITIONS = 128


def head_dim(config):
    return config["hidden_size"] // config["num_attention_heads"]


def layers(config, kind):
    return sum(k == kind for k in config["layer_types"])


def arch_spec(config):
    from edl_tpu.models import ArchSpec, ShortConvSpec

    kinds = {"conv": "conv", "full_attention": "attention"}
    rope = config["rope_parameters"]
    if rope["rope_type"] != "default" or config["conv_bias"]:
        raise ValueError("lfm2_lm: unscaled rotary positions and no tap bias, as published")
    return ArchSpec(
        layer_types=tuple(kinds[kind] for kind in config["layer_types"]),
        short_conv=ShortConvSpec(taps=config["conv_L_cache"]),
        rope_theta=float(rope["rope_theta"]), tie_embeddings=True,
        dense_layers=config["num_dense_layers"],
    )


def moe_spec(config):
    from benchmark.reference.lfm2_lm import RENORM_EPS
    from edl_tpu.models import MoESpec

    share = config["share"]
    if not config["use_expert_bias"]:
        raise ValueError("lfm2_lm: a balancing bias under the choice, as published")
    return MoESpec(
        num_experts=share["router_experts"], top_k=config["num_experts_per_tok"],
        d_ff=config["moe_intermediate_size"], norm_topk_prob=config["norm_topk_prob"],
        norm_topk_eps=RENORM_EPS, aux_weight=0.0, z_weight=0.0, score_func="sigmoid",
        route_scale=float(config["routed_scaling_factor"]),
        bias_rate=config["train"]["expert_bias_rate"],
        held=(share["experts_first"], config["num_experts"]),
    )


def build(config, global_batch, seed):
    import jax.numpy as jnp
    import optax

    from edl_tpu.models import TransformerLM
    from edl_tpu.train import cross_entropy_loss

    train = config["train"]
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("lfm2_lm: layer_types does not list num_hidden_layers layers")
    if train["compute_dtype"] not in ("bfloat16", "float32"):
        raise ValueError("lfm2_lm: compute_dtype %r" % train["compute_dtype"])
    model = TransformerLM(
        dtype=getattr(jnp, train["compute_dtype"]),
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        num_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"], remat=train["remat"],
        remat_policy=train["remat_policy"], norm_eps=config["norm_eps"],
        qk_norm="head", moe=moe_spec(config), arch=arch_spec(config),
    )
    opt = train["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError("lfm2_lm: unknown optimizer %r" % opt["name"])

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


def conv_mixer_params(config):
    """The in projection ``[B_g | C_g | x~]`` and the out projection (the
    ``conv_L_cache x hidden`` taps multiply elementwise and count for nothing)."""
    d = config["hidden_size"]
    return d * 3 * d + d * d


def attention_params(config):
    d, hd = config["hidden_size"], head_dim(config)
    return 2 * d * config["num_attention_heads"] * hd + (
        2 * d * config["num_key_value_heads"] * hd
    )


def routed_experts_a_token(config):
    """Expert matmuls a token meets HERE, expected under balanced routing: its
    ``num_experts_per_tok`` choices fall on the held ``num_experts`` of the
    ``router_experts`` with that share (4 x 8 / 64 = 0.5)."""
    return (
        config["num_experts_per_tok"] * config["num_experts"]
        / config["share"]["router_experts"]
    )


def matmul_params(config):
    """Parameters that multiply every token on this chip: a conv layer's two
    projections, an attention layer's four, the dense layers' SwiGLU, in an
    expert layer the router (at its whole width) and the expected
    ``routed_experts_a_token`` routed experts, and the tied head over the slice
    (as a lookup the embedding counts for nothing)."""
    d, fe = config["hidden_size"], config["moe_intermediate_size"]
    dense = config["num_dense_layers"]
    sparse = config["num_hidden_layers"] - dense
    expert_layer = (
        d * config["share"]["router_experts"]
        + routed_experts_a_token(config) * 3 * d * fe
    )
    return (
        layers(config, "conv") * conv_mixer_params(config)
        + layers(config, "full_attention") * attention_params(config)
        + dense * 3 * d * config["intermediate_size"]
        + sparse * expert_layer + d * config["vocab_size"]
    )


def attention_forward_flops(config, sequences):
    """Causal attention's forward over ``sequences`` sequences, the attention
    layers only: two matmuls of 2*T*T*D per head, half of each masked."""
    t = config["train"]["seq_len"]
    return (
        2.0 * sequences * config["num_attention_heads"] * t * t
        * head_dim(config) * layers(config, "full_attention")
    )


def flops_per_item(config):
    """As ``transformer_lm.flops_per_item``: 6 per matrix-multiplied parameter
    a token meets and three times the causal attention forward. The routed
    experts count at their expected ``routed_experts_a_token``. Recomputation
    under remat, the taps and gates of the convolution, the sort, the gathers,
    norms, RoPE, the softmaxes and the optimizer are not counted."""
    t = config["train"]["seq_len"]
    return 6.0 * matmul_params(config) + 3.0 * attention_forward_flops(config, 1) / t


def kernel_flops(config, sequences):
    """What the flash kernels execute (``transformer_lm.kernel_flops``), in the
    attention layers."""
    return 3.5 * attention_forward_flops(config, sequences)


def sconv_conv_flops(config, tokens):
    """What the gated convolutions have to compute for ``tokens`` tokens, all
    conv layers: forward a channel a token the two gates' products, ``L`` tap
    products and ``L - 1`` sums (``2 L + 1``); backward twice that."""
    taps = config["conv_L_cache"]
    return (
        3.0 * (2 * taps + 1) * tokens * config["hidden_size"] * layers(config, "conv")
    )


def sconv_conv_bytes(config, tokens):
    """The least HBM traffic of that work, in the compute dtype (bfloat16, 2
    bytes): forward three reads (``B_g``, ``C_g``, ``x~``) and one write of
    ``[tokens, hidden]``; backward four reads (those and ``dy``) and three
    writes (the three gradients). The taps and their gradient are ``L x
    hidden`` and count for nothing. What remat reads a second time is not
    needed traffic."""
    return 11.0 * tokens * config["hidden_size"] * 2 * layers(config, "conv")


def moe_kernel_flops(config, tokens):
    """What the grouped matmuls have to compute for ``tokens`` tokens, all
    expert layers: gate, up and down over the rows that fall on held experts
    (``routed_experts_a_token`` a token, expected), forward and both gradients.
    What remat computes a second time does not count."""
    rows = tokens * routed_experts_a_token(config)
    sparse = config["num_hidden_layers"] - config["num_dense_layers"]
    return 6.0 * 3 * rows * config["hidden_size"] * config["moe_intermediate_size"] * sparse


def moe_kernel_bytes(config, tokens):
    """The least HBM traffic of that work (``moe_lm.moe_kernel_bytes`` over the
    held rows and the held banks)."""
    rows = tokens * routed_experts_a_token(config)
    d, f, e = config["hidden_size"], config["moe_intermediate_size"], config["num_experts"]
    sparse = config["num_hidden_layers"] - config["num_dense_layers"]
    return 9.0 * (rows * d * 2 + rows * f * 2 + e * d * f * 2) * sparse


def check(config, state, seed):
    """On one seeded sequence, with the trained parameters and the trained
    bias: logits and the cross-entropy against the plain reference, which
    computes each expert layer with the program's choice of experts (a
    convolution and an attention layer carry one token's other expert into its
    neighbours' streams, so leaving the flipped tokens out, as ``afmoe_lm.py``
    does, leaves none: the first form read 0.21..0.24 on the tokens no flip
    had touched; my chip runs, PR 37) and makes its own choice beside it; the
    router's logits, scores and choices layer by layer and token by token (a
    flip only where the reference is nearer a tie than the scores differ); the
    bias the program leaves behind against the reference's rule on the
    program's counts; then, each at the step's own shape, the gated convolution
    and its gradients against the reference's shifted products, the rotation at
    the configuration's base against the rotation written out, the flash
    kernels against dense float32 attention and the grouped matmul at the held
    rows' shape and the experts' width."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import lfm2_lm as reference
    from edl_tpu.train import cross_entropy_loss

    n = config["check"]["sample_items"]
    t = _items(config, seed + 7, n)
    # run.py hands over plain arrays on one device: no second copy of 3.3 GB
    params, stats, apply_fn = state.params, state.batch_stats, state.apply_fn
    del state
    tokens, targets = t[:, :-1], t[:, 1:]
    expert_layers = range(config["num_dense_layers"], config["num_hidden_layers"])

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
        # computed with the PROGRAM's choice of experts, each judged below
        # against the reference's own: see ``reference.mixture``
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
    # routing, layer by layer and token by token ([L, N]): the program's choice
    # against the one the reference makes for itself on the same stream (both
    # sides have computed every earlier layer with the program's experts, so
    # no token's stream has gone another way)
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
    # a flip is right only where the reference's k-th of ``s + b`` stands above
    # its (k+1)-th by at most twice the largest difference between the token's
    # own program and reference scores: any other is a wrong top-k
    misrouted = int(jnp.sum(differs & (info["margin"] > 2.0 * moved)))
    # a flip that brings in or takes out a HELD expert changes what this chip
    # computes for the token: counted apart
    first = config["share"]["experts_first"]

    def held_only(experts):
        here = (experts >= first) & (experts < first + config["num_experts"])
        return jnp.sort(jnp.where(here, experts, -1), axis=-1)

    differs_here = jnp.any(
        held_only(routed["experts"]) != held_only(info["experts"]), axis=-1
    )
    flips_a_layer = [float(v) for v in jnp.mean(differs, axis=-1)]
    flip_share = max(flips_a_layer)  # judged: the layer where most tokens flip
    flip_share_any = float(jnp.mean(jnp.any(differs, axis=0)))
    flip_share_here = float(jnp.mean(jnp.any(differs_here, axis=0)))
    flips_here_a_layer = [float(v) for v in jnp.mean(differs_here, axis=-1)]
    widest_flip = float(jnp.max(jnp.where(differs, info["margin"], 0.0)))
    # every token's logits: no flip sent the reference another way
    rel = float(jnp.max(jnp.abs(got_logits - want_logits)) / jnp.max(jnp.abs(want_logits)))
    finite = bool(jnp.isfinite(got_logits).all())
    rows_held = [float(v) for v in routed["rows_held"]]
    rows_dropped = float(jnp.sum(routed["rows_dropped"]))
    del got_logits, want_logits, params, stats, info, routed
    loss_rel = abs(float(got_ce) - float(want_ce)) / abs(float(want_ce))

    b, steps = config["train"]["batch_per_chip"], config["train"]["seq_len"]
    conv = gated_conv_vs_reference(
        seed, b, steps, config["hidden_size"], config["conv_L_cache"]
    )
    rotation = rope_vs_reference(
        seed, steps, config["num_key_value_heads"], head_dim(config),
        float(config["rope_parameters"]["rope_theta"]),
    )
    kernel = kernel_vs_reference(
        seed, b, config["num_attention_heads"], config["num_key_value_heads"],
        steps, head_dim(config), None,
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
        and rows_dropped == 0
        and conv["max_rel_err"] <= CONV_REL_TOL
        and conv["d_taps"] <= CONV_TAPS_REL_TOL
        and rotation["rel_err"] <= ROPE_REL_TOL
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
        "flipped_share_in_some_layer": flip_share_any,
        "flipped_share_on_a_held_expert": flip_share_here,
        "flipped_share_by_layer": flips_a_layer,
        "flipped_share_on_a_held_expert_by_layer": flips_here_a_layer,
        "widest_flipped_margin": widest_flip, "tokens_misrouted": misrouted,
        "bias_abs_err": bias_err, "bias_abs_tol": BIAS_ABS_TOL,
        "bias_mean": bias_mean, "bias_mean_tol": BIAS_MEAN_TOL,
        "bias_abs_max": float(jnp.max(jnp.abs(bias))),
        "rows_held": rows_held, "rows_dropped": rows_dropped,
        "sample_items": n,
        "gated_conv": conv, "conv_rel_tol": CONV_REL_TOL,
        "conv_taps_rel_tol": CONV_TAPS_REL_TOL,
        "rotation": rotation, "rope_rel_tol": ROPE_REL_TOL,
        "kernel": kernel, "kernel_rel_tol": KERNEL_REL_TOL,
        "grouped_matmul": gmm, "grouped_matmul_rel_tol": GMM_REL_TOL,
    }


def gated_conv_vs_reference(seed, b, t, c, taps, conv=None):
    """``ops.gated_causal_conv`` as the mixer calls it (``[b, t, 3 c]``
    bfloat16 in, value and the gradients of the input and of the taps) against
    the reference's shifted products in float32 on the same inputs. ``conv``
    replaces the program's (the tests' wrong programs)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.lfm2_lm import gated_conv
    from edl_tpu.ops import gated_causal_conv

    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 3)
    x = jax.random.normal(keys[0], (b, t, 3 * c), jnp.bfloat16)
    bound = taps ** -0.5
    w = jax.random.uniform(keys[1], (taps, c), jnp.float32, -bound, bound)
    dy = jax.random.normal(keys[2], (b, t, c), jnp.bfloat16)  # cotangent

    @jax.jit
    def got(x, w, dy):
        out, vjp = jax.vjp(conv or gated_causal_conv, x, w)
        return (out, *vjp(dy))

    @jax.jit
    def want(x, w, dy):
        out, vjp = jax.vjp(
            lambda x, w: gated_conv(*jnp.split(x, 3, axis=-1), w), x, w
        )
        return (out, *vjp(dy))

    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    out, dx, dw = got(x, w, dy)
    ref_out, ref_dx, ref_dw = want(f32(x), w, f32(dy))
    errs = {"value": _rel(out, ref_out), "d_taps": _rel(dw, ref_dw)}
    for i, name in enumerate(("d_b_gate", "d_c_gate", "d_inner")):
        errs[name] = _rel(dx[..., i * c:(i + 1) * c], ref_dx[..., i * c:(i + 1) * c])
    return {
        "shape": [b, t, 3 * c], "taps": taps,
        "max_rel_err": max(v for k, v in errs.items() if k != "d_taps"), **errs,
    }


def rope_vs_reference(seed, t, heads, d, theta, base=None):
    """The program's rotation of a seeded ``[1, t, heads, d]`` (bfloat16) at
    base ``base`` (the configuration's ``theta`` unless a test gives another)
    against the rotation at ``theta`` written out in float64 on the host, over
    the last ``ROPE_LAST_POSITIONS`` positions."""
    import jax
    import jax.numpy as jnp

    from edl_tpu.models.transformer import rope

    x = jax.random.normal(jax.random.PRNGKey(seed % (2 ** 31)), (1, t, heads, d), jnp.bfloat16)
    positions = jnp.arange(t)[None, :]
    got = jax.jit(lambda x: rope(x, positions, theta if base is None else base))(x)
    last = min(ROPE_LAST_POSITIONS, t)
    x64 = np.asarray(x[:, t - last:], np.float64)
    half = d // 2
    angle = (
        np.arange(t - last, t, dtype=np.float64)[:, None]
        * float(theta) ** (-2.0 * np.arange(half, dtype=np.float64) / d)
    )[None, :, None, :]
    first, second = x64[..., :half], x64[..., half:]
    want = np.concatenate(
        [first * np.cos(angle) - second * np.sin(angle),
         second * np.cos(angle) + first * np.sin(angle)], axis=-1,
    )
    return {"theta": theta, "positions": [t - last, t - 1],
            "rel_err": _rel(got[:, t - last:], want)}
