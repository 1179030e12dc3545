"""Family ``afmoe_lm``: the program's ``TransformerLM`` as one chip's share of an
``afmoe`` decoder (Arcee's Trinity line) — layers of two kinds of attention
(a window with rotary positions, the whole sequence with none) over per-head
QK norms and a sigmoid gate on the heads' outputs, a norm before and after each
branch, leading dense SwiGLU layers, then expert layers: sigmoid scores over
all the model's experts, a balancing bias that a step moves without a
gradient, top-k weights normalised and scaled, **the experts this chip holds**
(``models/moe.py:DroplessMoE(held=...)`` over ``ops/grouped_matmul.py``) beside
a shared expert, an embedding times sqrt(hidden_size) and a slice of the
vocabulary — built from a file that keeps the published ``config.json`` keys.

See ``resnet_vd.py`` for what a family is. The token generator (uniform ids
from the slice, two batches a run, as every LM cell: PERF.md section 6 has what
other pools do to the routing) is ``transformer_lm.py``'s; the routing comparison follows
``moe_lm.py``'s, whose limits it shares where the reason is the same.
"""

from __future__ import annotations

import numpy as np

from benchmark.families.moe_lm import (  # noqa: F401 — the family's interface
    GMM_REL_TOL,
    MOE_TRACE_KERNELS,
    grouped_matmul_vs_reference,
)
from benchmark.families.transformer_lm import (  # noqa: F401 — the family's interface
    KERNEL_REL_TOL,
    LOGITS_REL_TOL,
    LOSS_REL_TOL,
    _items,
    host_batches,
)

# The attention kernels in a device trace: a model of both kinds of layer puts
# its attention call under the scope ``attn_window`` or ``attn_full`` inside
# the flax module ``attn``, and XLA names the custom calls after the scope
# (``%attn_window.N``, ``%attn_full.N``: forward, dq and dkv alike). Every
# string has to be in the operation's HLO instruction.
TRACE_KERNELS = ("%attn_", " custom-call(")
# The router's logits of the program against the reference's, as max
# |difference| over max |reference|, layer by layer over the tokens that
# reached the layer on the reference's path (no flip in an earlier layer). The
# router is float32 at the highest precision (below); what differs is its
# input, a bfloat16 residual stream four routers deep in which every token
# also attends to the few percent of tokens an earlier layer flipped:
# measured 0.0087-0.0127 over twelve seeds (my chip runs, PR 31), where OLMoE's one layer reads
# 0.002-0.004 under its limit of 0.015. An 8-bit float under the router (2^-4
# a value against 2^-9) would read thirty times OLMoE's, 0.06-0.12.
ROUTER_LOGITS_REL_TOL = 0.03
# Tokens whose HELD experts may differ from the reference's in any expert
# layer (such a token counts once and is left out of the logits comparison:
# one of the experts that computed its layer is another one). The rule is
# ``moe_lm.py``'s, on every flip, held or not: a flip is right only where the
# reference's k-th of ``s + b`` stands above its (k+1)-th by at most twice the
# largest difference between the token's own program and reference scores; any
# other difference is a wrong top-k and fails the check (``tokens_misrouted``).
# Measured over twelve seeds (my chip runs, PR 31): 6-14% of the tokens flip in
# a layer (128 sigmoid scores lie densely: the 8th and 9th of ``s + b`` are
# 0.009 apart on average), 1.0-5.0% on a held expert, 6.3-8.9% in some layer.
# An 8-bit float under the routers would flip most tokens in every layer.
ROUTE_FLIP_LIMIT = 0.25
# The router's own arithmetic: the program's logits against W_r x in float32
# at the highest precision on the program's OWN router input (sown beside the
# logits), as max |difference| over max |logit|. The same float32 matmul
# twice: 2e-7 on the chip (six-pass float32 against itself; my probe, PR 31).
# A router whose weights and result are bfloat16 reads 3.1e-3 to 3.6e-3 there,
# and so does one whose INPUT is rounded to bfloat16 where the program's is not
# (XLA keeps the norm's float32 result under the router: the first form of this
# check, which sowed the rounded input, read 0.0044-0.0052). Neither can
# ROUTER_LOGITS_REL_TOL tell from the bfloat16 residual stream; this limit can.
ROUTER_ARITHMETIC_REL_TOL = 1e-4
# The bias the program leaves after one more step against the reference's rule
# applied to the program's own counts, as max |difference|. Both add the same
# float32 terms of +-load_balance_coeff less their mean; only the order of one
# mean's sum can differ (1e-10). An update left out, applied twice or with the
# wrong sign is off by load_balance_coeff = 1e-3; a bias kept in bfloat16
# (2^-9 of values near 0.05) by 1e-4.
BIAS_ABS_TOL = 1e-6
# The rule keeps the bias's mean where it was, at zero: the largest |mean| a
# run may show (float32 sums of 128 terms over a few hundred steps: 1e-8).
BIAS_MEAN_TOL = 1e-5
# Which keys a query sees, read off the kernels themselves: with q = k = 0 the
# weights are uniform over the visible keys, and with v_j (for dv: dO_i) the
# one-hot of its position modulo head_dim the result counts the visible keys
# of each residue, exactly known: 16 of 2048 for every residue once the
# window is full. The kernels' bfloat16 output is within 2^-8 of that (0.004);
# one key too many or too few at either edge of the window moves one residue
# of every such row by 1/16 (0.0625).
MEMBERSHIP_REL_TOL = 0.02


def head_dim(config):
    return config["head_dim"]


def arch_spec(config):
    from edl_tpu.models.transformer import ArchSpec

    kinds = {"sliding_attention": "sliding_attention", "full_attention": "attention"}
    return ArchSpec(
        layer_types=tuple(kinds[kind] for kind in config["layer_types"]),
        head_dim=config["head_dim"], rope="sliding",
        sliding_window=config["sliding_window"],
        dense_layers=config["num_dense_layers"], post_norms=True, attn_gate=True,
        embedding_multiplier=(
            config["hidden_size"] ** 0.5 if config["mup_enabled"] else 1.0
        ),
    )


def moe_spec(config):
    from edl_tpu.models.moe import MoESpec

    share = config["share"]
    if config["num_shared_experts"] != 1 or config["score_func"] != "sigmoid":
        raise ValueError("afmoe_lm: one shared expert and sigmoid scores, as published")
    return MoESpec(
        num_experts=share["router_experts"], top_k=config["num_experts_per_tok"],
        d_ff=config["moe_intermediate_size"], norm_topk_prob=config["route_norm"],
        aux_weight=0.0, z_weight=0.0, score_func="sigmoid",
        route_scale=config["route_scale"], bias_rate=config["load_balance_coeff"],
        shared_d_ff=config["moe_intermediate_size"],
        held=(share["experts_first"], config["num_experts"]),
    )


def build(config, global_batch, seed):
    import optax

    from edl_tpu.models import TransformerLM
    from edl_tpu.train import cross_entropy_loss

    import jax.numpy as jnp

    train = config["train"]
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("afmoe_lm: layer_types and num_hidden_layers disagree")
    if train["compute_dtype"] not in ("bfloat16", "float32"):
        raise ValueError("afmoe_lm: compute_dtype %r" % train["compute_dtype"])
    model = TransformerLM(
        dtype=getattr(jnp, train["compute_dtype"]),
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        num_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"], remat=train["remat"],
        remat_policy=train["remat_policy"], norm_eps=config["rms_norm_eps"],
        qk_norm="head", moe=moe_spec(config), arch=arch_spec(config),
    )
    opt = train["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError("afmoe_lm: unknown optimizer %r" % opt["name"])

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


def attention_params(config):
    """q, k, v, the gate (of q's width) and the out projection."""
    d, hd = config["hidden_size"], config["head_dim"]
    q = d * config["num_attention_heads"] * hd
    return 3 * q + 2 * d * config["num_key_value_heads"] * hd


def routed_experts_a_token(config):
    """Expert matmuls a token meets HERE, expected under balanced routing:
    its ``num_experts_per_tok`` choices fall on the held ``num_experts`` of
    the ``router_experts`` with that share (8 x 16 / 128 = 1)."""
    return (
        config["num_experts_per_tok"] * config["num_experts"]
        / config["share"]["router_experts"]
    )


def matmul_params(config):
    """Parameters that multiply every token on this chip: attention's five
    projections in every layer, the dense layers' SwiGLU, in an expert layer
    the router (at its whole width), the shared expert and the expected
    ``routed_experts_a_token`` routed ones, and the head over the slice."""
    d, fe = config["hidden_size"], config["moe_intermediate_size"]
    dense = config["num_dense_layers"]
    sparse = config["num_hidden_layers"] - dense
    expert_layer = (
        d * config["share"]["router_experts"]
        + (1 + routed_experts_a_token(config)) * 3 * d * fe
    )
    return (
        config["num_hidden_layers"] * attention_params(config)
        + dense * 3 * d * config["intermediate_size"]
        + sparse * expert_layer + d * config["vocab_size"]
    )


def layer_attention_forward_flops(config, sequences, windowed):
    """One layer's attention forward over ``sequences`` sequences: two matrix
    multiplications over the visible (query, key) pairs, 2 * D operations a
    pair each: T^2 / 2 pairs a head under the causal mask, T * W - W^2 / 2
    under a window of W < T."""
    t, w = config["train"]["seq_len"], config["sliding_window"]
    pairs = t * w - w * w / 2.0 if windowed and w < t else t * t / 2.0
    return 4.0 * sequences * config["num_attention_heads"] * pairs * config["head_dim"]


def attention_forward_flops(config, sequences):
    windowed = sum(kind == "sliding_attention" for kind in config["layer_types"])
    return (
        windowed * layer_attention_forward_flops(config, sequences, True)
        + (len(config["layer_types"]) - windowed)
        * layer_attention_forward_flops(config, sequences, False)
    )


def flops_per_item(config):
    """As ``transformer_lm.flops_per_item``: 6 per matrix-multiplied parameter
    a token meets and three times the attention forward, the window counted.
    The routed experts count at their expected ``routed_experts_a_token``.
    Recomputation under remat, the sort, the gathers, norms, RoPE, the gate's
    sigmoid, the softmaxes and the optimizer are not counted."""
    t = config["train"]["seq_len"]
    return 6.0 * matmul_params(config) + 3.0 * attention_forward_flops(config, 1) / t


def kernel_flops(config, sequences):
    """What all the layers' flash kernels execute for ``sequences`` sequences:
    the forward, and a backward that recomputes the scores (five matrix
    multiplications to the forward's two), over visible pairs only."""
    return 3.5 * attention_forward_flops(config, sequences)


def _layers_of(config, windowed):
    return sum((kind == "sliding_attention") == windowed for kind in config["layer_types"])


def kind_kernel_flops(config, sequences, windowed):
    """``kernel_flops`` of the windowed layers' kernels alone, or of the full
    layers' alone."""
    return (
        3.5 * _layers_of(config, windowed)
        * layer_attention_forward_flops(config, sequences, windowed)
    )


def kind_kernel_bytes(config, sequences, windowed):
    """The least HBM traffic of those kernels, bfloat16: the forward reads q, k,
    v and writes o; dq reads q, k, v, dO and writes dq; dkv reads the same four
    and writes dk and dv at q's width (they are folded to the kv heads'
    outside). k and v are read once a group of heads that share them."""
    t, hd = config["train"]["seq_len"], config["head_dim"]
    wide = sequences * t * config["num_attention_heads"] * hd * 2
    narrow = sequences * t * config["num_key_value_heads"] * hd * 2
    return _layers_of(config, windowed) * (9 * wide + 6 * narrow)


def moe_kernel_flops(config, tokens):
    """What the grouped matmuls have to compute for ``tokens`` tokens, all
    expert layers: gate, up and down over the rows that fall on held experts
    (``routed_experts_a_token`` a token, expected), forward and both gradients.
    What remat computes a second time does not count."""
    rows = tokens * routed_experts_a_token(config)
    layers = config["num_hidden_layers"] - config["num_dense_layers"]
    return 6.0 * 3 * rows * config["hidden_size"] * config["moe_intermediate_size"] * layers


def moe_kernel_bytes(config, tokens):
    """The least HBM traffic of that work (``moe_lm.moe_kernel_bytes`` over the
    held rows and the held banks)."""
    rows = tokens * routed_experts_a_token(config)
    d, f, e = config["hidden_size"], config["moe_intermediate_size"], config["num_experts"]
    layers = config["num_hidden_layers"] - config["num_dense_layers"]
    return 9.0 * (rows * d * 2 + rows * f * 2 + e * d * f * 2) * layers


def check(config, state, seed):
    """On one seeded sequence, with the trained parameters and the trained
    bias: logits and the cross-entropy against the plain reference; the
    router's logits, scores and choices layer by layer and token by token (a
    flip only where the reference is nearer a tie than the scores differ); the
    bias the program leaves behind against the reference's rule on the
    program's counts; then the windowed and the full kernel at the step's own
    shape, each against dense float32 attention and against the exact count of
    the keys a query sees; and the grouped matmul at the held rows' shape."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import afmoe_lm as reference
    from edl_tpu.train import cross_entropy_loss

    n = config["check"]["sample_items"]
    t = _items(config, seed + 7, n)
    one = jax.devices()[0]
    params = jax.device_put(jax.device_get(state.params), one)
    stats = jax.device_put(jax.device_get(state.batch_stats), one)
    apply_fn = state.apply_fn
    del state
    tokens, targets = jax.device_put((t[:, :-1], t[:, 1:]), one)
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
    def plain(params, stats, tokens, targets):
        logits, info = reference.forward(config, params, stats, tokens)
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
        want_logits, want_ce, info = plain(params, stats, tokens, targets)
    bias = jnp.stack([stats["layer_%d" % i]["moe"]["router_bias"] for i in expert_layers])
    bias_err = float(jnp.max(jnp.abs(routed["bias_after"] - rule(stats, routed["experts"]))))
    bias_mean = float(jnp.max(jnp.abs(jnp.mean(bias, axis=-1))))
    # routing, layer by layer and token by token ([L, N])
    differs = jnp.any(
        jnp.sort(routed["experts"], axis=-1) != jnp.sort(info["experts"], axis=-1),
        axis=-1,
    )
    # a token an EARLIER layer flipped has rightly another residual stream, so
    # its router's input is not the reference's: each layer's logits are
    # compared on the tokens that reached it on the reference's path
    upstream = jnp.cumsum(differs, axis=0) - differs > 0  # ANY flip: the weights move too
    moved_logits = jnp.max(jnp.abs(routed["router_logits"] - info["router_logits"]), axis=-1)
    router_rel = float(
        jnp.max(jnp.where(upstream, 0.0, moved_logits))
        / jnp.max(jnp.abs(info["router_logits"]))
    )
    moved = jnp.max(
        jnp.abs(jax.nn.sigmoid(routed["router_logits"]) - info["scores"]), axis=-1
    )
    scores_err = float(jnp.max(moved))
    # the router's arithmetic on its own input, and what a bfloat16 router
    # (weights and result rounded) reads there: the precision below the stated
    # one, which has to fail ROUTER_ARITHMETIC_REL_TOL
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
    # a flip between two experts held elsewhere moves this chip's part of the
    # layer only through the weights' sum (two scores at a tie: 1e-3 of it); a
    # flip that brings in or takes out a HELD expert changes the token's layer
    # output, so those tokens are the ones the logits comparison leaves out
    first = config["share"]["experts_first"]

    def held_only(experts):
        here = (experts >= first) & (experts < first + config["num_experts"])
        return jnp.sort(jnp.where(here, experts, -1), axis=-1)

    differs_here = jnp.any(
        held_only(routed["experts"]) != held_only(info["experts"]), axis=-1
    )
    flipped = jnp.any(differs_here, axis=0)                          # [N]
    flip_share = float(jnp.mean(flipped))
    flips_a_layer = [float(v) for v in jnp.mean(differs, axis=-1)]
    flips_here_a_layer = [float(v) for v in jnp.mean(differs_here, axis=-1)]
    widest_flip = float(jnp.max(jnp.where(differs, info["margin"], 0.0)))
    error = jnp.max(jnp.abs(got_logits - want_logits), axis=-1).reshape(-1)
    scale = float(jnp.max(jnp.abs(want_logits)))
    rel = float(jnp.max(jnp.where(flipped, 0.0, error))) / scale
    rel_flipped = float(jnp.max(jnp.where(flipped, error, 0.0))) / scale
    finite = bool(jnp.isfinite(got_logits).all())
    rows_held = [float(v) for v in routed["rows_held"]]
    rows_dropped = float(jnp.sum(routed["rows_dropped"]))
    reference_rows_held = [float(v) for v in info["rows_held"]]
    del got_logits, want_logits, params, stats, info, routed
    loss_rel = abs(float(got_ce) - float(want_ce)) / abs(float(want_ce))

    b = config["train"]["batch_per_chip"]  # a model with sown metrics is never split
    shape = (
        b, config["num_attention_heads"], config["num_key_value_heads"],
        config["train"]["seq_len"], config["head_dim"],
    )
    kernels = {
        "window": kernel_vs_reference(seed, *shape, config["sliding_window"]),
        "full": kernel_vs_reference(seed, *shape, None),
    }
    members = {
        "window": kernel_membership(*shape, config["sliding_window"]),
        "full": kernel_membership(*shape, None),
    }
    held_rows = dict(
        config, num_experts_per_tok=1, intermediate_size=config["moe_intermediate_size"]
    )  # 16 groups of b * T / 16 rows expected: what the held experts see
    gmm = grouped_matmul_vs_reference(held_rows, seed, b * config["train"]["seq_len"])
    ok = (
        finite and rel <= LOGITS_REL_TOL and loss_rel <= LOSS_REL_TOL
        and router_rel <= ROUTER_LOGITS_REL_TOL
        and arithmetic_rel <= ROUTER_ARITHMETIC_REL_TOL
        and misrouted == 0 and flip_share <= ROUTE_FLIP_LIMIT
        and bias_err <= BIAS_ABS_TOL and bias_mean <= BIAS_MEAN_TOL
        and rows_dropped == 0
        and all(k["max_rel_err"] <= KERNEL_REL_TOL for k in kernels.values())
        and all(m["max_rel_err"] <= MEMBERSHIP_REL_TOL for m in members.values())
        and gmm["max_rel_err"] <= GMM_REL_TOL
    )
    return {
        "ok": bool(ok), "logits_rel_err": rel, "logits_rel_tol": LOGITS_REL_TOL,
        "loss": float(got_ce), "reference_loss": float(want_ce),
        "loss_rel_err": loss_rel, "loss_rel_tol": LOSS_REL_TOL,
        "logits_rel_err_flipped_tokens": rel_flipped,
        "router_logits_rel_err": router_rel,
        "router_logits_rel_tol": ROUTER_LOGITS_REL_TOL,
        "router_arithmetic_rel_err": arithmetic_rel,
        "router_arithmetic_rel_tol": ROUTER_ARITHMETIC_REL_TOL,
        "router_arithmetic_rel_err_of_a_bfloat16_router": arithmetic_rel_bf16,
        "router_scores_abs_err": scores_err,
        "flipped_share": flip_share, "flipped_limit": ROUTE_FLIP_LIMIT,
        "any_flip_share_by_layer": flips_a_layer,
        "flipped_share_by_layer": flips_here_a_layer,
        "widest_flipped_margin": widest_flip, "tokens_misrouted": misrouted,
        "bias_abs_err": bias_err, "bias_abs_tol": BIAS_ABS_TOL,
        "bias_mean": bias_mean, "bias_mean_tol": BIAS_MEAN_TOL,
        "bias_abs_max": float(jnp.max(jnp.abs(bias))),
        "rows_held": rows_held, "reference_rows_held": reference_rows_held,
        "rows_dropped": rows_dropped,
        "sample_items": n, "kernel": kernels, "kernel_rel_tol": KERNEL_REL_TOL,
        "kernel_membership": members, "membership_rel_tol": MEMBERSHIP_REL_TOL,
        "grouped_matmul": gmm, "grouped_matmul_rel_tol": GMM_REL_TOL,
    }


def kernel_vs_reference(seed, b, h, h_kv, t, d, window):
    """``ops.attention.attention`` as the step calls it (value and q/k/v
    gradients, causal, ``window`` or none, bfloat16) against the reference's
    dense float32 attention on the same inputs, a few query heads at a time
    because the reference's [t, t] scores are dense."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.afmoe_lm import HEADS_AT_ONCE, masked_attention
    from edl_tpu.ops import attention

    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 4)
    q = jax.random.normal(keys[0], (b, h, t, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, h_kv, t, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, h_kv, t, d), jnp.bfloat16)
    w = jax.random.normal(keys[3], (b, h, t, d), jnp.bfloat16)  # cotangent

    def value_and_grads(fn):
        def run(q, k, v, w):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out, *vjp(w.astype(out.dtype)))
        return jax.jit(run)

    got = value_and_grads(
        lambda q, k, v: attention(q, k, v, causal=True, window=window)
    )(q, k, v, w)
    ref_fn = value_and_grads(lambda q, k, v: masked_attention(q, k, v, window))
    group = h // h_kv
    chunk = min(group, HEADS_AT_ONCE)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    out, dq = [], []
    dk, dv = jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)
    with jax.default_matmul_precision("highest"):
        for first in range(0, h, chunk):  # heads that share one kv head
            qs, ks = slice(first, first + chunk), slice(first // group, first // group + 1)
            o, gq, gk, gv = ref_fn(f32(q[:, qs]), f32(k[:, ks]), f32(v[:, ks]), f32(w[:, qs]))
            out.append(o)
            dq.append(gq)
            dk, dv = dk.at[:, ks].add(gk), dv.at[:, ks].add(gv)
    want = [jnp.concatenate(out, axis=1), jnp.concatenate(dq, axis=1), dk, dv]
    errs = {}
    for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        errs[name] = (
            float(np.max(np.abs(a - r)) / np.max(np.abs(r)))
            if np.isfinite(a).all() else float("inf")
        )
    return {"shape": [b, h, h_kv, t, d], "window": window,
            "max_rel_err": max(errs.values()), **errs}


def kernel_membership(b, h, h_kv, t, d, window):
    """Which keys each query sees, and which queries see each key, counted by
    the kernels: q = k = 0 makes the weights uniform over the visible keys, and
    one-hot values (cotangents) of the position modulo ``d`` make the output
    (dv) the count of visible keys (seeing queries) of each residue over the
    number visible. The expected counts are exact integers from the mask's
    definition ``i - window < j <= i``; rows at both edges of the window (the
    first row with a full window, the last key every later row still sees)
    are all among them. Returns the largest |difference| of a row over the
    row's largest expected value."""
    import jax
    import jax.numpy as jnp

    from edl_tpu.ops import attention

    q = jnp.zeros((b, h, t, d), jnp.bfloat16)
    k = jnp.zeros((b, h_kv, t, d), jnp.bfloat16)
    residue = np.arange(t) % d
    one_hot = np.eye(d, dtype=np.float32)[residue]                   # [t, d]
    v = jnp.broadcast_to(jnp.asarray(one_hot, jnp.bfloat16), (b, h_kv, t, d))
    w = jnp.broadcast_to(jnp.asarray(one_hot, jnp.bfloat16), (b, h, t, d))

    @jax.jit
    def run(q, k, v, w):
        out, vjp = jax.vjp(
            lambda q, k, v: attention(q, k, v, causal=True, window=window), q, k, v
        )
        return out, vjp(w)[2]

    out, dv = run(q, k, v, w)
    span = t if window is None else window
    i = np.arange(t)
    visible = np.minimum(i + 1, span).astype(np.float64)             # keys row i sees
    # prefix[x, c]: positions below x of residue c
    prefix = np.concatenate([np.zeros((1, d)), np.cumsum(one_hot, axis=0)])
    want_out = (prefix[i + 1] - prefix[np.maximum(i + 1 - span, 0)]) / visible[:, None]
    # dv_j = sum over the rows i in [j, j + span) of dO_i / visible_i, every
    # query head of the group adding its own
    weighted = np.concatenate([np.zeros((1, d)), np.cumsum(one_hot / visible[:, None], axis=0)])
    want_dv = (h // h_kv) * (weighted[np.minimum(i + span, t)] - weighted[i])
    errs = {}
    for name, a, r in (("out", out, want_out), ("dv", dv, want_dv)):
        a = np.asarray(a, np.float64)
        # row by row, against the row's own largest count: the early rows'
        # few keys weigh a hundred times a full window's
        errs[name] = (
            float(np.max(np.max(np.abs(a - r), axis=-1) / np.max(r, axis=-1)))
            if np.isfinite(a).all() else float("inf")
        )
    return {"window": window, "max_rel_err": max(errs.values()), **errs}
