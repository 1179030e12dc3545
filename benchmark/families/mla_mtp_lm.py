"""Family ``mla_mtp_lm``: the program's ``TransformerLM`` as one chip's share of
a decoder whose every layer is multi-head latent attention WITH a query rank
(zai-org's GLM-4.7-Flash line, ``glm4_moe_lite``; DeepSeek-V2's block):
``models/transformer.py:LatentAttention(q_lora_rank=...)``, keys of 256 and
values of 256 a head through the grid-pipelined flash kernels, no gate; a norm
before each branch; the leading layer a dense SwiGLU, the others sigmoid-scored
experts under a balancing bias without groups, **the experts this chip holds**
(``models/moe.py:DroplessMoE(held=...)``) beside a shared one; an untied head
over a slice of the vocabulary; and behind the last norm **a multi-token
prediction module** (``ArchSpec.mtp``: one more block, the embedding and the
head used a second time), whose loss rides the step's objective through the
``"losses"`` collection. Built from a file that keeps the published
``config.json`` keys.

See ``resnet_vd.py`` for what a family is. The token generator, the distinct
batches and the start are ``solar_lm.py``'s (uniform ids of the held slice, no
batch twice, the head at the optimum of ids that cannot be learnt); the kernels'
comparison and their names in a trace are ``kda_lm.py``'s; the routing
comparison is ``lfm2_lm.py``'s rule (the reference computes with the program's
choice and each choice is judged against the reference's own), written here as
one function over the trunk's expert layers and the module's.
"""

from __future__ import annotations

import numpy as np

from benchmark.families.afmoe_lm import (  # noqa: F401 — the family's interface
    BIAS_ABS_TOL,
    BIAS_MEAN_TOL,
    ROUTER_ARITHMETIC_REL_TOL,
)
from benchmark.families.kda_lm import (  # noqa: F401 — the family's interface
    TRACE_KERNELS,
    mla_kernel_vs_reference,
)
from benchmark.families.moe_lm import (  # noqa: F401 — the family's interface
    GMM_REL_TOL,
    MOE_TRACE_KERNELS,
    grouped_matmul_vs_reference,
)
from benchmark.families.solar_lm import as_drawn, host_batches, started  # noqa: F401
from benchmark.families.ssm_lm import _rel
from benchmark.families.transformer_lm import KERNEL_REL_TOL, LOSS_REL_TOL, _items

# Every limit below lies between two readings: the largest the program gave on
# the chip over this PR's seeds (TPU v5 lite; PERF.md section 6, PR 55: after
# the cell's own window, and freshly drawn parameters with the head as drawn),
# and what the same program reads in the nearest precision below,
# ``float8_e4m3fn``: at the cell's own size on the chip
# (``bench_results/glm_precision_below.py``) and at a width of 256 on the CPU
# (``benchmark/tests/test_mla_mtp_lm.py``). The 8-bit program hands its kernels
# bfloat16 operands (no Pallas kernel here takes an 8-bit float).
#
# Logits of the program (bfloat16 operands, float32 accumulation, float32
# logits) against the float32 reference computed with the program's own choice
# of experts, as max |difference| over max |reference| over every token; the
# main head's and the module's, which lies one block and one joined projection
# deeper, under one limit. Read (PR 55): 0.0036 main and 0.0100 the module's
# after the window, 0.0098 and 0.0090 freshly drawn; 8-bit 0.152 and 0.126.
LOGITS_REL_TOL = 0.045
# The router's logits of the program against the reference's, layer by layer
# (the trunk's four and the module's), as max |difference| over max
# |reference|: a float32 router whose input is a bfloat16 residual stream.
# Read: 0.0084 after the window, 0.0081 freshly drawn; 8-bit 0.113. (A stream
# that training has made one direction reads 0.13-0.61: the configuration's
# ``assumed`` says why the cell trains at 1e-5.)
ROUTER_LOGITS_REL_TOL = 0.04
# Tokens whose choice of experts may differ from the one the reference makes
# for itself on the same stream, in the expert layer where most do, by
# ``lfm2_lm.py``'s rule: a flip is right only where the reference's margin (the
# 4th's lead over the 5th of ``s + b``) is at most twice the largest difference
# between the token's own program and reference scores; any other difference
# fails the check as ``tokens_misrouted``. Read: 3.7% after the window, 3.5%
# freshly drawn; 8-bit 40.9%.
ROUTE_FLIP_LIMIT = 0.12
# ``q`` out of the query rank in the first layer (``x W_qa``, its norm, ``W_qb``,
# from the trained parameters on the normed embedding, before the rotation)
# against the reference's float32 form, as max |difference| over max
# |reference|: two matmuls' bfloat16 operands and outputs and a norm between.
# A dropped norm or a full-rank matrix in the pair's place is off by the whole.
# Read: 0.0052 after the window, 0.0043 freshly drawn; 8-bit 0.084.
QUERY_REL_TOL = 0.02


def latent_spec(config):
    from edl_tpu.models import LatentAttentionSpec

    if config["q_lora_rank"] is None:
        raise ValueError("mla_mtp_lm: a query rank, as published (kda_lm runs none)")
    if config["partial_rotary_factor"] != 1 or config["rope_scaling"] is not None:
        raise ValueError("mla_mtp_lm: the whole rotated part rotated, unscaled, as published")
    if config["attention_bias"] or config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("mla_mtp_lm: no bias and as many key heads as heads, as published")
    return LatentAttentionSpec(
        kv_lora_rank=config["kv_lora_rank"], qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"], v_head_dim=config["v_head_dim"],
        q_lora_rank=config["q_lora_rank"],
    )


def arch_spec(config):
    from edl_tpu.models import ArchSpec, MTPSpec

    if config["num_nextn_predict_layers"] != 1:
        raise ValueError("mla_mtp_lm: one multi-token module, as published")
    return ArchSpec(
        layer_types=("latent_attention",) * config["num_hidden_layers"],
        latent_attention=latent_spec(config), rope_theta=float(config["rope_theta"]),
        dense_layers=config["first_k_dense_replace"],
        tie_embeddings=config["tie_word_embeddings"],
        mtp=MTPSpec(depth=1, loss_weight=config["train"]["mtp_loss_weight"]),
    )


def moe_spec(config):
    from edl_tpu.models import MoESpec

    share = config["share"]
    if config["topk_method"] != "noaux_tc" or (config["n_group"], config["topk_group"]) != (1, 1):
        raise ValueError("mla_mtp_lm: sigmoid scores under a bias without groups, as published")
    if config["n_shared_experts"] != 1:
        raise ValueError("mla_mtp_lm: one shared expert, as published")
    return MoESpec(
        num_experts=share["router_experts"], top_k=config["num_experts_per_tok"],
        d_ff=config["moe_intermediate_size"], norm_topk_prob=config["norm_topk_prob"],
        aux_weight=0.0, z_weight=0.0, score_func="sigmoid",
        route_scale=config["routed_scaling_factor"],
        bias_rate=config["train"]["expert_bias_rate"],
        shared_d_ff=config["moe_intermediate_size"],
        held=(share["experts_first"], config["n_routed_experts"]),
    )


def build(config, global_batch, seed):
    import jax.numpy as jnp
    import optax

    from edl_tpu.models import TransformerLM
    from edl_tpu.train import cross_entropy_loss

    train = config["train"]
    if train["compute_dtype"] not in ("bfloat16", "float32"):
        raise ValueError("mla_mtp_lm: compute_dtype %r" % train["compute_dtype"])
    model = started(TransformerLM, train["start"])(
        dtype=getattr(jnp, train["compute_dtype"]),
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_layers=config["num_hidden_layers"], d_ff=config["intermediate_size"],
        remat=train["remat"], remat_policy=train["remat_policy"],
        norm_eps=config["rms_norm_eps"], moe=moe_spec(config), arch=arch_spec(config),
    )
    opt = train["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError("mla_mtp_lm: unknown optimizer %r" % opt["name"])

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


def attention_calls(config):
    """Latent-attention calls a step: the trunk's layers and the module's block."""
    return config["num_hidden_layers"] + config["num_nextn_predict_layers"]


def expert_layers(config):
    """Expert layers a step: the trunk's past the dense ones and the module's."""
    return (
        config["num_hidden_layers"] - config["first_k_dense_replace"]
        + config["num_nextn_predict_layers"]
    )


def mla_mixer_params(config):
    """The matrices of one latent layer: the query pair through its rank, the
    latent with the shared rotated key, its up projection to keys and values,
    the out projection."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rot, dv = (
        config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    )
    q_rank, rank = config["q_lora_rank"], config["kv_lora_rank"]
    return (
        d * q_rank + q_rank * h * (nope + rot) + d * (rank + rot)
        + rank * h * (nope + dv) + h * dv * d
    )


def routed_experts_a_token(config):
    """Expert matmuls a token meets HERE, expected under balanced routing: its
    ``num_experts_per_tok`` choices fall on the held ``n_routed_experts`` of
    the ``router_experts`` with that share (4 x 8 / 64 = 1/2)."""
    return (
        config["num_experts_per_tok"] * config["n_routed_experts"]
        / config["share"]["router_experts"]
    )


def matmul_params(config):
    """Parameters that multiply every token on this chip, a use at a time: each
    attention call's matrices (the module's block among them), the dense
    layers' SwiGLU, in an expert layer the router (at its whole width), the
    shared expert and the expected ``routed_experts_a_token`` routed ones, the
    module's joined projection ``[2 hidden, hidden]``, and the head over the
    slice TWICE (the main logits and the module's)."""
    d, fe = config["hidden_size"], config["moe_intermediate_size"]
    expert_layer = (
        d * config["share"]["router_experts"] + 3 * d * fe
        + routed_experts_a_token(config) * 3 * d * fe
    )
    modules = config["num_nextn_predict_layers"]
    return (
        attention_calls(config) * mla_mixer_params(config)
        + config["first_k_dense_replace"] * 3 * d * config["intermediate_size"]
        + expert_layers(config) * expert_layer
        + modules * 2 * d * d
        + (1 + modules) * d * config["vocab_size"]
    )


def attention_forward_flops(config, sequences):
    """The attention calls' causal forward over ``sequences`` sequences: T^2 / 2
    visible pairs a head, 2 (nope + rope) operations a pair for the scores and
    2 v_head_dim for the values, the module's call among them."""
    t = config["train"]["seq_len"]
    widths = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"]
    return (
        2.0 * sequences * config["num_attention_heads"] * (t * t / 2.0) * widths
        * attention_calls(config)
    )


def flops_per_item(config):
    """As ``transformer_lm.flops_per_item``: 6 per matrix-multiplied parameter
    a token meets (the routed experts at their expected share; the head twice)
    and three times the attention calls' forward. Recomputation under remat,
    norms, the rotation, the softmax, the sort, both cross-entropies and the
    optimizer are not counted."""
    t = config["train"]["seq_len"]
    return 6.0 * matmul_params(config) + 3.0 * attention_forward_flops(config, 1) / t


def kernel_flops(config, sequences):
    """What the attention calls' flash kernels execute: the forward's two
    products and the fused backward's five (the scores, ``dP``, ``dV``, ``dK``,
    ``dQ``), over visible pairs: 2 (4 d_qk + 3 d_v) a pair."""
    t = config["train"]["seq_len"]
    d_qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    pairs = sequences * config["num_attention_heads"] * t * t / 2.0
    return 2.0 * pairs * (4 * d_qk + 3 * config["v_head_dim"]) * attention_calls(config)


def moe_kernel_flops(config, tokens):
    """What the grouped matmuls have to compute for ``tokens`` tokens, all
    expert layers: gate, up and down over the rows that fall on held experts
    (``routed_experts_a_token`` a token, expected), forward and both gradients."""
    rows = tokens * routed_experts_a_token(config)
    return (
        6.0 * 3 * rows * config["hidden_size"] * config["moe_intermediate_size"]
        * expert_layers(config)
    )


def moe_kernel_bytes(config, tokens):
    """The least HBM traffic of that work (``moe_lm.moe_kernel_bytes`` over the
    held rows and the held banks)."""
    rows = tokens * routed_experts_a_token(config)
    d, f, e = config["hidden_size"], config["moe_intermediate_size"], config["n_routed_experts"]
    return 9.0 * (rows * d * 2 + rows * f * 2 + e * d * f * 2) * expert_layers(config)


def routing_vs_reference(config, routed, info, weights):
    """The routers layer by layer and token by token ([L, N]): ``routed`` is what
    the program's expert layers sowed (``experts``, ``router_logits``,
    ``router_in``), ``info`` the reference's own on the same stream (both sides
    computed every earlier layer with the program's experts), ``weights`` the
    routers' kernels [L, D, E]. ``lfm2_lm.py``'s rule: a flip is right only
    where the reference's margin is at most twice the largest difference
    between the token's own program and reference scores."""
    import jax
    import jax.numpy as jnp

    differs = jnp.any(
        jnp.sort(routed["experts"], axis=-1) != jnp.sort(info["experts"], axis=-1),
        axis=-1,
    )
    moved_logits = jnp.max(jnp.abs(routed["router_logits"] - info["router_logits"]), axis=-1)
    moved = jnp.max(
        jnp.abs(jax.nn.sigmoid(routed["router_logits"]) - info["scores"]), axis=-1
    )
    # the router's arithmetic on its own input, and what a bfloat16 router
    # reads there: the precision below the stated one, which has to fail
    fed = routed["router_in"].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        exact = jnp.einsum("lnd,lde->lne", fed, weights)
        coarse = jnp.einsum(
            "lnd,lde->lne", fed, weights.astype(jnp.bfloat16).astype(jnp.float32)
        ).astype(jnp.bfloat16).astype(jnp.float32)
    largest = jnp.max(jnp.abs(exact))
    flips_a_layer = [float(v) for v in jnp.mean(differs, axis=-1)]
    return {
        "router_logits_rel_err": float(
            jnp.max(moved_logits) / jnp.max(jnp.abs(info["router_logits"]))
        ),
        "router_arithmetic_rel_err": float(
            jnp.max(jnp.abs(routed["router_logits"] - exact)) / largest
        ),
        "router_arithmetic_rel_err_of_a_bfloat16_router": float(
            jnp.max(jnp.abs(coarse - exact)) / largest
        ),
        "router_logits_rel_err_by_layer": [
            float(v) for v in jnp.max(moved_logits, axis=-1)
            / jnp.max(jnp.abs(info["router_logits"]), axis=(-2, -1))
        ],
        "router_logits_abs_max_by_layer": [
            float(v) for v in jnp.max(jnp.abs(info["router_logits"]), axis=(-2, -1))
        ],
        "router_scores_abs_err": float(jnp.max(moved)),
        "flipped_share": max(flips_a_layer),  # judged: the layer where most tokens flip
        "flipped_share_by_layer": flips_a_layer,
        "widest_flipped_margin": float(jnp.max(jnp.where(differs, info["margin"], 0.0))),
        "tokens_misrouted": int(jnp.sum(differs & (info["margin"] > 2.0 * moved))),
    }


def check(config, state, seed):
    """On one seeded sequence, with the trained parameters and the trained
    biases: the main logits, the module's logits and both cross-entropies
    against the plain reference computed with the program's choice of experts
    (``lfm2_lm.py``'s form: attention carries a token's other expert into its
    neighbours' streams); the module's loss as the model sows it against the
    reference's; the routers' logits, scores and choices layer by layer and
    token by token, the module's among them; the biases the program leaves
    behind against the reference's rule on the program's counts; ``q`` out of
    the first layer's query rank; then the two-width flash kernels against
    dense float32 attention at the step's own shape and the grouped matmul at
    the held rows' shape."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import mla_mtp_lm as reference
    from edl_tpu.train import cross_entropy_loss

    n = config["check"]["sample_items"]
    t = _items(config, seed + 7, n)
    # run.py hands over plain arrays on one device: no second copy of 8.5 GB
    params, stats, apply_fn = state.params, state.batch_stats, state.apply_fn
    del state
    tokens, targets = t[:, :-1], t[:, 1:]
    blocks = reference.expert_blocks(config)

    @jax.jit
    def program(params, stats, tokens, targets):
        logits, left = apply_fn(
            {"params": params, "batch_stats": stats}, tokens,
            mutable=["intermediates", "batch_stats", "metrics"],
        )
        ce, _ = cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
        )
        seen = [left["intermediates"][name]["moe"] for name in blocks]
        sown = [left["metrics"][name]["moe"] for name in blocks]
        return logits, left["intermediates"]["mtp_logits"][0], ce, {
            "mtp_loss": left["metrics"]["mtp_loss"][0],
            "q": left["intermediates"]["layer_0"]["attn"]["queries"][0],
            "experts": jnp.stack([p["top_idx"][0] for p in seen]),
            "router_logits": jnp.stack([p["router_logits"][0] for p in seen]),
            "router_in": jnp.stack([p["router_in"][0] for p in seen]),
            "bias_after": jnp.stack([
                left["batch_stats"][name]["moe"]["router_bias"] for name in blocks
            ]),
            "rows_held": jnp.stack([p["moe_rows_held"][0] for p in sown]),
            "rows_dropped": jnp.stack([p["moe_rows_dropped"][0] for p in sown]),
        }

    @jax.jit
    def plain(params, stats, tokens, targets, chosen):
        logits, ahead, info = reference.forward(config, params, stats, tokens, chosen)
        return logits, ahead, reference.losses(logits, ahead, tokens, targets), info

    @jax.jit
    def rule(stats, experts):  # the reference's rule on the PROGRAM's counts
        e = config["share"]["router_experts"]
        return jnp.stack([
            reference.bias_update(
                config, stats[name]["moe"]["router_bias"],
                jnp.zeros((e,), jnp.int32).at[experts[j].reshape(-1)].add(1),
            )
            for j, name in enumerate(blocks)
        ])

    @jax.jit
    def first_queries(params, tokens):  # on the program's own normed embedding
        x = jnp.asarray(params["embed"]["embedding"])[tokens].astype(jnp.bfloat16)
        x32 = x.astype(jnp.float32)
        x = (x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + config["rms_norm_eps"]
        ) * params["layer_0"]["ln1"]["scale"]).astype(jnp.bfloat16)
        return reference.queries(config, params["layer_0"]["attn"], x)

    got_logits, got_ahead, got_ce, routed = program(params, stats, tokens, targets)
    with jax.default_matmul_precision("highest"):
        want_logits, want_ahead, (want_ce, want_mtp), info = plain(
            params, stats, tokens, targets, routed["experts"]
        )
        query_rel = _rel(routed.pop("q"), first_queries(params, tokens))
    bias = jnp.stack([stats[name]["moe"]["router_bias"] for name in blocks])
    bias_err = float(jnp.max(jnp.abs(routed["bias_after"] - rule(stats, routed["experts"]))))
    bias_mean = float(jnp.max(jnp.abs(jnp.mean(bias, axis=-1))))
    routing = routing_vs_reference(
        config, routed, info,
        jnp.stack([params[name]["moe"]["router"]["kernel"] for name in blocks]),
    )
    rel = _rel(got_logits, want_logits)
    # the two positions past the last scored one read a padded id: left out
    mtp_rel = _rel(got_ahead[:, :-2], want_ahead[:, :-2])
    finite = bool(jnp.isfinite(got_logits).all() and jnp.isfinite(got_ahead).all())
    nonzero = float(jnp.max(jnp.abs(want_logits))) > 0 and float(jnp.max(jnp.abs(want_ahead))) > 0
    rows_held = [float(v) for v in routed["rows_held"]]
    rows_dropped = float(jnp.sum(routed["rows_dropped"]))
    got_mtp = float(routed["mtp_loss"])
    del got_logits, want_logits, got_ahead, want_ahead, params, stats, info, routed
    loss_rel = abs(float(got_ce) - float(want_ce)) / abs(float(want_ce))
    mtp_loss_rel = abs(got_mtp - float(want_mtp)) / abs(float(want_mtp))

    b, steps = config["train"]["batch_per_chip"], config["train"]["seq_len"]
    kernel = mla_kernel_vs_reference(
        seed, b, config["num_attention_heads"], steps,
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"], config["v_head_dim"],
    )
    held_rows = dict(
        config, num_experts=config["n_routed_experts"], num_experts_per_tok=1,
        intermediate_size=config["moe_intermediate_size"],
    )  # the held groups of b * T * k / E rows expected: what the held experts see
    gmm = grouped_matmul_vs_reference(
        held_rows, seed, int(b * steps * routed_experts_a_token(config))
    )
    ok = (
        finite and nonzero and rel <= LOGITS_REL_TOL and mtp_rel <= LOGITS_REL_TOL
        and loss_rel <= LOSS_REL_TOL and mtp_loss_rel <= LOSS_REL_TOL
        and routing["router_logits_rel_err"] <= ROUTER_LOGITS_REL_TOL
        and routing["router_arithmetic_rel_err"] <= ROUTER_ARITHMETIC_REL_TOL
        and routing["tokens_misrouted"] == 0 and routing["flipped_share"] <= ROUTE_FLIP_LIMIT
        and bias_err <= BIAS_ABS_TOL and bias_mean <= BIAS_MEAN_TOL
        and rows_dropped == 0 and query_rel <= QUERY_REL_TOL
        and kernel["max_rel_err"] <= KERNEL_REL_TOL
        and gmm["max_rel_err"] <= GMM_REL_TOL
    )
    return {
        "ok": bool(ok), "logits_rel_err": rel, "mtp_logits_rel_err": mtp_rel,
        "logits_rel_tol": LOGITS_REL_TOL, "logits_nonzero": nonzero,
        "loss": float(got_ce), "reference_loss": float(want_ce),
        "loss_rel_err": loss_rel, "loss_rel_tol": LOSS_REL_TOL,
        "mtp_loss": got_mtp, "reference_mtp_loss": float(want_mtp),
        "mtp_loss_rel_err": mtp_loss_rel,
        **routing,
        "router_logits_rel_tol": ROUTER_LOGITS_REL_TOL,
        "router_arithmetic_rel_tol": ROUTER_ARITHMETIC_REL_TOL,
        "flipped_limit": ROUTE_FLIP_LIMIT, "expert_blocks": blocks,
        "bias_abs_err": bias_err, "bias_abs_tol": BIAS_ABS_TOL,
        "bias_mean": bias_mean, "bias_mean_tol": BIAS_MEAN_TOL,
        "bias_abs_max": float(jnp.max(jnp.abs(bias))),
        "rows_held": rows_held, "rows_dropped": rows_dropped,
        "query_rel_err": query_rel, "query_rel_tol": QUERY_REL_TOL,
        "sample_items": n,
        "kernel": kernel, "kernel_rel_tol": KERNEL_REL_TOL,
        "grouped_matmul": gmm, "grouped_matmul_rel_tol": GMM_REL_TOL,
    }
