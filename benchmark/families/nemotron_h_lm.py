"""Family ``nemotron_h_lm``: the program's ``TransformerLM`` as one chip's share
of a Nemotron-H decoder with a latent expert layer (NVIDIA's Nemotron-3 line):
**blocks of one branch each** (``ArchSpec.one_branch``), by the letters of
``hybrid_override_pattern`` a Mamba-2 mixer in groups (``models/mamba.py`` over
``ops/ssd.py`` and ``ops/causal_conv.py``, the gated norm by group),
position-free grouped-query attention (the flash kernels as routed) or the
expert layer alone: sigmoid scores over all the model's experts under a
balancing bias, top-k weights normalised and scaled, **ungated squared-ReLU
experts in a latent** between two shared projections, the experts this chip
holds (``models/moe.py:DroplessMoE(held=..., gated=False, activation="relu2",
latent=...)``) beside a full-width shared expert; an untied head over a slice
of the vocabulary. Built from a file that keeps the published ``config.json``
keys.

See ``resnet_vd.py`` for what a family is. The token generator is
``transformer_lm.py``'s (uniform ids of the held slice); the routing comparison
is ``lfm2_lm.py``'s (the reference computes with the program's choice and each
choice is judged against the reference's own); the scan's, the flash kernels'
and the grouped matmul's own comparisons are ``ssm_lm.py``'s and ``moe_lm.py``'s
at this configuration's shapes.
"""

from __future__ import annotations

import numpy as np

from benchmark.families import ssm_lm
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
from benchmark.families.ssm_lm import (  # noqa: F401 — the family's interface
    MIXER_REL_TOL,
    SCAN_REL_TOL,
    STATE_RMS_TOL,
    _rel,
)
from benchmark.families.transformer_lm import (  # noqa: F401 — the family's interface
    KERNEL_REL_TOL,
    LOSS_REL_TOL,
    TRACE_KERNELS,
    _items,
    host_batches,
)

# Every limit of the stream below lies between two readings: the largest the
# program gave on the chip over this PR's seeds (TPU v5 lite, the cell's own
# traffic; PERF.md section 6, PR 49, has the runs), and what the same program
# reads in the nearest precision below, ``float8_e4m3fn``
# (``benchmark/tests/test_nemotron_h_lm.py``, the cell's nine blocks at a width
# of 256 on the CPU), about the geometric middle of the two. ``MIXER_REL_TOL``,
# ``SCAN_REL_TOL``, ``STATE_RMS_TOL`` (``ssm_lm.py``), ``KERNEL_REL_TOL``
# (``transformer_lm.py``), ``GMM_REL_TOL`` (``moe_lm.py``) and the router's and
# the bias's three (``afmoe_lm.py``) stand as they are, each beside its reason
# there: the operation and its precision are the same.
#
# Logits of the program (bfloat16 operands, float32 accumulation, float32
# logits) against the float32 reference computed with the program's own choice
# of experts, as max |difference| over max |reference| over every token: 0.0086
# to 0.0113 on the chip over ten seeds, 0.30 in 8 bits.
LOGITS_REL_TOL = 0.05
# The router's logits of the program against the reference's, block by block,
# as max |difference| over max |reference|: a float32 router whose input is a
# bfloat16 residual stream, four routers deep behind four Mamba-2 mixers: 0.0066
# to 0.0080 on the chip, 0.30 in 8 bits.
ROUTER_LOGITS_REL_TOL = 0.045
# Tokens whose choice of experts may differ from the one the reference makes
# for itself on the same stream, in the expert block where most do, by
# ``afmoe_lm.py``'s rule: a flip is right only where the reference's margin
# (the 22nd's lead over the 23rd of ``s + b``) is at most twice the largest
# difference between the token's own program and reference scores; any other
# difference fails the check as ``tokens_misrouted``. 512 sigmoid scores lie
# densely and a token has twenty-two places to tie where Ling's has eight, but
# no group's edge to cross: 14 to 15% of the tokens flip in the first expert
# block and 27 to 30% in the fourth on the chip, none misrouted (the widest
# flipped margin 0.0054 of a score); in 8 bits 76%.
ROUTE_FLIP_LIMIT = 0.5


def letters(config, letter):
    return config["hybrid_override_pattern"].count(letter)


def mamba_spec(config):
    from edl_tpu.models import MambaSpec

    if config["mamba_num_heads"] % config["n_groups"]:
        raise ValueError("nemotron_h_lm: whole groups of Mamba-2 heads")
    return MambaSpec(
        num_heads=config["mamba_num_heads"], head_dim=config["mamba_head_dim"],
        d_state=config["ssm_state_size"], n_groups=config["n_groups"],
        d_conv=config["conv_kernel"], chunk=config["chunk_size"],
        conv_bias=config["use_conv_bias"],
    )


def arch_spec(config):
    from edl_tpu.models import ArchSpec

    kinds = {"M": "mamba", "*": "attention", "E": "moe"}
    if config["attention_bias"] or config["mamba_proj_bias"] or config["mlp_bias"]:
        raise ValueError("nemotron_h_lm: no bias on a projection, as published")
    return ArchSpec(
        layer_types=tuple(kinds[c] for c in config["hybrid_override_pattern"]),
        mamba=mamba_spec(config), head_dim=config["head_dim"], rope=False,
        tie_embeddings=config["tie_word_embeddings"], one_branch=True,
    )


def moe_spec(config):
    from edl_tpu.models import MoESpec

    share = config["share"]
    if config["n_shared_experts"] != 1 or config["mlp_hidden_act"] != "relu2":
        raise ValueError("nemotron_h_lm: one shared expert and relu2, as published")
    return MoESpec(
        num_experts=share["router_experts"], top_k=config["num_experts_per_tok"],
        d_ff=config["moe_intermediate_size"], norm_topk_prob=config["norm_topk_prob"],
        aux_weight=0.0, z_weight=0.0, score_func="sigmoid",
        route_scale=config["routed_scaling_factor"],
        bias_rate=config["train"]["expert_bias_rate"],
        shared_d_ff=config["moe_shared_expert_intermediate_size"],
        held=(share["experts_first"], config["n_routed_experts"]),
        n_group=config["n_group"], topk_group=config["topk_group"],
        gated=False, activation="relu2", latent=config["moe_latent_size"],
    )


def build(config, global_batch, seed):
    import jax.numpy as jnp
    import optax

    from edl_tpu.models import TransformerLM
    from edl_tpu.train import cross_entropy_loss

    train = config["train"]
    if len(config["hybrid_override_pattern"]) != config["num_hidden_layers"]:
        raise ValueError("nemotron_h_lm: the pattern does not list num_hidden_layers blocks")
    if train["compute_dtype"] not in ("bfloat16", "float32"):
        raise ValueError("nemotron_h_lm: compute_dtype %r" % train["compute_dtype"])
    model = TransformerLM(
        dtype=getattr(jnp, train["compute_dtype"]),
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        num_layers=config["num_hidden_layers"], d_ff=config["intermediate_size"],
        remat=train["remat"], remat_policy=train["remat_policy"],
        norm_eps=config["layer_norm_epsilon"], moe=moe_spec(config),
        arch=arch_spec(config),
    )
    opt = train["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError("nemotron_h_lm: unknown optimizer %r" % opt["name"])

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


def d_inner(config):
    return config["mamba_num_heads"] * config["mamba_head_dim"]


def mamba_params(config):
    """The two projections of one Mamba-2 block: in (z, x, B, C, dt) and out."""
    d = config["hidden_size"]
    in_width = (
        2 * d_inner(config) + 2 * config["n_groups"] * config["ssm_state_size"]
        + config["mamba_num_heads"]
    )
    return d * in_width + d_inner(config) * d


def attention_params(config):
    """q, k, v and the out projection of the attention block."""
    d, hd = config["hidden_size"], config["head_dim"]
    return 2 * d * config["num_attention_heads"] * hd + 2 * d * config["num_key_value_heads"] * hd


def routed_experts_a_token(config):
    """Expert pairs of matmuls a token meets HERE, expected under balanced
    routing: its ``num_experts_per_tok`` choices fall on the held
    ``n_routed_experts`` of the ``router_experts`` with that share
    (22 x 8 / 512 = 0.34)."""
    return (
        config["num_experts_per_tok"] * config["n_routed_experts"]
        / config["share"]["router_experts"]
    )


def expert_block_params(config):
    """What multiplies a token in one expert block: the router at its whole
    width, both latent projections, the shared expert's two matrices, and the
    expected ``routed_experts_a_token`` routed experts of two matrices at the
    latent's width."""
    d, latent = config["hidden_size"], config["moe_latent_size"]
    return (
        d * config["share"]["router_experts"] + 2 * d * latent
        + 2 * d * config["moe_shared_expert_intermediate_size"]
        + routed_experts_a_token(config) * 2 * latent * config["moe_intermediate_size"]
    )


def matmul_params(config):
    """Parameters that multiply every token on this chip, block by block of
    its kind, and the head over the slice."""
    return (
        letters(config, "M") * mamba_params(config)
        + letters(config, "*") * attention_params(config)
        + letters(config, "E") * expert_block_params(config)
        + config["hidden_size"] * config["vocab_size"]
    )


def _as_ssm(config):
    """The Mamba-2 layer's sizes under ``ssm_lm.py``'s names."""
    return {
        "mamba_n_heads": config["mamba_num_heads"], "mamba_d_head": config["mamba_head_dim"],
        "mamba_n_groups": config["n_groups"], "mamba_d_state": config["ssm_state_size"],
        "mamba_chunk_size": config["chunk_size"],
    }


def scan_forward_flops_per_token(config):
    """``ssm_lm.scan_forward_flops_per_token`` at this layer's chunk, heads
    and groups: ``C B^T`` a group and ``(L o C B^T)(dt x)`` inside a chunk, half
    of each masked away, the chunk's state and what it inherits."""
    return ssm_lm.scan_forward_flops_per_token(_as_ssm(config))


def attention_forward_flops(config, sequences):
    """Causal attention's forward over ``sequences`` sequences, the attention
    blocks only: two matmuls of 2*T*T*D per head, half of each masked."""
    t = config["train"]["seq_len"]
    return (
        2.0 * sequences * config["num_attention_heads"] * t * t
        * config["head_dim"] * letters(config, "*")
    )


def flops_per_item(config):
    """As ``transformer_lm.flops_per_item``: 6 per matrix-multiplied parameter
    a token meets (the routed experts at their expected share), three times the
    attention blocks' causal forward, three times the chunked scan's forward of
    the Mamba-2 blocks. Recomputation under remat, the convolution, norms,
    gates, the softmax, the sort and the optimizer are not counted."""
    t = config["train"]["seq_len"]
    return (
        6.0 * matmul_params(config)
        + 3.0 * attention_forward_flops(config, 1) / t
        + 3.0 * scan_forward_flops_per_token(config) * letters(config, "M")
    )


def kernel_flops(config, sequences):
    """What the flash kernels execute (``transformer_lm.kernel_flops``)."""
    return 3.5 * attention_forward_flops(config, sequences)


def ssm_scan_flops(config, tokens):
    """What the scans have to compute for ``tokens`` tokens, all Mamba-2
    blocks, forward and backward (``ssm_lm.ssm_scan_flops``)."""
    return 3.0 * scan_forward_flops_per_token(config) * tokens * letters(config, "M")


def ssm_scan_bytes(config, tokens):
    """The least HBM traffic of that work (``ssm_lm.ssm_scan_bytes``: x, dt,
    B, C and y once each way, and their gradients) over the Mamba-2 blocks."""
    one_layer = dict(_as_ssm(config), layer_types=["mamba"])
    return ssm_lm.ssm_scan_bytes(one_layer, tokens) * letters(config, "M")


def moe_kernel_flops(config, tokens):
    """What the grouped matmuls have to compute for ``tokens`` tokens, all
    expert blocks: up and down (an ungated expert's TWO matrices, latent x
    width) over the rows that fall on held experts (``routed_experts_a_token``
    a token, expected), forward and both gradients. What remat computes a
    second time does not count."""
    rows = tokens * routed_experts_a_token(config)
    return (
        6.0 * 2 * rows * config["moe_latent_size"] * config["moe_intermediate_size"]
        * letters(config, "E")
    )


def moe_kernel_bytes(config, tokens):
    """The least HBM traffic of that work: each of the six grouped matmuls a
    block reads its two operands and writes its result once, all bfloat16
    (``moe_lm.moe_kernel_bytes`` over the held rows and the held banks, two
    matrices an expert)."""
    rows = tokens * routed_experts_a_token(config)
    latent, f, e = (
        config["moe_latent_size"], config["moe_intermediate_size"], config["n_routed_experts"]
    )
    return 6.0 * (rows * latent * 2 + rows * f * 2 + e * latent * f * 2) * letters(config, "E")


def expert_blocks(config):
    return [i for i, c in enumerate(config["hybrid_override_pattern"]) if c == "E"]


def check(config, state, seed):
    """On one seeded sequence, with the trained parameters and the trained
    bias: logits and the cross-entropy against the plain reference computed
    with the program's choice of experts (``lfm2_lm.py``'s form: a scan and an
    attention layer carry a token's other expert into its neighbours'
    streams); the router's logits, scores and choices block by block and token
    by token; the bias the program leaves behind against the reference's rule
    on the program's counts; the first Mamba-2 block's mixer with its trained
    parameters, the norm by group, and the scan alone at the cell's chunk and
    groups against the step-by-step recurrence; then the flash kernels at the
    step's own shape and the grouped matmul at the held rows' shapes."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import nemotron_h_lm as reference
    from edl_tpu.train import cross_entropy_loss

    n = config["check"]["sample_items"]
    t = _items(config, seed + 7, n)
    # run.py hands over plain arrays on one device: no second copy of 9 GB
    params, stats, apply_fn = state.params, state.batch_stats, state.apply_fn
    del state
    tokens, targets = t[:, :-1], t[:, 1:]
    blocks = expert_blocks(config)

    @jax.jit
    def program(params, stats, tokens, targets):
        logits, left = apply_fn(
            {"params": params, "batch_stats": stats}, tokens,
            mutable=["intermediates", "batch_stats", "metrics"],
        )
        ce, _ = cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
        )
        seen = [left["intermediates"]["layer_%d" % i]["moe"] for i in blocks]
        sown = [left["metrics"]["layer_%d" % i]["moe"] for i in blocks]
        return logits, ce, {
            "experts": jnp.stack([p["top_idx"][0] for p in seen]),
            "router_logits": jnp.stack([p["router_logits"][0] for p in seen]),
            "router_in": jnp.stack([p["router_in"][0] for p in seen]),
            "bias_after": jnp.stack([
                left["batch_stats"]["layer_%d" % i]["moe"]["router_bias"] for i in blocks
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
            for j, i in enumerate(blocks)
        ])

    got_logits, got_ce, routed = program(params, stats, tokens, targets)
    with jax.default_matmul_precision("highest"):
        want_logits, want_ce, info = plain(
            params, stats, tokens, targets, routed["experts"]
        )
    bias = jnp.stack([stats["layer_%d" % i]["moe"]["router_bias"] for i in blocks])
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
        params["layer_%d" % i]["moe"]["router"]["kernel"] for i in blocks
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
    flip_share = max(flips_a_layer)  # judged: the block where most tokens flip
    widest_flip = float(jnp.max(jnp.where(differs, info["margin"], 0.0)))
    rel = float(jnp.max(jnp.abs(got_logits - want_logits)) / jnp.max(jnp.abs(want_logits)))
    finite = bool(jnp.isfinite(got_logits).all())
    rows_held = [float(v) for v in routed["rows_held"]]
    rows_dropped = float(jnp.sum(routed["rows_dropped"]))
    del got_logits, want_logits, stats, info, routed
    loss_rel = abs(float(got_ce) - float(want_ce)) / abs(float(want_ce))

    steps, b = config["train"]["seq_len"], config["train"]["batch_per_chip"]
    first = config["hybrid_override_pattern"].index("M")
    mixer = mixer_vs_reference(config, params["layer_%d" % first]["mamba"], seed, steps)
    del params
    scan = ssm_lm.scan_vs_reference(_as_ssm(config), seed, steps)
    kernel = ssm_lm.kernel_vs_reference(
        seed, b, config["num_attention_heads"], config["num_key_value_heads"],
        steps, config["head_dim"], config["head_dim"] ** -0.5,
    )
    held_rows = dict(
        num_experts=config["n_routed_experts"], num_experts_per_tok=1,
        hidden_size=config["moe_latent_size"],
        intermediate_size=config["moe_intermediate_size"],
    )  # the held groups of b * T * k / E rows expected, at the latent's width
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
        and mixer["rel_err"] <= MIXER_REL_TOL
        and scan["max_rel_err"] <= SCAN_REL_TOL
        and scan["state_rms_err"] <= STATE_RMS_TOL
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
        "sample_items": n, "mixer": mixer, "mixer_rel_tol": MIXER_REL_TOL,
        "scan": scan, "scan_rel_tol": SCAN_REL_TOL, "state_rms_tol": STATE_RMS_TOL,
        "kernel": kernel, "kernel_rel_tol": KERNEL_REL_TOL,
        "grouped_matmul": gmm, "grouped_matmul_rel_tol": GMM_REL_TOL,
    }


def mixer_vs_reference(config, mamba_params, seed, t, mixer=None):
    """The program's ``Mamba2Mixer`` at the cell's groups against
    ``reference.mamba_mixer`` (the recurrence a step at a time, the gated norm
    over each group's channels) with the same parameters on one seeded
    ``[1, t, hidden]`` input (unit normal, as an RMSNorm leaves it). ``mixer``
    replaces the program's (the tests' wrong programs)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import nemotron_h_lm as reference
    from edl_tpu.models import Mamba2Mixer

    x = jax.random.normal(
        jax.random.PRNGKey(seed % (2 ** 31)), (1, t, config["hidden_size"]), jnp.bfloat16
    )
    if mixer is None:
        mixer = Mamba2Mixer(
            mamba_spec(config), jnp.bfloat16, config["layer_norm_epsilon"]
        ).apply
    got = jax.jit(lambda p, x: mixer({"params": p}, x))(mamba_params, x)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: reference.mamba_mixer(config, p, x))(mamba_params, x)
    return {"shape": [1, t, config["hidden_size"]], "groups": config["n_groups"],
            "rel_err": _rel(got, want)}
