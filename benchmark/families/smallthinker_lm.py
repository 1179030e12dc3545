"""Family ``smallthinker_lm``: the program's ``TransformerLM`` as one chip's
share of a SmallThinker decoder (PowerInfer's SmallThinker-21BA3B-Instruct,
arXiv:2507.20984) — a norm before each branch and none after; attention at 28
query heads over 4 key heads of 128 without a norm or a gate, a window with
rotary positions in three layers of four and the whole sequence with no
position term in the fourth; in EVERY layer an expert layer **routed from the
block's own input, before the attention branch**
(``models/moe.py:MoESpec.route_from = "block_input"``), top-k of the logits
under a softmax over the chosen ones, ReLU-gated experts of three matrices,
**the experts this chip holds** (``DroplessMoE(held=...)`` over
``ops/grouped_matmul.py``), no shared expert; both auxiliary losses; an untied
head over a slice of the vocabulary. Built from a file that keeps the published
``config.json`` keys.

See ``resnet_vd.py`` for what a family is. The distinct batches and the start
are ``solar_lm.py``'s (uniform ids of the held slice, no batch twice); the
window's pair counts and the count of the keys a kernel sees ``afmoe_lm.py``'s;
the routing comparison ``moe_lm.py``'s rule on the logits (a flip only where
the reference is nearer a tie than the logits differ), in ``lfm2_lm.py``'s form
(the reference computes with the program's choice and each choice is judged
against the reference's own); the grouped matmul's check ``moe_lm.py``'s.
"""

from __future__ import annotations

import numpy as np

from benchmark.families import afmoe_lm
from benchmark.families.afmoe_lm import (  # noqa: F401 — the family's interface
    MEMBERSHIP_REL_TOL,
    ROUTER_ARITHMETIC_REL_TOL,
    TRACE_KERNELS,
    kernel_membership,
)
from benchmark.families.moe_lm import (  # noqa: F401 — the family's interface
    AUX_REL_TOL,
    GMM_REL_TOL,
    MOE_TRACE_KERNELS,
    grouped_matmul_vs_reference,
)
from benchmark.families.solar_lm import as_drawn, host_batches, started  # noqa: F401
from benchmark.families.ssm_lm import _rel
from benchmark.families.transformer_lm import KERNEL_REL_TOL, LOSS_REL_TOL, _items

# Every limit below lies between two readings: the largest the program gave on
# the chip over this PR's seeds (TPU v5 lite; PERF.md section 6, PR 59: after
# the cell's own window on seeds 3000005911-956, and freshly drawn parameters
# with the head as drawn on seed 3000005931), and what the same program reads
# in the nearest precision below, ``float8_e4m3fn``: at the cell's own size on
# the chip (``bench_results/smallthinker_precision_below.py``, seed 3000005931)
# and at a width of 256 on the CPU (``benchmark/tests/test_smallthinker_lm.py``).
# The 8-bit program hands its kernels bfloat16 operands (no Pallas kernel here
# takes an 8-bit float).
#
# Logits of the program (bfloat16 operands, float32 accumulation, float32
# logits) against the float32 reference computed with the program's own choice
# of experts, as max |difference| over max |reference| over every token. Read:
# 0.0015 to 0.0030 after the window, 0.0073 to 0.0079 freshly drawn; 8-bit 0.109
# to 0.121 on the chip (0.14 at the toy's width). The limit is 5.7 times the
# largest sound reading and 2.4 times under the 8-bit one.
LOGITS_REL_TOL = 0.045
# The routers' logits of the program against ``x W_r`` on the REFERENCE's block
# input, layer by layer (eight deep), as max |difference| over max |reference|:
# a float32 router whose operand is the un-normed bfloat16 residual stream as
# the block received it. Read: 0.0043 to 0.0055 after the window, 0.0075 to
# 0.0077 freshly drawn; 8-bit 0.102 to 0.114. A router handed the normed input,
# or the stream after attention, reads another tensor: 0.2 and more
# (``test_smallthinker_lm.py``).
ROUTER_LOGITS_REL_TOL = 0.04
# Tokens whose choice of experts may differ from the one the reference makes
# for itself on the same stream, in the layer where most do, by ``moe_lm.py``'s
# rule: a flip is right only where the reference's margin (the 6th logit's lead
# over the 7th) is at most twice the largest difference between the token's own
# program and reference logits; any other difference fails the check as
# ``tokens_misrouted``. 64 logits, the 6th and 7th 0.03 apart on average: read
# 3.8 to 4.4% in the eighth layer after the window (1.1% in the first), 3.3 to
# 3.7% freshly drawn; 8-bit 40 to 46%.
ROUTE_FLIP_LIMIT = 0.12
# The share of the held rows' gate values the ReLU zeroes, as the program's
# gauge reads it a layer against the reference's count on its own stream, as
# |difference|: both count signs of ``[rows, 768]`` products near 0.5, and what
# differs is the sign of the values nearest zero under bfloat16 operands: read
# 2.3e-5 to 3.3e-5 on the chip (12,288 rows a layer), 0.0017 at the toy's 96
# rows. No precision's limit (8-bit reads 4e-4): a gauge over the buffer's rows
# that are nobody's (half of it here) reads 0.25 away, one over a SiLU's values
# 0.2 and more (``test_smallthinker_lm.py``).
GATE_DEAD_ABS_TOL = 0.02
# ``ROUTER_ARITHMETIC_REL_TOL`` (``afmoe_lm.py``'s, 1e-4: the router's own
# arithmetic on the operand it sowed) reads 1.0e-7 to 3.3e-7 here and a bfloat16
# router 3.2e-3 to 3.5e-3. It also read 1.05e-3 to 1.9e-3 in every layer but the
# first on this PR's first runs, with a float32 router: XLA made the block
# input's bfloat16 sum again in the router's fusion and in the sown copy's, one
# rounded and one not (``models/moe.py:_as_stored`` holds it to one array).


def head_dim(config):
    return config["head_dim"]


def arch_spec(config):
    from edl_tpu.models import ArchSpec

    windows, rotated = config["sliding_window_layout"], config["rope_layout"]
    if not len(windows) == len(rotated) == config["num_hidden_layers"]:
        raise ValueError("smallthinker_lm: one entry of each layout a layer")
    if list(windows) != list(rotated) or config["rope_scaling"] is not None:
        raise ValueError(
            "smallthinker_lm: rotary positions in the windowed layers and in no "
            "other, unscaled, as published"
        )
    return ArchSpec(
        layer_types=tuple(
            "sliding_attention" if windowed else "attention" for windowed in windows
        ),
        head_dim=config["head_dim"], rope="sliding",
        rope_theta=float(config["rope_theta"]),
        sliding_window=config["sliding_window_size"],
        tie_embeddings=config["tie_word_embeddings"],
    )


def moe_spec(config):
    from edl_tpu.models import MoESpec

    share, layers = config["share"], config["num_hidden_layers"]
    if not (config["moe_primary_router_apply_softmax"] and config["norm_topk_prob"]):
        raise ValueError("smallthinker_lm: a softmax over the chosen logits, as published")
    return MoESpec(
        num_experts=share["router_experts"],
        top_k=config["moe_num_active_primary_experts"],
        d_ff=config["moe_ffn_hidden_size"], norm_topk_prob=True,
        # the trainer sums what the layers sow; the terms are means over the layers
        aux_weight=config["train"]["load_balance_coef"] / layers,
        z_weight=config["train"]["router_z_coef"] / layers,
        score_func="softmax", activation="relu", route_from="block_input",
        held=(share["experts_first"], config["moe_num_primary_experts"]),
    )


def build(config, global_batch, seed):
    import jax.numpy as jnp
    import optax

    from edl_tpu.models import TransformerLM
    from edl_tpu.train import cross_entropy_loss

    train = config["train"]
    if train["compute_dtype"] not in ("bfloat16", "float32"):
        raise ValueError("smallthinker_lm: compute_dtype %r" % train["compute_dtype"])
    model = started(TransformerLM, train["start"])(
        dtype=getattr(jnp, train["compute_dtype"]),
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        num_layers=config["num_hidden_layers"],
        d_ff=config["moe_ffn_hidden_size"],  # of no layer: every block is an expert layer
        remat=train["remat"], remat_policy=train["remat_policy"],
        norm_eps=config["rms_norm_eps"], moe=moe_spec(config), arch=arch_spec(config),
    )
    opt = train["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError("smallthinker_lm: unknown optimizer %r" % opt["name"])

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
    """q and the out projection at the query heads' width, k and v at the key
    heads'."""
    d, hd = config["hidden_size"], config["head_dim"]
    return 2 * d * hd * (config["num_attention_heads"] + config["num_key_value_heads"])


def routed_experts_a_token(config):
    """Expert matmuls a token meets HERE, expected under balanced routing: its
    ``moe_num_active_primary_experts`` choices fall on the held
    ``moe_num_primary_experts`` of the ``router_experts`` with that share
    (6 x 8 / 64 = 3/4)."""
    return (
        config["moe_num_active_primary_experts"] * config["moe_num_primary_experts"]
        / config["share"]["router_experts"]
    )


def matmul_params(config):
    """Parameters that multiply every token on this chip: attention's four
    projections, the router at its whole width and the expected
    ``routed_experts_a_token`` experts of three matrices in every layer, and
    the head over the slice."""
    d, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    layer = (
        attention_params(config) + d * config["share"]["router_experts"]
        + routed_experts_a_token(config) * 3 * d * f
    )
    return config["num_hidden_layers"] * layer + d * config["vocab_size"]


def _afmoe_keys(config):
    """``config`` under the two keys ``afmoe_lm.py``'s counts read: its window
    and its layers' kinds (the pair counts are the same arithmetic: T^2 / 2 a
    head under the causal mask, T W - W^2 / 2 under a window of W < T)."""
    return dict(
        config, sliding_window=config["sliding_window_size"],
        layer_types=[
            "sliding_attention" if windowed else "full_attention"
            for windowed in config["sliding_window_layout"]
        ],
    )


def layer_attention_forward_flops(config, sequences, windowed):
    return afmoe_lm.layer_attention_forward_flops(_afmoe_keys(config), sequences, windowed)


def attention_forward_flops(config, sequences):
    return afmoe_lm.attention_forward_flops(_afmoe_keys(config), sequences)


def flops_per_item(config):
    """As ``transformer_lm.flops_per_item``: 6 per matrix-multiplied parameter
    a token meets (the routed experts at their expected share) and three times
    the attention forward over visible pairs, the window counted.
    Recomputation under remat, the sort, the gathers, norms, the rotation, the
    softmaxes, the auxiliary losses and the optimizer are not counted."""
    t = config["train"]["seq_len"]
    return 6.0 * matmul_params(config) + 3.0 * attention_forward_flops(config, 1) / t


def kernel_flops(config, sequences):
    """What all the layers' flash kernels execute: ``afmoe_lm.kernel_flops``
    (the forward, and a backward that recomputes the scores; visible pairs)."""
    return afmoe_lm.kernel_flops(_afmoe_keys(config), sequences)


def kind_kernel_flops(config, sequences, windowed):
    return afmoe_lm.kind_kernel_flops(_afmoe_keys(config), sequences, windowed)


def kind_kernel_bytes(config, sequences, windowed):
    return afmoe_lm.kind_kernel_bytes(_afmoe_keys(config), sequences, windowed)


def moe_kernel_flops(config, tokens):
    """What the grouped matmuls have to compute for ``tokens`` tokens, all
    layers: gate, up and down over the rows that fall on held experts
    (``routed_experts_a_token`` a token, expected), forward and both gradients.
    A column the ReLU zeroes is computed all the same, and counted."""
    rows = tokens * routed_experts_a_token(config)
    return (
        6.0 * 3 * rows * config["hidden_size"] * config["moe_ffn_hidden_size"]
        * config["num_hidden_layers"]
    )


def moe_kernel_bytes(config, tokens):
    """The least HBM traffic of that work (``moe_lm.moe_kernel_bytes`` over the
    held rows and the held banks)."""
    rows = tokens * routed_experts_a_token(config)
    d, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    banks = config["moe_num_primary_experts"] * d * f * 2
    return 9.0 * (rows * d * 2 + rows * f * 2 + banks) * config["num_hidden_layers"]


def routing_vs_reference(routed, info, weights):
    """The routers layer by layer and token by token ([L, N]): ``routed`` is what
    the program's expert layers sowed (``experts``, ``router_logits``,
    ``router_in``), ``info`` the reference's own on ITS block inputs (both sides
    computed every layer with the program's experts), ``weights`` the routers'
    kernels [L, D, E]. A flip is right only where the reference's margin is at
    most twice the largest difference between the token's own program and
    reference logits."""
    import jax
    import jax.numpy as jnp

    differs = jnp.any(
        jnp.sort(routed["experts"], axis=-1) != jnp.sort(info["experts"], axis=-1),
        axis=-1,
    )
    moved = jnp.max(jnp.abs(routed["router_logits"] - info["router_logits"]), axis=-1)
    # the router's arithmetic on its own operand, and what a bfloat16 router
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
            jnp.max(moved) / jnp.max(jnp.abs(info["router_logits"]))
        ),
        "router_logits_rel_err_by_layer": [
            float(v) for v in jnp.max(moved, axis=-1)
            / jnp.max(jnp.abs(info["router_logits"]), axis=(-2, -1))
        ],
        "router_arithmetic_rel_err": float(
            jnp.max(jnp.abs(routed["router_logits"] - exact)) / largest
        ),
        "router_arithmetic_rel_err_by_layer": [
            float(v) for v in
            jnp.max(jnp.abs(routed["router_logits"] - exact), axis=(-2, -1)) / largest
        ],
        "router_arithmetic_rel_err_of_a_bfloat16_router": float(
            jnp.max(jnp.abs(coarse - exact)) / largest
        ),
        "flipped_share": max(flips_a_layer),  # judged: the layer where most tokens flip
        "flipped_share_by_layer": flips_a_layer,
        "widest_flipped_margin": float(jnp.max(jnp.where(differs, info["margin"], 0.0))),
        "tokens_misrouted": int(jnp.sum(differs & (info["margin"] > 2.0 * moved))),
    }


def check(config, state, seed):
    """On one seeded sequence, with the trained parameters: logits, the
    cross-entropy and both auxiliary terms against the plain reference computed
    with the program's choice of experts; the routers' logits layer by layer
    against ``x W_r`` on the reference's block input, their own arithmetic on
    the operand the program sowed, and the choices token by token; the share of
    the gate the ReLU zeroes against the reference's count; then the windowed
    and the full kernel at the step's own shape, each against dense float32
    attention in row blocks and against the exact count of the keys a query
    sees; and the grouped matmul at the held rows' shape."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import smallthinker_lm as reference
    from edl_tpu.train import cross_entropy_loss

    n = config["check"]["sample_items"]
    t = _items(config, seed + 7, n)
    # run.py hands over plain arrays on one device: no second copy
    params, apply_fn = state.params, state.apply_fn
    del state
    tokens, targets = t[:, :-1], t[:, 1:]
    blocks = ["layer_%d" % i for i in range(config["num_hidden_layers"])]

    @jax.jit
    def program(params, tokens, targets):
        logits, left = apply_fn(
            {"params": params}, tokens, mutable=["losses", "intermediates", "metrics"]
        )
        ce, _ = cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
        )
        terms = [left["losses"][name]["moe"] for name in blocks]
        seen = [left["intermediates"][name]["moe"] for name in blocks]
        sown = [left["metrics"][name]["moe"] for name in blocks]
        return logits, ce, {
            "load_balance": sum(p["load_balance"][0] for p in terms),
            "router_z": sum(p["router_z"][0] for p in terms),
        }, {
            "experts": jnp.stack([p["top_idx"][0] for p in seen]),
            "router_logits": jnp.stack([p["router_logits"][0] for p in seen]),
            "router_in": jnp.stack([p["router_in"][0] for p in seen]),
            "rows_held": jnp.stack([p["moe_rows_held"][0] for p in sown]),
            "rows_dropped": jnp.stack([p["moe_rows_dropped"][0] for p in sown]),
            "gate_dead": jnp.stack([p["moe_gate_dead"][0] for p in sown]),
        }

    @jax.jit
    def plain(params, tokens, targets, chosen):
        logits, info = reference.forward(config, params, tokens, chosen)
        return logits, reference.cross_entropy(logits, targets), info

    got_logits, got_ce, got_aux, routed = program(params, tokens, targets)
    with jax.default_matmul_precision("highest"):
        want_logits, want_ce, info = plain(params, tokens, targets, routed["experts"])
    routing = routing_vs_reference(
        routed, info, jnp.stack([params[name]["moe"]["router"]["kernel"] for name in blocks])
    )
    rel = _rel(got_logits, want_logits)
    finite = bool(jnp.isfinite(got_logits).all())
    nonzero = float(jnp.max(jnp.abs(want_logits))) > 0
    rows_held = [float(v) for v in routed["rows_held"]]
    rows_dropped = float(jnp.sum(routed["rows_dropped"]))
    gate_dead = [float(v) for v in routed["gate_dead"]]
    want_dead = [float(v) for v in info["gate_dead"]]
    dead_err = max(abs(a - b) for a, b in zip(gate_dead, want_dead))

    def relative(got, want):
        return abs(float(got) - float(want)) / abs(float(want))

    loss_rel = relative(got_ce, want_ce)
    aux_rel = {name: relative(got_aux[name], info[name]) for name in got_aux}
    aux = {name: float(v) for name, v in got_aux.items()}
    want_aux = {name: float(info[name]) for name in got_aux}
    del got_logits, want_logits, params, info, routed

    b, steps = config["train"]["batch_per_chip"], config["train"]["seq_len"]
    shape = (
        b, config["num_attention_heads"], config["num_key_value_heads"], steps,
        config["head_dim"],
    )
    window = config["sliding_window_size"]
    kernels = {
        "window": kernel_vs_reference(seed, *shape, window),
        "full": kernel_vs_reference(seed, *shape, None),
    }
    members = {
        "window": kernel_membership(*shape, window),
        "full": kernel_membership(*shape, None),
    }
    held_rows = dict(
        num_experts=config["moe_num_primary_experts"], num_experts_per_tok=1,
        hidden_size=config["hidden_size"], intermediate_size=config["moe_ffn_hidden_size"],
    )  # the held groups of b * T * k / E rows expected: what the held experts see
    gmm = grouped_matmul_vs_reference(
        held_rows, seed, int(b * steps * routed_experts_a_token(config))
    )
    ok = (
        finite and nonzero and rel <= LOGITS_REL_TOL and loss_rel <= LOSS_REL_TOL
        and max(aux_rel.values()) <= AUX_REL_TOL
        and routing["router_logits_rel_err"] <= ROUTER_LOGITS_REL_TOL
        and routing["router_arithmetic_rel_err"] <= ROUTER_ARITHMETIC_REL_TOL
        and routing["tokens_misrouted"] == 0 and routing["flipped_share"] <= ROUTE_FLIP_LIMIT
        and rows_dropped == 0 and dead_err <= GATE_DEAD_ABS_TOL
        and all(k["max_rel_err"] <= KERNEL_REL_TOL for k in kernels.values())
        and all(m["max_rel_err"] <= MEMBERSHIP_REL_TOL for m in members.values())
        and gmm["max_rel_err"] <= GMM_REL_TOL
    )
    return {
        "ok": bool(ok), "logits_rel_err": rel, "logits_rel_tol": LOGITS_REL_TOL,
        "logits_nonzero": nonzero,
        "loss": float(got_ce), "reference_loss": float(want_ce),
        "loss_rel_err": loss_rel, "loss_rel_tol": LOSS_REL_TOL,
        "aux": aux, "reference_aux": want_aux,
        "aux_rel_err": aux_rel, "aux_rel_tol": AUX_REL_TOL,
        **routing,
        "router_logits_rel_tol": ROUTER_LOGITS_REL_TOL,
        "router_arithmetic_rel_tol": ROUTER_ARITHMETIC_REL_TOL,
        "flipped_limit": ROUTE_FLIP_LIMIT,
        "rows_held": rows_held, "rows_dropped": rows_dropped,
        "gate_dead": gate_dead, "reference_gate_dead": want_dead,
        "gate_dead_abs_err": dead_err, "gate_dead_abs_tol": GATE_DEAD_ABS_TOL,
        "sample_items": n, "kernel": kernels, "kernel_rel_tol": KERNEL_REL_TOL,
        "kernel_membership": members, "membership_rel_tol": MEMBERSHIP_REL_TOL,
        "grouped_matmul": gmm, "grouped_matmul_rel_tol": GMM_REL_TOL,
    }


def kernel_vs_reference(seed, b, h, h_kv, t, d, window):
    """``ops.attention.attention`` as the step calls it (value and q/k/v
    gradients, causal, ``window`` or none, bfloat16) against the reference's
    dense float32 attention in row blocks on the same inputs, every head at
    once (``afmoe_lm.kernel_vs_reference`` walks four heads at a time, which a
    group of seven does not divide into)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.smallthinker_lm import masked_attention
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
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = value_and_grads(lambda q, k, v: masked_attention(q, k, v, window))(
            f32(q), f32(k), f32(v), f32(w)
        )
    errs = {}
    for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        errs[name] = (
            float(np.max(np.abs(a - r)) / np.max(np.abs(r)))
            if np.isfinite(a).all() else float("inf")
        )
    return {"shape": [b, h, h_kv, t, d], "window": window,
            "max_rel_err": max(errs.values()), **errs}
