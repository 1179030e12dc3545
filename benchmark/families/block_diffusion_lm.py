"""Family ``block_diffusion_lm``: the program's ``TransformerLM`` as one chip's
share of an SDAR decoder (JetLM's SDAR-30B-A3B-Chat, ``model_type``
``sdar_moe``; arXiv:2510.06303) **under the block-diffusion training step**
(BD3-LM, arXiv:2503.09573): a sequence of ``L`` ids and beside it its noised
copy, ``2 L`` positions through every layer under the block-diffusion mask
(``ArchSpec.block_diffusion``; ``ops/attention.py``'s third mask kind, as spans
of the ``flash2`` kernels), rotary positions that repeat across the halves, the
head over the noised half alone, and a ``1 / t``-weighted loss
(``train/step.py:make_block_diffusion_loss``). The block is a pre-norm decoder
block: attention at 32 query heads over 4 key heads of 128 with an RMSNorm over
each head's q and k; in EVERY layer an expert layer, softmax over all the
model's experts, top-k renormalised over the chosen, SiLU-gated experts of
three matrices, **the experts this chip holds** (``DroplessMoE(held=...)``), no
shared expert; the load-balance term; an untied head over a slice of the
vocabulary. Built from a file that keeps the published ``config.json`` keys.

**An item is a data token**: ``L`` a sequence, not the ``2 L`` positions the
blocks see; ``flops_per_item`` counts both copies' work.

See ``resnet_vd.py`` for what a family is. The batches are noised **through the
program's** ``edl_tpu/data/block_diffusion.py``, as a user's ``data_fn`` would;
the start is ``solar_lm.py``'s; the routing comparison ``moe_lm.py``'s rule in
``smallthinker_lm.py``'s form; the count of the keys a kernel sees
``afmoe_lm.py``'s method, on this mask's own integers; the grouped matmul's
check ``moe_lm.py``'s.
"""

from __future__ import annotations

import numpy as np

from benchmark.families.afmoe_lm import (  # noqa: F401 — the family's interface
    MEMBERSHIP_REL_TOL,
    ROUTER_ARITHMETIC_REL_TOL,
    TRACE_KERNELS,
)
from benchmark.families.moe_lm import (  # noqa: F401 — the family's interface
    AUX_REL_TOL,
    GMM_REL_TOL,
    MOE_TRACE_KERNELS,
    grouped_matmul_vs_reference,
)
from benchmark.families.smallthinker_lm import routing_vs_reference
from benchmark.families.solar_lm import as_drawn, started  # noqa: F401
from benchmark.families.ssm_lm import _rel
from benchmark.families.transformer_lm import KERNEL_REL_TOL, LOSS_REL_TOL

# Every limit below lies between two readings (PERF.md section 6, PR 61): the
# largest the program gave on the chip over this PR's seeds (TPU v5 lite: after
# the cell's own window at its AdamW 1e-5 on seeds 3000006111-112, 121-126 and
# 201-209, and at a tenth of that rate on 141-147 and 181-186; freshly drawn
# parameters with the head as drawn on seed 3000006131), and what the same
# program reads in the nearest precision below, ``float8_e4m3fn``, at the
# cell's own size on the chip
# (``bench_results/sdar_precision_below.py``, seed 3000006131) and at a width
# of 256 on the CPU (``benchmark/tests/test_block_diffusion_lm.py``). The 8-bit
# program hands its kernels bfloat16 operands (no Pallas kernel here takes an
# 8-bit float).
#
# Logits of the L noised positions (bfloat16 operands, float32 accumulation,
# float32 logits) against the float32 reference computed with the program's own
# choice of experts, as max |difference| over max |reference|. Read: 0.0004 to
# 0.0051 after the window (0.0022 to 0.0045 at 1e-5), 0.0054 freshly drawn;
# 8-bit 0.0726. The limit is 3.7 times the largest sound reading and 3.6 times
# under the 8-bit one. (A start
# that sharpens attention, the per-head norms' scales at 2 to 4, reads 0.03 to
# 0.34 in the stated precision at a width of 512 on the CPU: a softmax that
# falls on few keys carries bfloat16's rounding of its scores into whole rows,
# so the cell keeps the norms' scales as drawn.)
LOGITS_REL_TOL = 0.02
# The routers' logits of the program against ``N_2(h) W_r`` on the REFERENCE's
# stream, layer by layer (five deep), as max |difference| over max |reference|.
# Read: 0.0035 to 0.0075 after the window, 0.0051 freshly drawn; 8-bit 0.0714.
# The limit is 2.7 times the largest sound reading and 3.6 times under the
# 8-bit one.
ROUTER_LOGITS_REL_TOL = 0.02
# Positions whose choice of experts may differ from the one the reference makes
# for itself on the same stream, in the layer where most do, by ``moe_lm.py``'s
# rule: a flip is right only where the reference's margin (the 8th logit's lead
# over the 9th) is at most twice the largest difference between the position's
# own program and reference logits; any other difference fails the check as
# ``tokens_misrouted``. 128 logits, the 8th and 9th 0.02 apart on average, and
# the 4096 ``[MASK]`` positions of a sequence are one row that flips as one.
# Read: 0.058 to 0.126 after the window at the cell's AdamW 1e-5 (the first
# layer's, where the routers had moved most), 0.045 to 0.075 at a tenth of that
# rate, 0.050 freshly drawn; 8-bit 0.52. The limit is twice the largest sound
# reading and half the 8-bit one.
ROUTE_FLIP_LIMIT = 0.25
# The weighted loss (no limit of its own: ``transformer_lm.py``'s
# ``LOSS_REL_TOL``, 0.01) and the program's ``(x_t, labels, weights)`` against
# the reference's forward process on the same ``t`` and ``u``: equal ids, and
# the weights (``1 / t``, up to 1000) to float32's rounding, as |difference|
# over the reference's float64 weight. Read: 3.0e-8 to 6.0e-8 (half a float32
# ulp; PR 61's first chip runs judged the ABSOLUTE difference at 1e-6, which a
# weight of 100 already passes by rounding alone: 1.6e-6 and 5.8e-6, not
# correct); weights held in bfloat16 read 2e-3, in float16 2.4e-4.
FORWARD_PROCESS_REL_TOL = 1e-6


def spec(config):
    return config["train"]["block_diffusion"]


def arch_spec(config):
    from edl_tpu.models import ArchSpec, BlockDiffusionSpec

    if config["use_sliding_window"] or config["rope_scaling"] is not None or (
        config["attention_bias"]
    ):
        raise ValueError(
            "block_diffusion_lm: no window, unscaled rotary positions and no bias, as published"
        )
    bd = spec(config)
    return ArchSpec(
        head_dim=config["head_dim"], rope_theta=float(config["rope_theta"]),
        tie_embeddings=config["tie_word_embeddings"],
        block_diffusion=BlockDiffusionSpec(block=bd["block"], mask_id=bd["mask_id"]),
    )


def moe_spec(config):
    from edl_tpu.models import MoESpec

    share, layers = config["share"], config["num_hidden_layers"]
    if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"] or (
        config["hidden_act"] != "silu"
    ):
        raise ValueError(
            "block_diffusion_lm: a SiLU-gated expert layer in every block, as published"
        )
    return MoESpec(
        num_experts=share["router_experts"], top_k=config["num_experts_per_tok"],
        d_ff=config["moe_intermediate_size"], norm_topk_prob=config["norm_topk_prob"],
        # the trainer sums what the layers sow; the term is the mean over the layers
        aux_weight=config["train"]["load_balance_coef"] / layers, z_weight=0.0,
        score_func="softmax", held=(share["experts_first"], config["num_experts"]),
    )


def build(config, global_batch, seed):
    import jax.numpy as jnp
    import optax

    from edl_tpu.models import TransformerLM
    from edl_tpu.train import make_block_diffusion_loss

    train = config["train"]
    if train["compute_dtype"] not in ("bfloat16", "float32"):
        raise ValueError("block_diffusion_lm: compute_dtype %r" % train["compute_dtype"])
    model = started(TransformerLM, train["start"])(
        dtype=getattr(jnp, train["compute_dtype"]),
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        num_layers=config["num_hidden_layers"],
        d_ff=config["moe_intermediate_size"],  # of no layer: every block is an expert layer
        remat=train["remat"], remat_policy=train["remat_policy"],
        norm_eps=config["rms_norm_eps"], qk_norm="head", moe=moe_spec(config),
        arch=arch_spec(config),
    )
    opt = train["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError("block_diffusion_lm: unknown optimizer %r" % opt["name"])
    return {
        "model": model,
        "optimizer": optax.adamw(opt["lr"]),
        "loss": make_block_diffusion_loss(),
        # x_0 and then x_t: 2 L ids a sequence
        "sample_input": np.zeros((global_batch, 2 * train["seq_len"]), np.int32),
        "apply_kwargs": None,
        "items_per_step": global_batch * train["seq_len"],
    }


def clean_ids(config, seed, n):
    """``n`` sequences of ``seq_len`` ids, uniform over the held slice's rows
    but the ``[MASK]`` row (which the forward process alone writes)."""
    rs = np.random.default_rng(seed)
    bd = spec(config)
    if bd["mask_id"] != config["vocab_size"] - 1:
        raise ValueError("block_diffusion_lm: [MASK] is the last row of the held slice")
    return rs.integers(0, bd["mask_id"], (n, config["train"]["seq_len"])).astype(np.int32)


def host_batches(config, global_batch, seed, n_batches=None):
    """``train.distinct_batches`` batches (more than a run dispatches: no batch
    comes twice) of ``(tokens [B, 2 L], (labels [B, L], weights [B, L]))``:
    uniform clean ids from the seed, noised by the program's own
    ``data/block_diffusion.py`` at the batch's place in the data order."""
    from edl_tpu.data.block_diffusion import noised_batch

    bd = spec(config)
    if n_batches is None:
        n_batches = config["train"]["distinct_batches"]
    return [
        noised_batch(
            clean_ids(config, seed * 1000 + i, global_batch), seed, i,
            bd["block"], bd["mask_id"], bd["t_min"],
        )
        for i in range(n_batches)
    ]


def attention_params(config):
    """q and the out projection at the query heads' width, k and v at the key
    heads'."""
    d, hd = config["hidden_size"], config["head_dim"]
    return 2 * d * hd * (config["num_attention_heads"] + config["num_key_value_heads"])


def routed_experts_a_token(config):
    """Expert matmuls a POSITION meets here, expected under balanced routing:
    its ``num_experts_per_tok`` choices fall on the held ``num_experts`` of the
    ``router_experts`` with that share (8 x 16 / 128 = 1)."""
    return (
        config["num_experts_per_tok"] * config["num_experts"]
        / config["share"]["router_experts"]
    )


def layer_matmul_params(config):
    """Parameters that multiply every position of a layer on this chip:
    attention's four projections, the router at its whole width and the
    expected ``routed_experts_a_token`` experts of three matrices."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    return (
        attention_params(config) + d * config["share"]["router_experts"]
        + routed_experts_a_token(config) * 3 * d * f
    )


def visible_pairs(config):
    """(query, key) pairs a head sees in one sequence's ``2 L x 2 L`` rectangle,
    exactly: a position of block ``b`` of either half sees ``(b + 1) B`` keys
    (a clean one the clean keys of blocks ``<= b``; a noised one the clean keys
    of blocks ``< b`` and its own block's ``B`` noised keys), so each half sees
    ``B^2 (1 + 2 + ... + L / B) = L (L + B) / 2``."""
    length, block = config["train"]["seq_len"], spec(config)["block"]
    if length % block:
        raise ValueError("block_diffusion_lm: L in whole blocks")
    return length * (length + block)


def attention_forward_flops(config, sequences):
    """All layers' attention forward over ``sequences`` sequences: two matrix
    multiplications over the visible pairs, 2 * D operations a pair each."""
    return (
        4.0 * sequences * config["num_hidden_layers"] * config["num_attention_heads"]
        * visible_pairs(config) * config["head_dim"]
    )


def flops_per_item(config):
    """A DATA token's share of a step (``L`` a sequence; both copies' work): 6
    per matrix-multiplied parameter a position meets in the layers, for its
    clean and its noised position; 6 per parameter of the head, which reads the
    noised position alone; three times the attention forward over the mask's
    exact pair count. As ``transformer_lm.flops_per_item`` otherwise:
    recomputation under remat, the sort, the gathers, norms, the rotation, the
    softmaxes, the auxiliary loss and the optimizer are not counted."""
    length = config["train"]["seq_len"]
    return (
        6.0 * (
            2 * config["num_hidden_layers"] * layer_matmul_params(config)
            + config["hidden_size"] * config["vocab_size"]
        )
        + 3.0 * attention_forward_flops(config, 1) / length
    )


def kernel_flops(config, sequences):
    """What all the layers' flash kernels have to execute: the forward, and a
    backward that recomputes the scores (five matrix multiplications to the
    forward's two), over VISIBLE pairs only. A tile the kernels walk that holds
    few visible pairs (a noised block's own 1024 x 1024 tile holds 4096) is
    time, not work."""
    return 3.5 * attention_forward_flops(config, sequences)


def kernel_bytes(config, sequences):
    """The least HBM traffic of those kernels, bfloat16
    (``afmoe_lm.kind_kernel_bytes`` over ``2 L`` positions): the forward reads
    q, k, v and writes o; the backward reads q, k, v, dO and writes dq, dk and
    dv at q's width. k and v are read once a group of heads that share them."""
    positions, hd = 2 * config["train"]["seq_len"], config["head_dim"]
    wide = sequences * positions * config["num_attention_heads"] * hd * 2
    narrow = sequences * positions * config["num_key_value_heads"] * hd * 2
    return config["num_hidden_layers"] * (9 * wide + 6 * narrow)


def moe_kernel_flops(config, tokens):
    """What the grouped matmuls have to compute for ``tokens`` DATA tokens (two
    positions each), all layers: gate, up and down over the rows that fall on
    held experts (``routed_experts_a_token`` a position, expected), forward and
    both gradients."""
    rows = 2 * tokens * routed_experts_a_token(config)
    return (
        6.0 * 3 * rows * config["hidden_size"] * config["moe_intermediate_size"]
        * config["num_hidden_layers"]
    )


def moe_kernel_bytes(config, tokens):
    """The least HBM traffic of that work (``moe_lm.moe_kernel_bytes`` over the
    held rows and the held banks)."""
    rows = 2 * tokens * routed_experts_a_token(config)
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    banks = config["num_experts"] * d * f * 2
    return 9.0 * (rows * d * 2 + rows * f * 2 + banks) * config["num_hidden_layers"]


def check_batch(config, seed, n):
    """The check's own batch and its randomness: ``(x_0, t, u)``, drawn as
    ``host_batches`` draws (the program's ``noise_draws``), at a place of the
    data order no step was fed from."""
    from edl_tpu.data.block_diffusion import noise_draws

    bd = spec(config)
    x0 = clean_ids(config, seed + 7, n)
    t, u = noise_draws(seed + 7, 1 << 20, x0.shape, bd["block"], bd["t_min"])
    return x0, t, u


def forward_process_vs_reference(config, x0, t, u):
    """The program's ``noised`` against the reference's forward process on the
    same ``t`` and ``u``: ``x_t`` and the labels equal, the weights to float32."""
    from benchmark.reference import block_diffusion_lm as reference
    from edl_tpu.data.block_diffusion import noised

    bd = spec(config)
    tokens, (labels, weights) = noised(x0, t, u, bd["block"], bd["mask_id"])
    want_xt, want_m, want_w = reference.forward_process(x0, t, u, bd["block"], bd["mask_id"])
    length = x0.shape[1]
    return (tokens, labels, weights), {
        "tokens_differ": int(
            np.sum(tokens[:, :length] != x0) + np.sum(tokens[:, length:] != want_xt)
            + np.sum(labels != x0)
        ),
        "weights_rel_err": float(np.max(
            np.abs(weights.astype(np.float64) - want_w) / np.maximum(want_w, 1.0)
        )),
        "masked_share": float(np.mean(want_m)),
    }


def check(config, state, seed):
    """On one seeded sequence, with the trained parameters: the program's
    forward process against the reference's on the same draws; logits of the
    ``L`` noised positions, the weighted loss and the load-balance term against
    the plain reference computed with the program's choice of experts; the
    routers' logits layer by layer, their own arithmetic on the operand the
    program sowed, and the choices position by position; then the kernel under
    the block-diffusion mask at the step's own shape against dense float32
    attention in row blocks, forward and gradients, and against the exact count
    of the keys a query sees and of the queries that see a key; and the grouped
    matmul at the held rows' shape."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import block_diffusion_lm as reference
    from edl_tpu.train import make_block_diffusion_loss

    n = config["check"]["sample_items"]
    (tokens, labels, weights), process = forward_process_vs_reference(
        config, *check_batch(config, seed, n)
    )
    # run.py hands over plain arrays on one device: no second copy
    params, apply_fn = state.params, state.apply_fn
    del state
    blocks = ["layer_%d" % i for i in range(config["num_hidden_layers"])]
    head = make_block_diffusion_loss()

    @jax.jit
    def program(params, tokens, labels, weights):
        logits, left = apply_fn(
            {"params": params}, tokens, mutable=["losses", "intermediates", "metrics"]
        )
        loss, metrics = head(logits, (labels, weights))
        terms = [left["losses"][name]["moe"] for name in blocks]
        seen = [left["intermediates"][name]["moe"] for name in blocks]
        sown = [left["metrics"][name]["moe"] for name in blocks]
        return logits, loss, metrics, {
            "load_balance": sum(p["load_balance"][0] for p in terms),
        }, {
            "experts": jnp.stack([p["top_idx"][0] for p in seen]),
            "router_logits": jnp.stack([p["router_logits"][0] for p in seen]),
            "router_in": jnp.stack([p["router_in"][0] for p in seen]),
            "rows_held": jnp.stack([p["moe_rows_held"][0] for p in sown]),
            "rows_dropped": jnp.stack([p["moe_rows_dropped"][0] for p in sown]),
            "load_max": jnp.stack([p["moe_load_max"][0] for p in sown]),
            "held_load_max": jnp.stack([p["moe_held_load_max"][0] for p in sown]),
        }

    @jax.jit
    def plain(params, tokens, labels, weights, chosen):
        logits, info = reference.forward(config, params, tokens, chosen)
        return logits, reference.weighted_cross_entropy(logits, labels, weights), info

    got_logits, got_loss, got_metrics, got_aux, routed = program(
        params, tokens, labels, weights
    )
    with jax.default_matmul_precision("highest"):
        want_logits, want_loss, info = plain(
            params, tokens, labels, weights, routed["experts"]
        )
    routing = routing_vs_reference(
        routed, info, jnp.stack([params[name]["moe"]["router"]["kernel"] for name in blocks])
    )
    rel = _rel(got_logits, want_logits)
    finite = bool(jnp.isfinite(got_logits).all())
    nonzero = float(jnp.max(jnp.abs(want_logits))) > 0
    rows_held = [float(v) for v in routed["rows_held"]]
    rows_dropped = float(jnp.sum(routed["rows_dropped"]))
    load_max = [float(v) for v in routed["load_max"]]
    held_load_max = [float(v) for v in routed["held_load_max"]]

    def relative(got, want):
        return abs(float(got) - float(want)) / abs(float(want))

    loss_rel = relative(got_loss, want_loss)
    aux_rel = {name: relative(got_aux[name], info[name]) for name in got_aux}
    aux = {name: float(v) for name, v in got_aux.items()}
    want_aux = {name: float(info[name]) for name in got_aux}
    metrics = {name: float(v) for name, v in got_metrics.items()}
    del got_logits, want_logits, params, info, routed

    b, length = config["train"]["batch_per_chip"], config["train"]["seq_len"]
    shape = (
        b, config["num_attention_heads"], config["num_key_value_heads"], length,
        config["head_dim"], spec(config)["block"],
    )
    kernel = kernel_vs_reference(seed, *shape)
    members = kernel_membership(*shape)
    held_rows = dict(
        num_experts=config["num_experts"], num_experts_per_tok=1,
        hidden_size=config["hidden_size"], intermediate_size=config["moe_intermediate_size"],
    )  # the held groups of 2 L k / E rows expected: what the held experts see
    gmm = grouped_matmul_vs_reference(
        held_rows, seed, int(b * 2 * length * routed_experts_a_token(config))
    )
    ok = (
        finite and nonzero and rel <= LOGITS_REL_TOL and loss_rel <= LOSS_REL_TOL
        and process["tokens_differ"] == 0
        and process["weights_rel_err"] <= FORWARD_PROCESS_REL_TOL
        and max(aux_rel.values()) <= AUX_REL_TOL
        and routing["router_logits_rel_err"] <= ROUTER_LOGITS_REL_TOL
        and routing["router_arithmetic_rel_err"] <= ROUTER_ARITHMETIC_REL_TOL
        and routing["tokens_misrouted"] == 0 and routing["flipped_share"] <= ROUTE_FLIP_LIMIT
        and rows_dropped == 0
        and kernel["max_rel_err"] <= KERNEL_REL_TOL
        and members["max_rel_err"] <= MEMBERSHIP_REL_TOL
        and gmm["max_rel_err"] <= GMM_REL_TOL
    )
    return {
        "ok": bool(ok), "logits_rel_err": rel, "logits_rel_tol": LOGITS_REL_TOL,
        "logits_nonzero": nonzero,
        "loss": float(got_loss), "reference_loss": float(want_loss),
        "loss_rel_err": loss_rel, "loss_rel_tol": LOSS_REL_TOL,
        "forward_process": process, "forward_process_rel_tol": FORWARD_PROCESS_REL_TOL,
        "loss_head_metrics": metrics,
        "aux": aux, "reference_aux": want_aux,
        "aux_rel_err": aux_rel, "aux_rel_tol": AUX_REL_TOL,
        **routing,
        "router_logits_rel_tol": ROUTER_LOGITS_REL_TOL,
        "router_arithmetic_rel_tol": ROUTER_ARITHMETIC_REL_TOL,
        "flipped_limit": ROUTE_FLIP_LIMIT,
        "rows_held": rows_held, "rows_dropped": rows_dropped,
        "load_max": load_max, "held_load_max": held_load_max,
        "sample_items": n, "kernel": kernel, "kernel_rel_tol": KERNEL_REL_TOL,
        "kernel_membership": members, "membership_rel_tol": MEMBERSHIP_REL_TOL,
        "grouped_matmul": gmm, "grouped_matmul_rel_tol": GMM_REL_TOL,
    }


def kernel_vs_reference(seed, b, h, h_kv, length, d, block):
    """``ops.attention.attention`` as the step calls it (value and q/k/v
    gradients under ``block_diffusion=(L, B)``, bfloat16, ``2 L`` positions)
    against the reference's dense float32 attention under its own mask in row
    blocks on the same inputs, every head at once."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.block_diffusion_lm import masked_attention
    from edl_tpu.ops import attention

    t = 2 * length
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
        lambda q, k, v: attention(q, k, v, causal=True, block_diffusion=(length, block))
    )(q, k, v, w)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = value_and_grads(lambda q, k, v: masked_attention(q, k, v, length, block))(
            f32(q), f32(k), f32(v), f32(w)
        )
    errs = {}
    for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        errs[name] = (
            float(np.max(np.abs(a - r)) / np.max(np.abs(r)))
            if np.isfinite(a).all() else float("inf")
        )
    return {"shape": [b, h, h_kv, t, d], "block_diffusion": [length, block],
            "max_rel_err": max(errs.values()), **errs}


def membership_counts(length, block, d, group):
    """``(out [2 L, d], dv [2 L, d])`` that ``kernel_membership``'s inputs give
    under the mask's definition, as exact sums of integers over integers: row
    ``i`` of block ``b`` sees ``(b + 1) B`` keys, the clean positions ``[0, (b +
    1) B)`` (a clean row) or ``[0, b B)`` and the noised ``[L + b B, L + (b + 1)
    B)`` (a noised one); clean key ``j`` of block ``b`` is seen by the clean
    rows ``[b B, L)`` and the noised rows ``[L + (b + 1) B, 2 L)``, noised key
    ``j`` by the noised rows of its own block."""
    t = 2 * length
    i = np.arange(t)
    noised = i >= length
    first = (i % length) // block * block                # the block's first position
    one_hot = np.eye(d, dtype=np.float64)[i % d]         # [t, d]: value / cotangent
    visible = (first + block).astype(np.float64)
    prefix = np.concatenate([np.zeros((1, d)), np.cumsum(one_hot, axis=0)])
    clean_hi = np.where(noised, first, first + block)
    own = prefix[length + first + block] - prefix[length + first]
    want_out = (prefix[clean_hi] + np.where(noised[:, None], own, 0.0)) / visible[:, None]
    weighted = np.concatenate(
        [np.zeros((1, d)), np.cumsum(one_hot / visible[:, None], axis=0)]
    )
    of_clean_key = (
        weighted[length] - weighted[first] + weighted[t] - weighted[length + first + block]
    )
    of_noised_key = weighted[length + first + block] - weighted[length + first]
    want_dv = group * np.where(noised[:, None], of_noised_key, of_clean_key)
    return want_out, want_dv


def kernel_membership(b, h, h_kv, length, d, block):
    """Which keys each query sees, and which queries see each key, counted by
    the kernels (``afmoe_lm.kernel_membership``'s method): q = k = 0 makes the
    weights uniform over the visible keys, and one-hot values (cotangents) of
    the position modulo ``d`` make the output (dv) the count of visible keys
    (seeing queries) of each residue over the number visible. The expected
    counts are ``membership_counts``' exact integers: the clean diagonal by
    blocks, the strict boundary of a noised row's clean keys and its own
    block's keys are all among them. Returns the largest |difference| of a row
    over the row's largest expected value."""
    import jax
    import jax.numpy as jnp

    from edl_tpu.ops import attention

    t = 2 * length
    q = jnp.zeros((b, h, t, d), jnp.bfloat16)
    k = jnp.zeros((b, h_kv, t, d), jnp.bfloat16)
    one_hot = np.eye(d, dtype=np.float32)[np.arange(t) % d]          # [t, d]
    v = jnp.broadcast_to(jnp.asarray(one_hot, jnp.bfloat16), (b, h_kv, t, d))
    w = jnp.broadcast_to(jnp.asarray(one_hot, jnp.bfloat16), (b, h, t, d))

    @jax.jit
    def run(q, k, v, w):
        out, vjp = jax.vjp(
            lambda q, k, v: attention(
                q, k, v, causal=True, block_diffusion=(length, block)
            ), q, k, v,
        )
        return out, vjp(w)[2]

    out, dv = run(q, k, v, w)
    want_out, want_dv = membership_counts(length, block, d, h // h_kv)
    errs = {}
    for name, a, r in (("out", out, want_out), ("dv", dv, want_dv)):
        a = np.asarray(a, np.float64)
        errs[name] = (
            float(np.max(np.max(np.abs(a - r), axis=-1) / np.max(r, axis=-1)))
            if np.isfinite(a).all() else float("inf")
        )
    return {"block_diffusion": [length, block], "max_rel_err": max(errs.values()), **errs}
