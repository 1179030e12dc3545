"""Family ``sparse_lm``: the program's ``TransformerLM`` as one chip's share of
a decoder whose every layer attends over a **learned selection of keys** (the
language model of Kwai's Keye-VL-2.0 line; the indexer is DeepSeek sparse
attention's, arXiv:2512.02556 section 2) — per-head QK norms and rotary
positions at the configuration's base on GQA heads; an indexer of
``sa_config.indexer_num_heads`` heads of ``indexer_head_dim`` against one
shared key head that scores every causal pair; each query attending to its
``sa_config.topk`` best keys (``ops/sparse_attention.py``); the indexer trained
by its KL towards the main attention's head-mean probabilities and by nothing
else; then softmax-routed experts with renormalised top-k weights, **the
experts this chip holds** (``models/moe.py:DroplessMoE(held=...)``), no shared
one, a load-balancing loss over all the model's experts; an untied head over a
slice of the vocabulary — built from a file that keeps the published
``config.json`` keys.

See ``resnet_vd.py`` for what a family is. The token generator is
``transformer_lm.py``'s (uniform ids of the held slice).
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark.families.moe_lm import (  # noqa: F401 — the family's interface
    GMM_REL_TOL,
    MOE_TRACE_KERNELS,
    grouped_matmul_vs_reference,
)
from benchmark.families.ssm_lm import _rel
from benchmark.families.transformer_lm import (  # noqa: F401 — the family's interface
    KERNEL_REL_TOL,
    LOSS_REL_TOL,
    _items,
    host_batches,
)

# Every limit below lies between two readings: what the cell reads on the chip
# (bfloat16 compute, over fourteen seeds; my chip runs, PR 41) and
# what the same program reads in float8_e4m3fn, 2^-4 a value, the nearest
# precision below (sandbox, ``benchmark/tests/test_sparse_lm.py``: the cell's
# five layers at a width of 256, where its bfloat16 reads what the chip reads).
#
# Logits of the program (bfloat16 operands, float32 accumulation, float32
# logits) against the float32 reference computed under the program's own
# selection of keys and choice of experts, as max |difference| over max
# |reference| over every token. Five pre-norm layers on a stream that is the
# embedding's N(0, 1) plus branches of a tenth its size: chip 0.0055..0.0070,
# sandbox bfloat16 0.0065, 8-bit 0.109.
LOGITS_REL_TOL = 0.02
# One layer's index scores of the program (bfloat16 q_I, k_I; float32 weights
# and sum) against the reference's float32 einsum ON THE PROGRAM'S OWN q_I, k_I
# and w (sown intermediates), as max |difference| over max |reference| over the
# causal pairs: the kernel's arithmetic alone. Sixteen products of 64 bfloat16
# terms accumulated in float32 are exact to the last bit or two: chip 0.0 on
# every seed. The same scores KEPT in bfloat16 (the precision below; the check
# reports it beside the reading) read 0.0023..0.0027 there.
INDEX_ARITHMETIC_REL_TOL = 2e-4
# The same scores against the reference's own float32 indexer on the float32
# stream (first layer: both streams are the embedding's): bfloat16 q_I and k_I,
# 2^-9 a value, through 16 x 64 products. Chip 0.0055..0.0074, sandbox bfloat16
# 0.0059, 8-bit 0.102.
INDEX_SCORES_REL_TOL = 0.025
# The selection. Every pair the program's and the reference's selections
# disagree on must lie, by the reference's own score, within SELECT_MARGIN_REL
# times the layer's largest |score| of its row's k-th score (chip 0.0060..0.0079,
# sandbox bfloat16 0.0061, 8-bit 0.136), and the disagreeing share of the
# selected pairs stays under SELECT_FLIP_LIMIT in every layer (chip 0.0054..0.0058,
# sandbox 0.0070, 8-bit 0.095): a fresh indexer's scores lie densely (2048 of
# up to 16,384 scores a row within a few hundredths), so a rounding of 2^-9
# moves pairs across the threshold, as an expert choice flips (PR 37).
SELECT_MARGIN_REL = 0.03
SELECT_FLIP_LIMIT = 0.025
# The indexer's loss of the program against the reference's on the same
# selection, layer by layer: a mean over 16,384 rows of KLs of up to 2048 terms
# each, bfloat16 probabilities on one side. Chip 0.0001..0.0004, sandbox bfloat16
# 0.0006, 8-bit 0.039. (The kernels' comparison on seeded operands shares the
# limit and reads 4.5e-7: the same operands on both sides.)
INDEX_KL_REL_TOL = 0.005
# Tokens whose choice of experts may differ in the layer where most do
# (``afmoe_lm.py``'s rule: a flip is right only inside the margin; any other is
# ``tokens_misrouted``), 128 softmax scores of a fresh router: chip 0.045..0.051,
# sandbox bfloat16 0.025, 8-bit 0.34. The routers' logits, layer by layer:
# chip 0.0049..0.0063, sandbox 0.0061, 8-bit 0.125.
ROUTE_FLIP_LIMIT = 0.14
ROUTER_LOGITS_REL_TOL = 0.02
# The kernels' comparison runs twice: under a fresh indexer, and under one that
# looks near (its first two values are NEAR_PULL times the position's cosine and
# sine over half a turn a sequence, the other 62 half a fresh one's), whose
# selection leaves whole tiles under the diagonal empty (``tile_live`` 0.355 at
# the cell's shape, where a fresh one's reads 1.0) and ties scores by the
# hundred: what a trained indexer gives the masked kernels and the bisection.
NEAR_PULL = 16.0
# The gradients ``L_I`` sends the indexer's three operands (the target's kernel
# in its ``dI`` mode, the index scores' backward and its whole-sequence ``dk``
# accumulator) against the reference's float32 autodiff of ``L_I`` alone on the
# same seeded operands, the worst of the three as max |difference| over max
# |reference|: ``dI`` crosses HBM in bfloat16 and each gradient sums up to
# 16,384 of them. Fresh: chip 0.0028..0.0053 at the cell's shape over ten seeds;
# the precision below (``dI`` as an 8-bit float under one scale a tensor,
# ``test_sparse_lm.py``) 0.026..0.042. Near: the weights' gradient is a sum of
# ``dI``, which adds up to nothing a row, against products that are nearly one
# constant, so what is left of the reference is a tenth of its terms and a
# rounding weighs ten times more, in either precision: chip 0.0103..0.0366 over
# ten seeds (the median 0.0143), 8-bit 0.28..0.35. Each limit is the geometric
# middle of its two readings.
INDEX_GRAD_REL_TOL = 0.012
INDEX_GRAD_NEAR_REL_TOL = 0.1


def sparse_spec(config):
    from edl_tpu.models import SparseAttentionSpec

    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("sparse_lm: one shared indexer key head, as published")
    return SparseAttentionSpec(
        index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        topk=sa["topk"], loss_weight=float(config["indexer_loss_weight"]),
    )


def arch_spec(config):
    from edl_tpu.models import ArchSpec

    if config["rope_scaling"]["rope_type"] != "default" or config["attention_bias"]:
        raise ValueError("sparse_lm: unscaled rotary positions and no bias, as published")
    if config["tie_word_embeddings"]:
        raise ValueError("sparse_lm: an untied head, as published")
    return ArchSpec(
        layer_types=("sparse_attention",) * config["num_hidden_layers"],
        sparse_attention=sparse_spec(config), head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
    )


def moe_spec(config):
    from edl_tpu.models import MoESpec

    share = config["share"]
    return MoESpec(
        num_experts=share["router_experts"], top_k=config["num_experts_per_tok"],
        d_ff=config["moe_intermediate_size"], norm_topk_prob=config["norm_topk_prob"],
        aux_weight=float(config["router_aux_loss_coef"]), z_weight=0.0,
        score_func="softmax", held=(share["experts_first"], config["num_experts"]),
    )


def starts_like_a_trained_one(lm, embedding_rms):
    """``lm`` (the program's ``TransformerLM`` class) with its first values
    changed and nothing else: ``init`` returns what the class draws with the
    embedding table multiplied up to rows of ``embedding_rms`` a value (it is
    drawn at ``d_model ** -0.5``). The benchmark's stand-in for the trained
    weights the cell's users start from, whose stream still tells tokens apart
    after many layers: a freshly drawn model's does not at 16,384 (each
    attention layer adds the mean of some thousand keys' values, the same for
    every query and as large as the embedding, so a softmax router reads the
    same logits for every token and which chip's experts it names is the
    seed's luck). An initial value on the benchmark's side, not an equation and
    not a field of the model: ``apply``, the step and the check are the
    class's own."""
    import flax.linen as nn

    class StartedLM(lm):
        @nn.nowrap
        def init(self, *args, **kwargs):
            variables = super().init(*args, **kwargs)
            params = dict(variables["params"])
            table = params["embed"]["embedding"]
            params["embed"] = {
                "embedding": table * (embedding_rms * self.d_model ** 0.5)
            }
            return {**variables, "params": params}

    return StartedLM


def build(config, global_batch, seed):
    import jax.numpy as jnp
    import optax

    from edl_tpu.models import TransformerLM
    from edl_tpu.train import cross_entropy_loss

    train = config["train"]
    if train["compute_dtype"] not in ("bfloat16", "float32"):
        raise ValueError("sparse_lm: compute_dtype %r" % train["compute_dtype"])
    model = starts_like_a_trained_one(TransformerLM, train["start"]["embedding_rms"])(
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
        raise ValueError("sparse_lm: unknown optimizer %r" % opt["name"])

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


# -- counts, from shapes -----------------------------------------------------


def causal_pairs(t):
    return t * (t + 1) // 2


def selected_pairs(config):
    """(query, key) pairs a layer's selection keeps in one sequence: ``sum_t
    min(t + 1, topk)``."""
    t, k = config["train"]["seq_len"], config["sa_config"]["topk"]
    k = min(k, t)
    return k * (k + 1) // 2 + (t - k) * k


def attention_params(config):
    d, hd = config["hidden_size"], config["head_dim"]
    return 2 * d * config["num_attention_heads"] * hd + (
        2 * d * config["num_key_value_heads"] * hd
    )


def indexer_params(config):
    """The indexer's three projections (the LayerNorm's 2 x 64 multiply
    elementwise and count for nothing)."""
    sa, d = config["sa_config"], config["hidden_size"]
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return d * heads * dim + d * dim + d * heads


def routed_experts_a_token(config):
    """Expert matmuls a token meets HERE, expected under balanced routing."""
    return (
        config["num_experts_per_tok"] * config["num_experts"]
        / config["share"]["router_experts"]
    )


def matmul_params(config):
    """Parameters that multiply every token on this chip, all layers: the
    attention's four projections, the indexer's three, the router at its whole
    width, the expected ``routed_experts_a_token`` routed experts; and the
    untied head over the slice (the embedding is a lookup)."""
    d, fe = config["hidden_size"], config["moe_intermediate_size"]
    layer = (
        attention_params(config) + indexer_params(config)
        + d * config["share"]["router_experts"]
        + routed_experts_a_token(config) * 3 * d * fe
    )
    return config["num_hidden_layers"] * layer + d * config["vocab_size"]


def sparse_kernel_flops(config, sequences):
    """What attention over the selection has to compute, all layers: over the
    SELECTED pairs only, two matrix multiplications forward (``q k``, ``p v``)
    and five backward (the fused backward recomputes the scores): 2 * D a pair
    a head each. What the mask empties inside a dense tile is time, not work."""
    pairs = selected_pairs(config) * sequences
    return (
        7.0 * 2 * config["head_dim"] * config["num_attention_heads"] * pairs
        * config["num_hidden_layers"]
    )


def sparse_kernel_bytes(config, sequences):
    """The least HBM traffic of that work in bfloat16: q, k, v and o once
    forward; q, k, v, o, dO read and dq, dk, dv written backward."""
    t = config["train"]["seq_len"] * sequences
    q = t * config["num_attention_heads"] * config["head_dim"] * 2
    kv = t * config["num_key_value_heads"] * config["head_dim"] * 2
    return (2 * q + 2 * kv + 4 * q + 4 * kv) * config["num_hidden_layers"]


def index_kernel_flops(config, sequences):
    """What the index scores have to compute, all layers: forward ``J`` heads'
    products of ``Di`` over EVERY causal pair (the selection needs every
    score); backward over the selected pairs only (``dI`` is zero elsewhere):
    the recomputed products and the two gradients' (three of the forward's)."""
    sa = config["sa_config"]
    pair = 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"]
    t = config["train"]["seq_len"]
    return (
        pair * (causal_pairs(t) + 3 * selected_pairs(config)) * sequences
        * config["num_hidden_layers"]
    )


def index_kernel_bytes(config, sequences):
    """The least HBM traffic of that work: the float32 scores of every causal
    pair written once forward; ``dI`` of the selected pairs read once backward
    in bfloat16 (the operands are a thousandth of either)."""
    t = config["train"]["seq_len"]
    return (
        4.0 * causal_pairs(t) + 2.0 * selected_pairs(config)
    ) * sequences * config["num_hidden_layers"]


def select_bytes(config, sequences):
    """The least HBM traffic of the selection: one read of the float32 scores
    of every causal pair (the thresholds it writes are 8 bytes a row)."""
    return 4.0 * causal_pairs(config["train"]["seq_len"]) * sequences * config["num_hidden_layers"]


def target_flops(config, sequences):
    """The indexer's target over the selected pairs: every head's ``q k``
    again, forward and once more for the gradient."""
    return (
        2.0 * 2 * config["head_dim"] * config["num_attention_heads"]
        * selected_pairs(config) * sequences * config["num_hidden_layers"]
    )


def flops_per_item(config):
    """Operations the forward and backward passes need for one token: 6 per
    matrix-multiplied parameter a token meets (the routed experts at their
    expected ``routed_experts_a_token``); attention over the SELECTED pairs
    only, three times its forward (two matmuls forward, four backward); the
    index scores' forward over EVERY causal pair and their backward (two
    gradients, twice the forward's a pair) over the selected pairs; the
    indexer's target (each head's ``q k`` over the selected pairs, once).
    Recomputation under remat, the fused backward's recomputed scores, the
    bisection, norms, RoPE, softmaxes, the sort and the optimizer are not
    counted: what the mask leaves unused inside a dense tile is time and not
    work, so a kernel that skips it reads a higher ``mfu`` on the same count."""
    t = config["train"]["seq_len"]
    sa = config["sa_config"]
    layers = config["num_hidden_layers"]
    picked, causal = selected_pairs(config), causal_pairs(t)
    heads, hd = config["num_attention_heads"], config["head_dim"]
    attention = 3.0 * 2 * 2 * hd * heads * picked
    pair = 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"]
    index = pair * (causal + 2 * picked)
    target = 2.0 * hd * heads * picked
    return 6.0 * matmul_params(config) + layers * (attention + index + target) / t


def moe_kernel_flops(config, tokens):
    """What the grouped matmuls have to compute for ``tokens`` tokens, all
    layers (``lfm2_lm.moe_kernel_flops``)."""
    rows = tokens * routed_experts_a_token(config)
    return (
        6.0 * 3 * rows * config["hidden_size"] * config["moe_intermediate_size"]
        * config["num_hidden_layers"]
    )


def moe_kernel_bytes(config, tokens):
    rows = tokens * routed_experts_a_token(config)
    d, f, e = config["hidden_size"], config["moe_intermediate_size"], config["num_experts"]
    return 9.0 * (rows * d * 2 + rows * f * 2 + e * d * f * 2) * config["num_hidden_layers"]


# -- the check ----------------------------------------------------------------


def check(config, state, seed):
    """On one seeded sequence, with the trained parameters, at the timed sizes:
    logits and the cross-entropy against the plain reference, which computes
    each layer's attention and indexer loss **under the program's selection**
    and each expert layer with the program's choice of experts (a key within a
    hair of its row's threshold goes either way, and one flipped key changes a
    row's output by its whole probability in every later layer: PR 37's finding
    for experts) and makes its own selection and choice beside them; every
    layer's selection against the reference's own (each disagreeing pair within
    a stated distance of the row's k-th score, the disagreeing share under a
    stated bound, exactly ``min(topk, t + 1)`` keys a row); every layer's
    ``L_I``; the first layer's index scores; the routers' choices by
    ``afmoe_lm.py``'s rule; then, each at the step's own shape, the selection's
    kernels on seeded operands, once under a fresh indexer and once under one
    that looks near (scores, the selection to the pair, attention and its three
    gradients, ``L_I`` and the three gradients it sends the indexer), and the
    grouped matmul at the held rows' shape and the experts' width."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import sparse_lm as reference
    from edl_tpu.train import cross_entropy_loss

    n = config["check"]["sample_items"]
    t = _items(config, seed + 7, n)
    params, apply_fn = state.params, state.apply_fn
    del state
    tokens, targets = t[:, :-1], t[:, 1:]
    layers = range(config["num_hidden_layers"])
    topk = config["sa_config"]["topk"]

    @jax.jit
    def program(params, tokens, targets):
        logits, left = apply_fn(
            {"params": params}, tokens, mutable=["intermediates", "metrics", "losses"],
        )
        ce, _ = cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
        )
        seen = [left["intermediates"]["layer_%d" % i] for i in layers]
        sown = [left["metrics"]["layer_%d" % i] for i in layers]
        first = seen[0]["attn"]
        return logits, ce, {
            "experts": jnp.stack([p["moe"]["top_idx"][0] for p in seen]),
            "router_logits": jnp.stack([p["moe"]["router_logits"][0] for p in seen]),
            "selections": jnp.stack([p["attn"]["selection"][0] for p in seen]),
            "index_kl": jnp.stack([p["attn"]["dsa_index_kl"][0] for p in sown]),
            "tile_live": jnp.stack([p["attn"]["dsa_tile_live"][0] for p in sown]),
            "rows_dropped": jnp.stack([p["moe"]["moe_rows_dropped"][0] for p in sown]),
            "rows_held": jnp.stack([p["moe"]["moe_rows_held"][0] for p in sown]),
            "first_scores": first["index_scores"][0],
            "first_operands": first["index_operands"][0],
        }

    @jax.jit
    def plain_layer(p, x, chosen, selection):
        """One layer of the reference under the program's selection and choice
        of experts, and what the selection's comparison needs of it (a layer a
        call: ``[T, T]`` rectangles of five layers at once do not fit beside
        the parameters at 16,384)."""
        picked = selection != 0
        x, kl, _, picks, router = reference.layer(
            config, p, x, reference.text_positions(tokens), chosen, picked
        )
        a_row = jnp.minimum(topk, jnp.arange(picked.shape[-1]) + 1)
        return x, picks[0]["scores"], router, {
            "index_kl": kl,
            "selected": sum(p["selected"] for p in picks),
            "flipped": sum(p["flipped"] for p in picks),
            "widest_flip": jnp.max(jnp.stack([p["widest_flip"] for p in picks])),
            "score_scale": jnp.max(jnp.stack([p["score_scale"] for p in picks])),
            "short_rows": jnp.sum(jnp.sum(picked, axis=-1) != a_row),
            "past_diagonal": jnp.sum(jnp.triu(picked, 1)),
        }

    got_logits, got_ce, got = program(params, tokens, targets)
    # the layers' selections wait on the host, one at a time on the device
    selections = np.asarray(got.pop("selections"))
    steps = tokens.shape[1]
    causal = jnp.tril(jnp.ones((steps, steps), bool))
    mine = jnp.where(causal, got.pop("first_scores")[0], 0.0)
    iq, ik, iw = (a[0].astype(jnp.float32) for a in got.pop("first_operands"))
    with jax.default_matmul_precision("highest"):
        # the first layer's scores: the kernel's arithmetic on its own operands
        exact = jnp.where(causal, jax.jit(reference.index_scores)(iq, ik, iw), 0.0)
        arithmetic_rel = _rel(mine, exact)
        # what scores kept in bfloat16 would read there: the precision below
        arithmetic_rel_bf16 = _rel(exact.astype(jnp.bfloat16).astype(jnp.float32), exact)
        del exact, iq, ik, iw
        x = jax.jit(reference.embed)(params, tokens)
        routers, picks = [], []
        for i in layers:
            x, scores, router, pick = plain_layer(
                params["layer_%d" % i], x, got["experts"][i], jnp.asarray(selections[i])
            )
            if i == 0:  # and against the reference's float32 indexer
                scores_rel = _rel(mine, jnp.where(causal, scores, 0.0))
                del mine
            del scores
            routers.append(router)
            picks.append(jax.device_get(pick))
        want_logits = jax.jit(lambda p, x: reference.head(config, p, x))(params, x)
        want_ce = reference.cross_entropy(want_logits, targets)
    del x, selections, causal
    info = {key: jnp.stack([r[key] for r in routers]) for key in routers[0]}
    rel = float(jnp.max(jnp.abs(got_logits - want_logits)) / jnp.max(jnp.abs(want_logits)))
    finite = bool(jnp.isfinite(got_logits).all())
    del got_logits, want_logits
    loss_rel = abs(float(got_ce) - float(want_ce)) / abs(float(want_ce))

    # the selection, layer by layer
    short_rows = int(sum(p["short_rows"] for p in picks))
    past_diagonal = int(sum(p["past_diagonal"] for p in picks))
    flips = [float(p["flipped"] / p["selected"]) for p in picks]
    widest = [float(p["widest_flip"] / p["score_scale"]) for p in picks]
    kl_rel = [
        abs(float(a) - float(p["index_kl"])) / abs(float(p["index_kl"]))
        for a, p in zip(got["index_kl"], picks)
    ]

    # routing, as afmoe_lm.py judges it: a flip only where the reference's k-th
    # score stands above its (k+1)-th by at most twice the scores' difference
    differs = jnp.any(
        jnp.sort(got["experts"], axis=-1) != jnp.sort(info["experts"], axis=-1), axis=-1
    )
    router_rel = float(
        jnp.max(jnp.abs(got["router_logits"] - info["router_logits"]))
        / jnp.max(jnp.abs(info["router_logits"]))
    )
    moved = jnp.max(
        jnp.abs(jax.nn.softmax(got["router_logits"], axis=-1) - info["scores"]), axis=-1
    )
    misrouted = int(jnp.sum(differs & (info["margin"] > 2.0 * moved)))
    route_flips = [float(v) for v in jnp.mean(differs, axis=-1)]
    rows_dropped = float(jnp.sum(got["rows_dropped"]))
    rows_held = [float(v) for v in got["rows_held"]]
    tile_live = [float(v) for v in got["tile_live"]]
    del got, info, routers, params

    sa = config["sa_config"]
    kernels, kernels_near = (
        sparse_kernels_vs_reference(
            seed, config["num_attention_heads"], config["num_key_value_heads"], steps,
            config["head_dim"], sa["indexer_num_heads"], sa["indexer_head_dim"], topk,
            near=near,
        )
        for near in (False, True)
    )
    held_rows = dict(
        config, num_experts_per_tok=1, intermediate_size=config["moe_intermediate_size"]
    )
    gmm = grouped_matmul_vs_reference(
        held_rows, seed,
        int(config["train"]["batch_per_chip"] * steps * routed_experts_a_token(config)),
    )
    ok = (
        finite and rel <= LOGITS_REL_TOL and loss_rel <= LOSS_REL_TOL
        and short_rows == 0 and past_diagonal == 0
        and max(flips) <= SELECT_FLIP_LIMIT and max(widest) <= SELECT_MARGIN_REL
        and max(kl_rel) <= INDEX_KL_REL_TOL
        and arithmetic_rel <= INDEX_ARITHMETIC_REL_TOL
        and scores_rel <= INDEX_SCORES_REL_TOL
        and router_rel <= ROUTER_LOGITS_REL_TOL and misrouted == 0
        and max(route_flips) <= ROUTE_FLIP_LIMIT and rows_dropped == 0
        and kernels["ok"] and kernels_near["ok"] and gmm["max_rel_err"] <= GMM_REL_TOL
    )
    return {
        "ok": bool(ok), "logits_rel_err": rel, "logits_rel_tol": LOGITS_REL_TOL,
        "loss": float(got_ce), "reference_loss": float(want_ce),
        "loss_rel_err": loss_rel, "loss_rel_tol": LOSS_REL_TOL,
        "rows_short_of_their_keys": short_rows, "pairs_past_the_diagonal": past_diagonal,
        "selection_flipped_share_by_layer": flips, "selection_flip_limit": SELECT_FLIP_LIMIT,
        "selection_widest_flip_by_layer": widest, "selection_margin_rel": SELECT_MARGIN_REL,
        "index_kl_rel_err_by_layer": kl_rel, "index_kl_rel_tol": INDEX_KL_REL_TOL,
        "index_arithmetic_rel_err": arithmetic_rel,
        "index_arithmetic_rel_tol": INDEX_ARITHMETIC_REL_TOL,
        "index_arithmetic_rel_err_in_bfloat16": arithmetic_rel_bf16,
        "index_scores_rel_err": scores_rel, "index_scores_rel_tol": INDEX_SCORES_REL_TOL,
        "router_logits_rel_err": router_rel, "router_logits_rel_tol": ROUTER_LOGITS_REL_TOL,
        "route_flipped_share_by_layer": route_flips, "route_flip_limit": ROUTE_FLIP_LIMIT,
        "tokens_misrouted": misrouted, "rows_held": rows_held, "rows_dropped": rows_dropped,
        "tile_live_by_layer": tile_live, "sample_items": n,
        "kernels": kernels, "kernels_near": kernels_near, "grouped_matmul": gmm, "grouped_matmul_rel_tol": GMM_REL_TOL,
    }


@functools.lru_cache(maxsize=None)
def _compared_forms(op, topk):
    """What the kernels' comparison calls, jitted once a process (it runs
    twice a check): ``op`` under both cotangents, and the reference's forms,
    every ``[T, T]`` array an argument (one closed over would be a constant
    of a gigabyte in the executable)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import sparse_lm as reference

    def one_group(q, k, v, w, picked):
        (o, p), vjp = jax.vjp(
            lambda q, k, v: reference.selected_attention(q, k, v, picked), q, k, v
        )
        return (o, p, *vjp((w, jnp.zeros_like(p))))

    def index_loss(iq, ik, iw, picked, target):
        return reference.index_kl(reference.index_scores(iq, ik, iw), picked, target)

    def program(q, k, v, w, iq, ik, iw):
        (out, kl, stats, detail), vjp = jax.vjp(
            lambda *operands: op(*operands, topk), q, k, v, iq, ik, iw
        )
        zero = jax.tree.map(jnp.zeros_like, (stats, detail))
        grads = vjp((w.astype(out.dtype), jnp.ones_like(kl), *zero))
        return out, grads, kl, stats["tile_live"], detail

    return {
        "program": jax.jit(program),
        "index_scores": jax.jit(reference.index_scores),
        "select": jax.jit(lambda scores: reference.select(scores, topk)),
        "one_group": jax.jit(one_group),
        "index_kl": jax.jit(reference.index_kl),
        "index_grads": jax.jit(jax.grad(index_loss, argnums=(0, 1, 2))),
    }


def sparse_kernels_vs_reference(seed, h, h_kv, t, d, j, di, topk, op=None, near=False):
    """``ops.sparse_attention`` as the layer calls it (one sequence; value,
    ``L_I``, the selection, the scores it was made from, and one backward pass
    under both cotangents: a seeded one for ``o`` and 1 for ``L_I``) on seeded
    bfloat16 operands against the reference in float32 on the same operands:
    the scores against the one einsum; the selection against ``lax.top_k`` over
    **the program's own scores**, to the pair (the bisection is exact, so any
    difference is a fault: a row short of its keys, a tie the wrong way);
    attention and the gradients of q, k and v against a dense softmax under
    that selection, the heads of one kv head at a time (``o``'s cotangent
    alone: what ``L_I`` sent them would show as an error); ``L_I`` and the
    gradients of the indexer's three operands against the reference's autodiff
    of ``L_I`` alone. ``near``: an indexer that has learnt to look near, in
    place of a fresh one whose selection scatters: its first two values are the
    position's cosine and sine, so a row's best keys are its last ones, whole
    tiles under the diagonal hold no selected pair (``tile_live``) and many
    scores tie. ``op`` replaces the program's (the tests' wrong programs)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import sparse_lm as reference
    from edl_tpu.ops import sparse_attention

    op = op or sparse_attention
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 7)
    bf16 = jnp.bfloat16
    q = jax.random.normal(keys[0], (1, h, t, d), bf16)
    k = jax.random.normal(keys[1], (1, h_kv, t, d), bf16)
    v = jax.random.normal(keys[2], (1, h_kv, t, d), bf16)
    w = jax.random.normal(keys[3], (1, h, t, d), bf16)  # cotangent
    iq = jax.random.normal(keys[4], (1, j, t, di), bf16)
    ik = jax.random.normal(keys[5], (1, t, di), bf16)
    iw = jax.random.normal(keys[6], (1, t, j), jnp.float32) * (j ** -0.5 * di ** -0.5)
    if near:
        turn = jnp.arange(t) * (jnp.pi / t)
        place = NEAR_PULL * jnp.stack([jnp.cos(turn), jnp.sin(turn)], axis=-1)
        iq = (0.5 * iq.astype(jnp.float32)).at[..., :2].set(place).astype(bf16)
        ik = (0.5 * ik.astype(jnp.float32)).at[..., :2].set(place).astype(bf16)
        iw = jnp.abs(iw)
    plain = _compared_forms(op, topk)
    out, (dq, dk, dv, diq, dik, diw), kl, tile_live, detail = plain["program"](
        q, k, v, w, iq, ik, iw
    )
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, detail["scores"][0], 0.0)
    picked = detail["selection"][0] != 0
    del detail
    index_operands = (f32(jnp.swapaxes(iq[0], 0, 1)), f32(ik[0]), iw[0])
    with jax.default_matmul_precision("highest"):
        exact = plain["index_scores"](*index_operands)
        arithmetic = _rel(scores, jnp.where(causal, exact, 0.0))
        del exact
        own, _ = plain["select"](scores)
        wrong_pairs = int(jnp.sum(own != picked))
        del own
        group = h // h_kv
        rows = lambda a, s: f32(jnp.swapaxes(a[0, s], 0, 1))  # noqa: E731
        outs, dqs, dks, dvs = [], [], [], []
        target = jnp.zeros((t, t), jnp.float32)
        for head in range(h_kv):  # [T, g, D], [T, 1, D]: one kv head's heads
            qs, ks = slice(head * group, (head + 1) * group), slice(head, head + 1)
            o, p, gq, gk, gv = plain["one_group"](
                rows(q, qs), rows(k, ks), rows(v, ks), rows(w, qs), picked
            )
            target = target + p * (group / h)
            outs.append(o), dqs.append(gq), dks.append(gk), dvs.append(gv)
        want_kl = plain["index_kl"](scores, picked, target)
        del scores
        # the indexer's gradient: L_I alone, the selection and the target given
        want_diq, want_dik, want_diw = plain["index_grads"](*index_operands, picked, target)
        del target, picked
    heads_first = lambda parts: jnp.swapaxes(jnp.concatenate(parts, axis=1), 0, 1)[None]  # noqa: E731
    errs = {"index_arithmetic": arithmetic}
    for name, a, r in zip(
        ("out", "dq", "dk", "dv", "d_index_q", "d_index_k", "d_index_w"),
        (out, dq, dk, dv, diq, dik, diw),
        (heads_first(outs), heads_first(dqs), heads_first(dks), heads_first(dvs),
         jnp.swapaxes(want_diq, 0, 1)[None], want_dik[None], want_diw[None]),
    ):
        errs[name] = _rel(a, r)
    kl_rel = abs(float(kl) - float(want_kl)) / abs(float(want_kl))
    attention_err = max(errs[n] for n in ("out", "dq", "dk", "dv"))
    index_grad_err = max(errs[n] for n in ("d_index_q", "d_index_k", "d_index_w"))
    index_grad_tol = INDEX_GRAD_NEAR_REL_TOL if near else INDEX_GRAD_REL_TOL
    ok = (
        attention_err <= KERNEL_REL_TOL
        and arithmetic <= INDEX_ARITHMETIC_REL_TOL and wrong_pairs == 0
        and kl_rel <= INDEX_KL_REL_TOL and index_grad_err <= index_grad_tol
    )
    return {
        "ok": bool(ok), "shape": [h, h_kv, t, d, j, di, topk], "near": near, **errs,
        "max_rel_err": attention_err, "kernel_rel_tol": KERNEL_REL_TOL,
        "index_grad_rel_err": index_grad_err, "index_grad_rel_tol": index_grad_tol,
        "pairs_selected_wrongly": wrong_pairs, "tile_live": float(tile_live),
        "index_kl": float(kl), "reference_index_kl": float(want_kl),
        "index_kl_rel_err": kl_rel,
    }
