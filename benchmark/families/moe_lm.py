"""Family ``moe_lm``: the program's ``TransformerLM`` with OLMoE's block —
every layer's feed-forward a dropless top-k mixture of SiLU-gated experts
(``models/moe.py:DroplessMoE`` over ``ops/grouped_matmul.py``), RMSNorm over
the projected q and k, the configuration's own epsilon — built from a file
that keeps the published ``config.json`` keys. The trainer finds the layer's
auxiliary losses by itself; nothing here asks for them.

See ``resnet_vd.py`` for what a family is. The token generator, the flash
kernels' check and their FLOP count are ``transformer_lm.py``'s.
"""

from __future__ import annotations

import numpy as np

from benchmark.families.transformer_lm import (  # noqa: F401 — the family's interface
    KERNEL_REL_TOL,
    LOGITS_REL_TOL,
    LOSS_REL_TOL,
    TRACE_KERNELS,
    _items,
    attention_forward_flops,
    head_dim,
    host_batches,
    kernel_flops,
    kernel_vs_reference,
)

# Each auxiliary term of the program against the reference's, relative. Both
# are means over thousands of tokens of float32 router statistics; what
# differs is the bfloat16 activations under the router (2^-9 a value, averaged
# away) and, for the load-balancing term, the few tokens whose eighth choice
# flips (below): measured 1e-4..1e-3. A router run in bfloat16 (logits to 2^-9
# of their size, squared and summed) sits near 1e-2 and a dropped term at 1.
AUX_REL_TOL = 5e-3
# The router's logits of the program against the reference's, as max
# |difference| over max |reference|. The router itself is float32 at the
# highest precision; what differs is its input, the bfloat16 residual stream
# and the bfloat16 output of the norm before it (2^-9 a value, summed over
# hidden_size products): measured 2e-3..4e-3 (PERF.md section 6, PR 25). An
# 8-bit float under the router (2^-4 a value) would be thirty times that.
ROUTER_LOGITS_REL_TOL = 0.015
# Routing near a tie. Where the reference's k-th logit stands above its
# (k+1)-th by less than the two logits' own errors, the program may rightly
# choose the other expert. So a token whose set of experts differs from the
# reference's ("flipped") must have a reference margin of at most twice the
# largest difference between its own program and reference router logits: any
# other difference is a wrong top-k and fails the check. Flipped tokens are
# counted, left out of the logits comparison (one of their k experts is another
# one), and may be at most ROUTE_FLIP_LIMIT of the sample. With 64 experts the
# 8th and 9th of a token's logits lie 0.07 apart on average, so logit errors of
# a few 1e-3 flip 3-5% of the tokens (measured 4.0%; a fixed band wide enough
# to hold every flip would hold a fifth of the tokens). The width is thus
# stated per token, and bounded by ROUTER_LOGITS_REL_TOL above.
ROUTE_FLIP_LIMIT = 0.10
# The grouped matmul (bfloat16 in, float32 accumulation, bfloat16 out)
# against a float32 loop over the groups on the
# same bfloat16 inputs: only the output's rounding differs (2^-9 of a value,
# under that of the largest). An 8-bit float (2^-4) fails.
GMM_REL_TOL = 1e-2
# The Megablox kernels in a device trace: custom calls named after the jitted
# kernel functions, ``%gmm.N`` (value and row gradient) and ``%tgmm.N`` (weight
# gradient). Every string has to be in the operation's HLO instruction.
MOE_TRACE_KERNELS = ("gmm", " custom-call(")


def moe_spec(config):
    from edl_tpu.models.moe import MoESpec

    layers = config["num_hidden_layers"]
    return MoESpec(
        num_experts=config["num_experts"], top_k=config["num_experts_per_tok"],
        d_ff=config["intermediate_size"], norm_topk_prob=config["norm_topk_prob"],
        # the trainer sums what the layers sow; the published terms are means
        # over the layers
        aux_weight=config["train"]["load_balance_coef"] / layers,
        z_weight=config["train"]["router_z_coef"] / layers,
    )


def build(config, global_batch, seed):
    import optax

    from edl_tpu.models import TransformerLM
    from edl_tpu.train import cross_entropy_loss

    train = config["train"]
    model = TransformerLM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        num_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"], remat=train["remat"],
        remat_policy=train["remat_policy"],
        norm_eps=config["rms_norm_eps"], qk_norm=True, moe=moe_spec(config),
    )
    opt = train["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError("moe_lm: unknown optimizer %r" % opt["name"])

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


def matmul_params(config):
    """Parameters that multiply every token: attention's four projections,
    the router, the ``num_experts_per_tok`` experts a token is sent to (not
    the ``num_experts`` the layer holds), and the head."""
    d, f = config["hidden_size"], config["intermediate_size"]
    hd = head_dim(config)
    q = d * config["num_attention_heads"] * hd
    kv = 2 * d * config["num_key_value_heads"] * hd
    layer = (
        q + kv + q + d * config["num_experts"]
        + config["num_experts_per_tok"] * 3 * d * f
    )
    return config["num_hidden_layers"] * layer + d * config["vocab_size"]


def flops_per_item(config):
    """As ``transformer_lm.flops_per_item``: 6 per matrix-multiplied parameter
    a token meets and three times the causal attention forward. Recomputation
    under remat, the sort, the gathers, norms, RoPE, the softmaxes and the
    optimizer are not counted."""
    t = config["train"]["seq_len"]
    return 6.0 * matmul_params(config) + 3.0 * attention_forward_flops(config, 1) / t


def moe_kernel_flops(config, tokens):
    """What the grouped matmuls have to compute for ``tokens`` tokens, all
    layers: gate, up and down over tokens * k rows, forward and both
    gradients (2 + 4 operations a multiply-add). The gate and up products that
    remat computes a second time are not needed work and do not count."""
    rows = tokens * config["num_experts_per_tok"]
    return (
        6.0 * 3 * rows * config["hidden_size"] * config["intermediate_size"]
        * config["num_hidden_layers"]
    )


def moe_kernel_bytes(config, tokens):
    """The least HBM traffic of that work: each of the nine grouped matmuls a
    layer reads its two operands and writes its result once, all bfloat16 (the
    weight gradient leaves the kernel in the weights' compute dtype)."""
    rows = tokens * config["num_experts_per_tok"]
    d, f, e = config["hidden_size"], config["intermediate_size"], config["num_experts"]
    wide, narrow, bank = rows * d * 2, rows * f * 2, e * d * f * 2
    # every one of the nine touches one wide and one narrow row matrix and a bank
    return 9.0 * (wide + narrow + bank) * config["num_hidden_layers"]


def check(config, state, seed):
    """On one seeded sequence: logits, the cross-entropy and both auxiliary
    terms against the plain reference, with the router's logits and the
    routing compared token by token (a flip only where the reference is nearer
    a tie than the logits differ); then the flash kernels and the grouped
    matmul at the step's own shapes."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import moe_lm as reference
    from edl_tpu.train import cross_entropy_loss

    n = config["check"]["sample_items"]
    t = _items(config, seed + 7, n)
    one = jax.devices()[0]
    params = jax.device_put(jax.device_get(state.params), one)
    apply_fn = state.apply_fn
    del state
    tokens, targets = jax.device_put((t[:, :-1], t[:, 1:]), one)
    layers = config["num_hidden_layers"]

    @jax.jit
    def program(params, tokens, targets):
        logits, sown = apply_fn(
            {"params": params}, tokens, mutable=["losses", "intermediates"]
        )
        ce, _ = cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
        )
        per_layer = [sown["losses"]["layer_%d" % i]["moe"] for i in range(layers)]
        seen = [sown["intermediates"]["layer_%d" % i]["moe"] for i in range(layers)]
        return logits, ce, {
            "load_balance": sum(p["load_balance"][0] for p in per_layer),
            "router_z": sum(p["router_z"][0] for p in per_layer),
        }, {
            "experts": jnp.stack([p["top_idx"][0] for p in seen]),
            "router_logits": jnp.stack([p["router_logits"][0] for p in seen]),
        }

    @jax.jit
    def plain(params, tokens, targets):
        logits, info = reference.forward(config, params, tokens)
        return logits, reference.cross_entropy(logits, targets), info

    got_logits, got_ce, got_aux, routed = program(params, tokens, targets)
    with jax.default_matmul_precision("highest"):
        want_logits, want_ce, info = plain(params, tokens, targets)
    # routing, layer by layer and token by token ([L, N])
    moved = jnp.max(jnp.abs(routed["router_logits"] - info["router_logits"]), axis=-1)
    router_rel = float(jnp.max(moved) / jnp.max(jnp.abs(info["router_logits"])))
    differs = jnp.any(
        jnp.sort(routed["experts"], axis=-1) != jnp.sort(info["experts"], axis=-1),
        axis=-1,
    )
    misrouted = int(jnp.sum(differs & (info["margin"] > 2.0 * moved)))
    flipped = jnp.any(differs, axis=0)                               # [N]
    flip_share = float(jnp.mean(flipped))
    widest_flip = float(jnp.max(jnp.where(differs, info["margin"], 0.0)))
    error = jnp.max(jnp.abs(got_logits - want_logits), axis=-1).reshape(-1)
    scale = float(jnp.max(jnp.abs(want_logits)))
    rel = float(jnp.max(jnp.where(flipped, 0.0, error))) / scale
    rel_flipped = float(jnp.max(jnp.where(flipped, error, 0.0))) / scale
    finite = bool(jnp.isfinite(got_logits).all())
    del got_logits, want_logits, params

    def relative(got, want):
        return abs(float(got) - float(want)) / abs(float(want))

    loss_rel = relative(got_ce, want_ce)
    aux_rel = {name: relative(got_aux[name], info[name]) for name in got_aux}

    b = config["train"]["batch_per_chip"]  # a model with sown losses is never split
    kernel = kernel_vs_reference(
        seed, b, config["num_attention_heads"], config["num_key_value_heads"],
        config["train"]["seq_len"], head_dim(config),
    )
    gmm = grouped_matmul_vs_reference(config, seed, b * config["train"]["seq_len"])
    ok = (
        finite and rel <= LOGITS_REL_TOL and loss_rel <= LOSS_REL_TOL
        and max(aux_rel.values()) <= AUX_REL_TOL
        and router_rel <= ROUTER_LOGITS_REL_TOL
        and misrouted == 0 and flip_share <= ROUTE_FLIP_LIMIT
        and kernel["max_rel_err"] <= KERNEL_REL_TOL
        and gmm["max_rel_err"] <= GMM_REL_TOL
    )
    return {
        "ok": bool(ok), "logits_rel_err": rel, "logits_rel_tol": LOGITS_REL_TOL,
        "loss": float(got_ce), "reference_loss": float(want_ce),
        "loss_rel_err": loss_rel, "loss_rel_tol": LOSS_REL_TOL,
        "aux": {name: float(v) for name, v in got_aux.items()},
        "reference_aux": {name: float(info[name]) for name in got_aux},
        "aux_rel_err": aux_rel, "aux_rel_tol": AUX_REL_TOL,
        "logits_rel_err_flipped_tokens": rel_flipped,
        "router_logits_rel_err": router_rel,
        "router_logits_rel_tol": ROUTER_LOGITS_REL_TOL,
        "flipped_share": flip_share, "flipped_limit": ROUTE_FLIP_LIMIT,
        "widest_flipped_margin": widest_flip,
        "tokens_misrouted": misrouted,
        "tokens_dropped": 0,  # dropless: every one of the N*k rows is computed
        "reference_load_max": [float(v) for v in info["load_max"]],
        "sample_items": n, "kernel": kernel, "kernel_rel_tol": KERNEL_REL_TOL,
        "grouped_matmul": gmm, "grouped_matmul_rel_tol": GMM_REL_TOL,
    }


def grouped_matmul_vs_reference(config, seed, tokens):
    """``grouped_matmul`` (value and both gradients, bfloat16) against a
    float32 loop over the groups on the same inputs, at the step's own shapes:
    ``tokens`` * k rows in groups drawn as a random router would fill them,
    for the gate/up shape and for the down shape."""
    import jax
    import jax.numpy as jnp

    from edl_tpu.ops.grouped_matmul import grouped_matmul

    e, k = config["num_experts"], config["num_experts_per_tok"]
    d, f = config["hidden_size"], config["intermediate_size"]
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 5)
    _, chosen = jax.lax.top_k(jax.random.normal(keys[0], (tokens, e)), k)
    sizes = np.bincount(np.asarray(chosen).reshape(-1), minlength=e)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    starts = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)[:-1]]), jnp.int32)
    rows = tokens * k
    # one group after another, each as a window of ``reach`` rows from its
    # start, masked past its size: shapes that do not depend on the sizes, so
    # every seed runs the one compiled loop
    reach = min(rows, 2 * -(-rows // e))
    if sizes.max() > reach:
        raise ValueError("a group of %d rows outgrows the reference's window" % sizes.max())

    def loop(lhs, rhs, starts, group_sizes):
        def one_group(out, group):
            start, size, weights = group
            window = jax.lax.dynamic_slice_in_dim(lhs, start, reach)
            product = jnp.where(
                (jnp.arange(reach) < size)[:, None], window @ weights, 0.0
            )
            there = jax.lax.dynamic_slice_in_dim(out, start, reach)
            return jax.lax.dynamic_update_slice_in_dim(
                out, there + product, start, 0
            ), None

        lhs = jnp.pad(lhs, ((0, reach), (0, 0)))  # the last window stays inside
        out, _ = jax.lax.scan(
            one_group, jnp.zeros((rows + reach, rhs.shape[2]), jnp.float32),
            (starts, group_sizes, rhs),
        )
        return out[:rows]

    def value_and_grads(fn):  # the groups are arguments: one program for every seed
        def run(lhs, rhs, w, starts, group_sizes):
            out, vjp = jax.vjp(lambda a, b: fn(a, b, starts, group_sizes), lhs, rhs)
            return (out, *vjp(w.astype(out.dtype)))
        return jax.jit(run)

    errs = {}
    for name, (kk, nn) in (("up", (d, f)), ("down", (f, d))):
        lhs = jax.random.normal(keys[1], (rows, kk), jnp.bfloat16)
        rhs = jax.random.normal(keys[2], (e, kk, nn), jnp.bfloat16) * kk ** -0.5
        w = jax.random.normal(keys[3], (rows, nn), jnp.bfloat16)  # cotangent
        got = value_and_grads(
            lambda a, b, starts, group_sizes: grouped_matmul(a, b, group_sizes)
        )(lhs, rhs, w, starts, group_sizes)
        with jax.default_matmul_precision("highest"):
            want = value_and_grads(loop)(
                lhs.astype(jnp.float32), rhs.astype(jnp.float32), w, starts,
                group_sizes,
            )
        for part, a, r in zip(("out", "d_lhs", "d_rhs"), got, want):
            a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
            errs["%s.%s" % (name, part)] = (
                float(np.max(np.abs(a - r)) / np.max(np.abs(r)))
                if np.isfinite(a).all() else float("inf")
            )
    return {
        "rows": rows, "groups": e, "largest_group": int(sizes.max()),
        "smallest_group": int(sizes.min()), "max_rel_err": max(errs.values()),
        **errs,
    }
