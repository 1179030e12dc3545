"""Family ``gdn_lm``: the program's ``TransformerLM`` as a linear-attention /
attention hybrid (Ai2's Olmo-Hybrid): by ``layer_types`` a block's mixer is a
gated-delta-rule layer (``models/gated_delta.py`` over ``ops/gated_delta.py``
and ``ops/causal_conv.py``) or position-free full attention over a QK norm of
the whole projected width; a norm sits on each branch's output and none on its
input; every block ends in a SwiGLU; the head is untied and the vocabulary one
chip's slice of it. Built from a file that keeps the published ``config.json``
keys.

See ``resnet_vd.py`` for what a family is. The token generator is
``transformer_lm.py``'s (uniform ids of the held slice), the flash kernels'
comparison ``ssm_lm.py``'s.
"""

from __future__ import annotations

import numpy as np

from benchmark.families.ssm_lm import (  # noqa: F401 — the family's interface
    _rel,
    _rms_rel,
    kernel_vs_reference,
)
from benchmark.families.transformer_lm import (  # noqa: F401 — the family's interface
    KERNEL_REL_TOL,
    LOSS_REL_TOL,
    TRACE_KERNELS,
    _items,
    host_batches,
)

# Logits of the program (bfloat16 operands, float32 accumulation, float32
# logits) against the float32 reference, as max |difference| over max
# |reference|. This model multiplies what it rounds: a delta-rule layer's
# output is a product of three projections of one input (q, k and v) and a
# SwiGLU's of two, a norm follows every branch and none precedes it, so the
# 2^-9 a value that bfloat16 costs the first layer (0.013 of its output, root
# mean square) grows by a factor near 1.6 a layer, where ``transformer_lm.py``'s
# 0.03 serves two pre-norm layers. After the period of four at the published
# widths: 0.039..0.098 over seven seeds on the chip (my chip runs, PR 35; the
# one worst element of 103 M decides, so the reading has a tail), 0.055 on
# fresh parameters in the sandbox. The same program computed in float8_e5m2
# (2^-3 a value, the nearest precision below) reads 0.81 there, and in
# float8_e4m3fn is not finite.
LOGITS_REL_TOL = 0.25
# What the program's first linear-attention layer hands its rule (q, k, v, g,
# beta, from the trained parameters on the embedded tokens) against the
# reference's float32 forms of the same, each as max |difference| over max
# |reference|, the largest of the five. What differs is the rounding of the in
# projection's output and of the convolution's result to bfloat16 (2^-9 a
# value): 0.0052..0.0059 over seven seeds on the chip (my chip runs, PR 35). A
# beta without its factor 2 is off by a half, a dropped decay or SiLU by the
# whole value, a dropped L2 norm by the norm of a 96-wide vector
# (``benchmark/tests/test_gdn_lm.py`` shows each over twice the limit).
RULE_INPUTS_REL_TOL = 0.02
# The chunked rule alone against the step-by-step recurrence (float32, on the
# host) at the step's own shape, on those inputs as the program made them: the
# output as max |difference| over max |reference|. What differs is the
# rounding of the chunk's matmul operands (the inverse T, W, V_new, the decayed
# keys and queries and the state a chunk reads) to bfloat16: 0.0050..0.0064 on
# the chip (seven seeds, PR 35). An operand in an 8-bit float would be 2^-3 or
# 2^-4 a value where this is 2^-9: thirty times the reading.
RULE_REL_TOL = 0.02
# The state after the last step of that run, as root-mean-square difference
# over root-mean-square reference (``ssm_lm.py``'s measure: set by every
# element, not by the worst). The delta rule subtracts what the state holds
# from what it is shown, so its operands' rounding does not average out as a
# state-space scan's does: 0.0034..0.0040 on the chip (seven seeds, PR 35). It
# shares the output's limit, and cannot tell a state carried in bfloat16 from
# one carried in float32 (0.0043 against 0.0040 at the published widths, T =
# 2048, sandbox).
STATE_RMS_TOL = 0.02
# So the rule runs once more on the same inputs widened to float32, at the
# highest matmul precision: what is left is the precision of the solve and of
# the carried state. On the chip the output reads 3.3e-6..3.9e-6 and the state
# 1.9e-6..2.5e-6 (seven seeds, PR 35; the chip's float32 exp and its six-pass
# matmuls against the host's), 3e-7 in the sandbox. A state carried in
# bfloat16 is rounded once a chunk, 2^-9 of its own size each time: 0.0016 on
# top of the operands' 0.0040 at the published widths (T = 2048, sandbox),
# 0.0017..0.0018 at the toy widths (sandbox).
EXACT_STATE_RMS_TOL = 3e-4
EXACT_REL_TOL = 3e-4


def layers(config, kind):
    return sum(k == kind for k in config["layer_types"])


def head_dim(config):
    """hidden_size / the PUBLISHED number of heads: a chip that holds a share
    of the heads holds them at their published size."""
    heads = config.get("published", {}).get(
        "num_attention_heads", config["num_attention_heads"]
    )
    return config["hidden_size"] // heads


def gated_delta_spec(config):
    from edl_tpu.models import GatedDeltaSpec

    if config["linear_num_key_heads"] != config["linear_num_value_heads"]:
        raise ValueError("gdn_lm: as many value heads as key heads are built")
    return GatedDeltaSpec(
        num_heads=config["linear_num_key_heads"],
        key_dim=config["linear_key_head_dim"],
        value_dim=config["linear_value_head_dim"],
        d_conv=config["linear_conv_kernel_dim"], chunk=config["train"]["rule_chunk"],
        neg_eigval=config["linear_allow_neg_eigval"],
    )


def arch_spec(config):
    from edl_tpu.models import ArchSpec

    kinds = {"linear_attention": "linear_attention", "full_attention": "attention"}
    if config["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("gdn_lm: only position-free attention is built")
    if config["tie_word_embeddings"] or config["attention_bias"]:
        raise ValueError("gdn_lm: an untied head and no biases, as published")
    return ArchSpec(
        layer_types=tuple(kinds[kind] for kind in config["layer_types"]),
        gated_delta=gated_delta_spec(config), head_dim=head_dim(config),
        rope=False, post_norms="only",
    )


def build(config, global_batch, seed):
    import optax

    from edl_tpu.models import TransformerLM
    from edl_tpu.train import cross_entropy_loss

    train = config["train"]
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("gdn_lm: layer_types does not list num_hidden_layers layers")
    model = TransformerLM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        num_layers=config["num_hidden_layers"], d_ff=config["intermediate_size"],
        remat=train["remat"], remat_policy=train["remat_policy"],
        norm_eps=config["rms_norm_eps"], qk_norm=True, arch=arch_spec(config),
    )
    opt = train["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError("gdn_lm: unknown optimizer %r" % opt["name"])

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


def mixer_params(config):
    """The matrices of one linear-attention layer: the in projection ``[q | k
    | v | gate | b | a]`` and the out projection."""
    d = config["hidden_size"]
    h = config["linear_num_key_heads"]
    keys, values = h * config["linear_key_head_dim"], h * config["linear_value_head_dim"]
    return d * (2 * keys + 2 * values + 2 * h) + values * d


def matmul_params(config):
    """Parameters that multiply every token: a linear-attention layer's two
    projections, a full layer's four, every layer's SwiGLU, and the head over
    the held slice (as a lookup the embedding counts for nothing)."""
    d, f = config["hidden_size"], config["intermediate_size"]
    hd = head_dim(config)
    attn = 2 * d * config["num_attention_heads"] * hd + (
        2 * d * config["num_key_value_heads"] * hd
    )
    return (
        layers(config, "linear_attention") * mixer_params(config)
        + layers(config, "full_attention") * attn
        + config["num_hidden_layers"] * 3 * d * f + d * config["vocab_size"]
    )


def rule_forward_flops_per_token(config):
    """The chunked rule's products for one token of one layer, forward, a
    multiply-add as 2, at the source's chunk of 64 whatever the program's: in a
    chunk ``K K^T`` and ``Q K^T`` (half of each masked away: C d_k each), ``W =
    T (beta e^gamma K)`` and ``U = T (beta V)`` with a triangular ``T`` (C d_k
    and C d_v), ``(Q K^T o decay) V_new`` (C d_v); against the state ``W S``,
    ``Q S`` and ``K^T V_new`` (2 d_k d_v each); and the solve as forward
    substitution would do it, row ``i`` of ``T`` from the ``i`` rows before it
    (C^2 / 3 a token). The decays, the running sums and the norms are
    elementwise and count for nothing; what the doubling spends on blocks of
    zeros (12 whole [C, C] products a chunk) is not needed work."""
    chunk = 64
    d_k, d_v = config["linear_key_head_dim"], config["linear_value_head_dim"]
    head = chunk * (3 * d_k + 2 * d_v) + 6 * d_k * d_v + chunk * chunk / 3.0
    return head * config["linear_num_key_heads"]


def attention_forward_flops(config, sequences):
    """Causal attention's forward over ``sequences`` sequences, the full
    layers only: two matmuls of 2*T*T*D per head, half of each masked."""
    t = config["train"]["seq_len"]
    return (
        2.0 * sequences * config["num_attention_heads"] * t * t
        * head_dim(config) * layers(config, "full_attention")
    )


def flops_per_item(config):
    """As ``transformer_lm.flops_per_item``: 6 per matrix-multiplied
    parameter a token meets, three times the causal attention forward of the
    full layers, three times the chunked rule's forward of the linear layers.
    Recomputation under remat, the convolutions, norms, gates, the softmax and
    the optimizer are not counted."""
    t = config["train"]["seq_len"]
    return (
        6.0 * matmul_params(config)
        + 3.0 * attention_forward_flops(config, 1) / t
        + 3.0 * rule_forward_flops_per_token(config) * layers(config, "linear_attention")
    )


def kernel_flops(config, sequences):
    """What the flash kernels execute (``transformer_lm.kernel_flops``), at
    the heads this chip holds, in the full layers."""
    return 3.5 * attention_forward_flops(config, sequences)


def gdn_scan_flops(config, tokens):
    """What the rules have to compute for ``tokens`` tokens, all linear
    layers, forward and backward (the backward of a matmul is two). What remat
    computes a second time is not needed work."""
    return (
        3.0 * rule_forward_flops_per_token(config) * tokens
        * layers(config, "linear_attention")
    )


def gdn_scan_bytes(config, tokens):
    """The least HBM traffic of that work: the forward reads q, k (bfloat16),
    v (bfloat16), g and beta (float32) and writes o; the backward reads them
    and ``do`` and writes the five gradients. Nothing between (no decay matrix,
    no inverse, no chunk state) has to touch HBM."""
    h = config["linear_num_key_heads"]
    keys, values = h * config["linear_key_head_dim"], h * config["linear_value_head_dim"]
    inputs = 2 * (2 * keys + values) + 2 * 4 * h
    forward = inputs + 2 * values
    backward = inputs + 2 * values + inputs
    return float(forward + backward) * tokens * layers(config, "linear_attention")


def check(config, state, seed):
    """On one seeded sequence: logits and loss against the plain reference;
    what the first linear-attention layer hands its rule, and the chunked rule
    alone on exactly that against the step-by-step recurrence; then the flash
    kernels at the step's own shape."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import gdn_lm as reference
    from edl_tpu.train import cross_entropy_loss

    n = config["check"]["sample_items"]
    t = _items(config, seed + 7, n)
    # run.py hands over plain arrays on one device: no second copy of 3.7 GB
    params, apply_fn = state.params, state.apply_fn
    del state
    tokens, targets = t[:, :-1], t[:, 1:]

    @jax.jit
    def program(params, tokens, targets):
        logits = apply_fn({"params": params}, tokens)
        loss, _ = cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
        )
        return logits, loss

    @jax.jit
    def plain(params, tokens, targets):
        logits = reference.forward(config, params, tokens)
        return logits, reference.loss(logits, targets)

    @jax.jit
    def compare(got, want):
        return (jnp.max(jnp.abs(got - want)), jnp.max(jnp.abs(want)),
                jnp.isfinite(got).all())

    got_logits, got_loss = program(params, tokens, targets)
    with jax.default_matmul_precision("highest"):
        want_logits, want_loss = plain(params, tokens, targets)
    diff, scale, finite = compare(got_logits, want_logits)
    del got_logits, want_logits
    rel, finite = float(diff) / float(scale), bool(finite)
    loss_rel = abs(float(got_loss) - float(want_loss)) / abs(float(want_loss))

    first = config["layer_types"].index("linear_attention")
    if first != 0:
        raise ValueError("gdn_lm: the rule's check reads layer 0's input, the embedding")
    x = jnp.asarray(params["embed"]["embedding"])[tokens[:1]].astype(jnp.bfloat16)
    rule = rule_vs_reference(config, params["layer_0"]["gdn"], x)
    del params
    kernel = kernel_vs_reference(
        seed, config["train"]["batch_per_chip"], config["num_attention_heads"],
        config["num_key_value_heads"], config["train"]["seq_len"],
        head_dim(config), head_dim(config) ** -0.5,
    )
    ok = (
        finite and rel <= LOGITS_REL_TOL and loss_rel <= LOSS_REL_TOL
        and rule["inputs_rel_err"] <= RULE_INPUTS_REL_TOL
        and rule["rel_err"] <= RULE_REL_TOL
        and rule["state_rms_err"] <= STATE_RMS_TOL
        and rule["exact_rel_err"] <= EXACT_REL_TOL
        and rule["exact_state_rms_err"] <= EXACT_STATE_RMS_TOL
        and kernel["max_rel_err"] <= KERNEL_REL_TOL
    )
    return {
        "ok": bool(ok), "logits_rel_err": rel, "logits_rel_tol": LOGITS_REL_TOL,
        "loss": float(got_loss), "reference_loss": float(want_loss),
        "loss_rel_err": loss_rel, "loss_rel_tol": LOSS_REL_TOL,
        "sample_items": n, "rule": rule, "rule_inputs_rel_tol": RULE_INPUTS_REL_TOL,
        "rule_rel_tol": RULE_REL_TOL, "state_rms_tol": STATE_RMS_TOL,
        "exact_rel_tol": EXACT_REL_TOL, "exact_state_rms_tol": EXACT_STATE_RMS_TOL,
        "kernel": kernel, "kernel_rel_tol": KERNEL_REL_TOL,
    }


RULE_ARGS = ("q", "k", "v", "g", "beta")


def rule_vs_reference(config, gdn_params, x, mixer=None, rule=None):
    """The rule's inputs as the program's ``GatedDeltaMixer`` makes them from
    the parameters ``gdn_params`` of ``layer_i/gdn`` on ``x`` ``[1, T,
    hidden]`` (bfloat16) against ``reference.rule_inputs``; then
    ``gated_delta_rule`` at the configuration's chunk on the program's own
    inputs against the float32 recurrence, output and final state, as the
    step runs it (bfloat16 operands) and once more with the inputs widened to
    float32 at the highest matmul precision. ``mixer`` and ``rule`` replace
    the program's (the tests' wrong programs)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import gdn_lm as reference
    from edl_tpu.models import GatedDeltaMixer
    from edl_tpu.ops import gated_delta_rule

    spec = gated_delta_spec(config)
    if mixer is None:
        mixer = GatedDeltaMixer(spec, jnp.bfloat16, config["rms_norm_eps"]).apply

    @jax.jit
    def made(p, x):
        _, sown = mixer({"params": p}, x, mutable=["intermediates", "metrics"])
        return sown["intermediates"]["rule_inputs"][0]

    args = made(gdn_params, x)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: reference.rule_inputs(config, p, x)[:5])(
            gdn_params, x
        )
    inputs = {name: _rel(a, r) for name, a, r in zip(RULE_ARGS, args, want)}
    del want

    rule = rule or gated_delta_rule
    run = jax.jit(lambda *a: rule(*a, chunk=spec.chunk, return_final_state=True))
    got_o, got_state = run(*args)
    wide = [a.astype(jnp.float32) for a in args]
    with jax.default_matmul_precision("highest"):
        exact_o, exact_state = run(*wide)
    # on the host: the chip's float32 exp reads low by 1.3e-6 of its value near
    # 1 (my chip run, PR 29), which 8192 sequential steps of a slow head
    # compound to more than what is being measured
    host = jax.devices("cpu")[0]
    want_o, want_state = jax.jit(reference.recurrence)(*jax.device_put(wide, host))
    return {
        "shape": [list(a.shape) for a in args[:3]], "chunk": spec.chunk,
        "inputs_rel_err": max(inputs.values()), "inputs": inputs,
        "rel_err": _rel(got_o, want_o),
        "state_rms_err": _rms_rel(got_state, want_state),
        "exact_rel_err": _rel(exact_o, want_o),
        "exact_state_rms_err": _rms_rel(exact_state, want_state),
    }
