"""Family ``transformer_lm``: the program's ``TransformerLM`` (pre-norm
RMSNorm, RoPE, SwiGLU, grouped-query attention, untied float32 head) built
from a configuration file that keeps the published ``config.json`` keys.

See ``resnet_vd.py`` for what a family is.
"""

from __future__ import annotations

import numpy as np

# Logits of the program (bfloat16 operands, float32 accumulation, float32
# logits) against the float32 reference, as max |difference| over max
# |reference|. Each of the eight matrix multiplications of a layer and the
# head rounds its operands to bfloat16 (2^-9 relative a value); at depth 1
# that compounds to under one percent (my chip run, PR 22: PERF.md section
# 6). An 8-bit float type (2^-4 a value) would be near ten percent, so 0.03
# passes the stated precision and fails a lower one.
LOGITS_REL_TOL = 0.03
LOSS_REL_TOL = 0.01
# flash kernels (bfloat16 in, float32 accumulation) against dense float32
# attention on the same inputs: chip_smoke.py's tolerance and reason — p is
# rounded to bfloat16 once before an fp32-accumulated matmul, so a few
# roundings compound.
KERNEL_REL_TOL = 2e-2
# The Pallas kernels in a device trace: custom calls that XLA names after the
# flax module they sit in (``attn`` of models/transformer.py's Block). The
# forward, dq and dkv kernels differ only in their output shapes; all three
# count. Every string has to be in the operation's HLO instruction.
TRACE_KERNELS = ("%attn.", " custom-call(")


def head_dim(config):
    return config["hidden_size"] // config["num_attention_heads"]


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
    )
    opt = train["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError("transformer_lm: unknown optimizer %r" % opt["name"])

    def lm_loss(logits, targets):  # chip_smoke.py's _lm_loss
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


def _items(config, seed, n):
    rs = np.random.default_rng(seed)
    return rs.integers(
        0, config["vocab_size"], (n, config["train"]["seq_len"] + 1)
    ).astype(np.int32)


def host_batches(config, global_batch, seed, n_batches=2):
    """Distinct host batches of (tokens, next tokens), uniform from the seed
    (chip_smoke.py's token generator)."""
    pool = []
    for i in range(n_batches):
        t = _items(config, seed * 1000 + i, global_batch)
        pool.append((np.ascontiguousarray(t[:, :-1]), np.ascontiguousarray(t[:, 1:])))
    return pool


def matmul_params(config):
    """Parameters that multiply every token: the layers' projections and the
    head. The embedding is a lookup and counts for nothing."""
    d, f = config["hidden_size"], config["intermediate_size"]
    hd = head_dim(config)
    q = d * config["num_attention_heads"] * hd
    kv = 2 * d * config["num_key_value_heads"] * hd
    layer = q + kv + q + 3 * d * f
    return config["num_hidden_layers"] * layer + d * config["vocab_size"]


def attention_forward_flops(config, sequences):
    """Causal attention's forward pass over ``sequences`` sequences, all
    layers: two matrix multiplications of 2*T*T*D per head, half of each
    masked away."""
    t = config["train"]["seq_len"]
    return (
        2.0 * sequences * config["num_attention_heads"] * t * t
        * head_dim(config) * config["num_hidden_layers"]
    )


def flops_per_item(config):
    """Operations the forward and backward passes need for one token: 6 per
    matrix-multiplied parameter (2 forward, 4 backward) and three times the
    causal attention forward (its backward is four matrix multiplications
    to the forward's two). Recomputation under remat, the flash backward's
    recomputed scores, norms, RoPE, the softmax and the optimizer are not
    counted."""
    t = config["train"]["seq_len"]
    return 6.0 * matmul_params(config) + 3.0 * attention_forward_flops(config, 1) / t


def kernel_flops(config, sequences):
    """What the three flash kernels execute for ``sequences`` sequences: the
    forward, and a backward that recomputes the scores (five matrix
    multiplications to the forward's two)."""
    return 3.5 * attention_forward_flops(config, sequences)


def check(config, state, seed):
    """Forward pass and loss against the plain reference on one seeded
    sequence, then the flash kernels against dense float32 attention at the
    step's own attention shape (a copy of chip_smoke.py's
    ``_kernel_vs_reference``, with the benchmark's own dense attention)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import transformer_lm as reference
    from edl_tpu.train import cross_entropy_loss

    n = config["check"]["sample_items"]
    t = _items(config, seed + 7, n)
    one = jax.devices()[0]
    params = jax.device_put(jax.device_get(state.params), one)
    apply_fn = state.apply_fn
    del state
    tokens, targets = jax.device_put((t[:, :-1], t[:, 1:]), one)

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

    got_logits, got_loss = program(params, tokens, targets)
    with jax.default_matmul_precision("highest"):
        want_logits, want_loss = plain(params, tokens, targets)
    diff = float(jnp.max(jnp.abs(got_logits - want_logits)))
    scale = float(jnp.max(jnp.abs(want_logits)))
    finite = bool(jnp.isfinite(got_logits).all())
    del got_logits, want_logits, params
    rel = diff / scale
    loss_rel = abs(float(got_loss) - float(want_loss)) / abs(float(want_loss))

    # the numerics plane splits an even batch in two: the kernels see half
    b = config["train"]["batch_per_chip"]
    b = b // 2 if b % 2 == 0 else b
    kernel = kernel_vs_reference(
        seed, b, config["num_attention_heads"], config["num_key_value_heads"],
        config["train"]["seq_len"], head_dim(config),
    )
    ok = (
        finite and rel <= LOGITS_REL_TOL and loss_rel <= LOSS_REL_TOL
        and kernel["max_rel_err"] <= KERNEL_REL_TOL
    )
    return {
        "ok": bool(ok), "logits_rel_err": rel, "logits_rel_tol": LOGITS_REL_TOL,
        "loss": float(got_loss), "reference_loss": float(want_loss),
        "loss_rel_err": loss_rel, "loss_rel_tol": LOSS_REL_TOL,
        "sample_items": n, "kernel": kernel, "kernel_rel_tol": KERNEL_REL_TOL,
    }


def kernel_vs_reference(seed, b, h, h_kv, t, d):
    """``flash_attention`` (value and q/k/v gradients, causal, bfloat16)
    against dense float32 attention on the same inputs, a few kv heads at a
    time because the reference's [t, t] scores are dense."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.transformer_lm import causal_attention
    from edl_tpu.ops import flash_attention

    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (b, h, t, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, h_kv, t, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, h_kv, t, d), jnp.bfloat16)
    w = jax.random.normal(keys[3], (b, h, t, d), jnp.bfloat16)  # cotangent

    def value_and_grads(fn):
        def run(q, k, v, w):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out, *vjp(w.astype(out.dtype)))
        return jax.jit(run)

    got = value_and_grads(lambda q, k, v: flash_attention(q, k, v, causal=True))(
        q, k, v, w
    )
    ref_fn = value_and_grads(causal_attention)
    group = h // h_kv
    kv_chunk = max(1, 4 // group)
    parts = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, h_kv, kv_chunk):
            qs = slice(i * group, (i + kv_chunk) * group)
            ks = slice(i, i + kv_chunk)
            parts.append(ref_fn(
                q[:, qs].astype(jnp.float32), k[:, ks].astype(jnp.float32),
                v[:, ks].astype(jnp.float32), w[:, qs].astype(jnp.float32),
            ))
    want = [jnp.concatenate(p, axis=1) for p in zip(*parts)]
    errs = {}
    for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        if not np.isfinite(a).all():
            errs[name] = float("inf")
            continue
        errs[name] = float(np.max(np.abs(a - r)) / np.max(np.abs(r)))
    return {"shape": [b, h, h_kv, t, d], "max_rel_err": max(errs.values()), **errs}
