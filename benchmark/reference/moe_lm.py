"""Plain reference for the ``moe_lm`` family: the decoder of Muennighoff et
al., "OLMoE: Open Mixture-of-Experts Language Models" (arXiv:2409.02060) as
its public ``config.json`` and the Hugging Face ``OlmoeForCausalLM`` describe
it — token embedding; per layer a pre-norm RMSNorm, multi-head causal
self-attention whose projected q and k each pass through an RMSNorm with a
learned scale over the whole projected vector (all heads together) before
the split into heads and before rotary position embedding (half-split
"rotate_half" convention, base ``rope_theta``), a residual, a second
RMSNorm, a mixture of experts, a residual; a final RMSNorm and an untied
output head; no biases. The mixture, per token ``x``::

    p    = softmax(W_r x)                       over num_experts
    w, e = the num_experts_per_tok largest p    (w / sum(w) if norm_topk_prob)
    y    = sum_j w_j * W_down[e_j] (silu(W_gate[e_j] x) * W_up[e_j] x)

Straightforward ``jax.numpy`` in float32: every expert is computed one after
another (a ``lax.scan`` over the experts, so that the compiler sees one
body and not sixty-four) over ALL tokens and masked by the routing weights (no
sort, no grouped matmul, no capacity), attention is dense, nothing is imported from
``edl_tpu.models``. It reads the program's parameter tree by its names
(``layer_i/attn/{q,k,v,o}`` kernels and ``{q_norm,k_norm}`` scales,
``layer_i/moe/router`` kernel and the ``[E, ...]`` banks ``gate``/``up``/
``down``, ``ln1``/``ln2``/``ln_f`` scales, ``embed``, ``lm_head``).

The training loss is next-token cross-entropy plus two auxiliary terms, each
the mean over the layers of (paper section 2, "Auxiliary losses"):

- load balancing ``alpha * E * sum_i f_i * P_i``: ``P_i`` the mean of ``p_i``
  over the tokens, ``f_i`` the share of the N*k token-to-expert
  **assignments** that went to expert ``i`` (``sum_i f_i = 1``; a uniform
  router gives ``alpha``). This is how megablocks, which the published run
  used, counts (it divides by tokens * top_k); Hugging Face's
  ``load_balancing_loss_func`` counts ``f_i`` as a share of the N tokens and is
  larger by the factor k.
- router z-loss ``beta * mean(logsumexp(W_r x)^2)``.

Departures from the published model, each on purpose: the statistics ``f`` and
``P`` are taken over the tokens of the call (the published run took them per
device micro-batch); ``clip_qkv`` is null in the published config and not
implemented. The caller sets ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.transformer_lm import _rms_norm, _rope, causal_attention


def route(config, logits):
    """``(weights [N, k], experts [N, k], margin [N])`` from router logits
    ``[N, E]``: the top-k of the softmax, and how far the k-th logit stands
    above the (k+1)-th (the room a rounding has before it changes the
    choice)."""
    k = config["num_experts_per_tok"]
    probs = jax.nn.softmax(logits, axis=-1)
    ranked = jnp.argsort(-probs, axis=-1)
    experts = ranked[:, :k]
    weights = jnp.take_along_axis(probs, experts, axis=-1)
    if config["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    kth = jnp.take_along_axis(logits, ranked[:, k - 1:k + 1], axis=-1)
    return weights, experts, kth[:, 0] - kth[:, 1]


def mixture(config, p, x, coefs):
    """The expert layer on tokens ``x`` [N, D] with parameters ``p``
    (``layer_i/moe``). Returns ``(y, info)``; ``info`` holds the two auxiliary
    terms, the router's logits, the chosen experts and the routing margin."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    e = config["num_experts"]
    n = x.shape[0]
    logits = x @ f32(p["router"]["kernel"])
    weights, experts, margin = route(config, logits)
    # [N, E]: a token's weight for each expert, 0 where it was not chosen
    dense = jnp.zeros((n, e), jnp.float32).at[
        jnp.arange(n)[:, None], experts
    ].set(weights)

    def one_expert(y, expert):  # on every token, weighted by the column of ``dense``
        gate, up, down, weight = expert
        hidden = jax.nn.silu(x @ gate) * (x @ up)
        return y + weight[:, None] * (hidden @ down), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (f32(p["gate"]), f32(p["up"]), f32(p["down"]), dense.T),
    )
    # a chosen expert's weight is a softmax probability, so it is above 0
    share = jnp.sum(dense > 0, axis=0) / (n * config["num_experts_per_tok"])
    mean_prob = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
    info = {
        "load_balance": coefs["load_balance_coef"] * e * jnp.sum(share * mean_prob),
        "router_z": coefs["router_z_coef"]
        * jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
        "experts": experts,
        "margin": margin,
        "router_logits": logits,
        "load_max": jnp.max(share) * e,
    }
    return y, info


def forward(config, params, tokens):
    """``(logits [B, T, vocab] in float32, info)`` for ``tokens`` [B, T].
    ``info``: ``load_balance`` and ``router_z`` (means over the layers),
    ``experts`` [L, B*T, k], ``margin`` [L, B*T], ``router_logits``
    [L, B*T, E], ``load_max`` [L]."""
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    coefs = config["train"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(params["embed"]["embedding"])[tokens]
    b, t, d = x.shape
    infos = []
    for i in range(config["num_hidden_layers"]):
        p = params["layer_%d" % i]
        h = _rms_norm(x, p["ln1"]["scale"], eps)
        q = jnp.einsum("btd,dhk->bthk", h, f32(p["attn"]["q"]["kernel"]))
        k = jnp.einsum("btd,dhk->bthk", h, f32(p["attn"]["k"]["kernel"]))
        v = jnp.einsum("btd,dhk->bthk", h, f32(p["attn"]["v"]["kernel"]))
        # QK-norm: over the whole projected vector, all heads together
        q = _rms_norm(q.reshape(b, t, -1), p["attn"]["q_norm"]["scale"], eps).reshape(q.shape)
        k = _rms_norm(k.reshape(b, t, -1), p["attn"]["k_norm"]["scale"], eps).reshape(k.shape)
        q, k = _rope(q, theta), _rope(k, theta)
        group = q.shape[2] // k.shape[2]
        outs = []  # one kv head at a time: the [T, T] scores are dense
        for j in range(k.shape[2]):
            outs.append(causal_attention(
                jnp.swapaxes(q[:, :, j * group:(j + 1) * group], 1, 2),
                jnp.swapaxes(k[:, :, j:j + 1], 1, 2),
                jnp.swapaxes(v[:, :, j:j + 1], 1, 2),
            ))
        a = jnp.swapaxes(jnp.concatenate(outs, axis=1), 1, 2)  # [B, T, H, D]
        x = x + jnp.einsum("bthk,hkd->btd", a, f32(p["attn"]["o"]["kernel"]))
        h = _rms_norm(x, p["ln2"]["scale"], eps)
        y, info = mixture(config, p["moe"], h.reshape(b * t, d), coefs)
        infos.append(info)
        x = x + y.reshape(b, t, d)
    x = _rms_norm(x, params["ln_f"]["scale"], eps)
    logits = x @ f32(params["lm_head"]["kernel"])
    stacked = {key: jnp.stack([info[key] for info in infos]) for key in infos[0]}
    stacked["load_balance"] = jnp.mean(stacked["load_balance"])
    stacked["router_z"] = jnp.mean(stacked["router_z"])
    return logits, stacked


def cross_entropy(logits, targets):
    """Mean next-token cross-entropy over every position."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(config, params, tokens, targets):
    """The training objective: cross-entropy plus both auxiliary terms."""
    logits, info = forward(config, params, tokens)
    return cross_entropy(logits, targets) + info["load_balance"] + info["router_z"]
