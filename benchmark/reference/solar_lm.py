"""Plain reference for the ``solar_lm`` family: one chip's share of the decoder
that upstage's Solar-Open2-250B ``config.json`` describes (``model_type``
``solar_open2``), written from three papers: the linear layer from Kimi Linear
(arXiv:2510.26692; ``flash-linear-attention``'s ``KimiDeltaAttention``, its own
gate and its low-rank pairs), the softmax layer a gated grouped-query attention
without a position term, and the expert layer from DeepSeek-V3
(arXiv:2412.19437, section 2.1.2), whose key names the config uses. The
``config`` key or the source of each form is in brackets; what no key carries
is in the configuration's ``assumed``. In float32, for tokens ``[B, T]``::

    h = E[token]                                    untied head [tie_word_embeddings false]
    h = h + Mixer_l(N(h));  h = h + FF_l(N(h))      RMSNorm, learned scale, rms_norm_eps
    logits = N_f(h) W_head

    layer l in gqa_layers: softmax attention, num_attention_heads query heads on
    num_key_value_heads key/value heads of head_dim, no bias:
        q, k, v = x W_q, x W_k, x W_v               no rotation [use_rope false], no QK norm
        o = softmax(q . k head_dim^-1/2 + causal mask) v
        out = (o * sigmoid(x W_gate)) W_o           elementwise [use_gqa_gate]

    every other layer (gqa_interval between two): Kimi delta attention,
    H = linear_attn_config.num_heads heads of d = linear_attn_config.head_dim:
        q, k, v = silu(conv1d_causal_depthwise_{short_conv_kernel_size}(x W_{q,k,v}))   no bias
        q = q / sqrt(|q|^2 + 1e-6) * d^-1/2;  k = k / sqrt(|k|^2 + 1e-6)     per head
        beta_t = 2 sigmoid(x_t W_b)                 per head [kda_allow_neg_eigval]
        g_t = -exp(A_log) softplus(x_t W_f_down W_f_up + dt_bias)   per head and key channel;
              W_f_down hidden x d, W_f_up d x H d [kda_use_full_proj false]; NO lower bound
        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T;   o_t = S_t^T q_t
        o = RMSNorm_d(o) * w * sigmoid(x W_g_down W_g_up + b_g)     per head
        out = o W_o

    every layer feeds forward through the experts [first_k_dense_replace 0], per
    token x, router in float32:
        s = sigmoid(W_r x)          over all n_routed_experts
        e = the num_experts_per_tok largest of s + b     b: the bias, no gradient
        w = s[e] / (sum s[e] + 1e-20) [norm_topk_prob] * routed_scaling_factor
        y = sum_j w_j SwiGLU_{e_j}(x) + SwiGLU_shared(x)    both of moe_intermediate_size
    and after a step, from its counts c_i of assignments (Wang et al., arXiv:2408.15664):
        delta = expert_bias_rate * sign(mean(c) - c);   b <- b + delta - mean(delta)

The delta rule is the **sequential recurrence** of ``reference/kda_lm.py`` (a
``lax.scan`` over time, one step a token: no chunks, no triangular solve, no
reference point of any exponent), the convolutions shifted products, attention
dense and masked a few heads at a time, the experts one after another over all
tokens. Nothing is imported from ``edl_tpu``. It reads the program's parameter
tree by its names (``layer_i/kda/{q,k,v,b,o}_proj``, ``{f,g}_down``,
``{f,g}_up`` (``g_up`` with a bias), ``{q,k,v}_conv`` ``[taps, H d]`` whose last
tap meets the current token, ``A_log`` ``[H]``, ``dt_bias`` ``[H, d]``, ``norm``;
``layer_i/attn/{q,k,v,g,o}``; ``layer_i/moe`` with ``router``, the banks
``gate``/``up``/``down`` and ``shared``; ``ln1``/``ln2``/``ln_f``; ``embed``,
``lm_head``) and the biases from ``stats["layer_i"]["moe"]["router_bias"]``.

**The share.** ``config["share"]`` says which of the ``router_experts`` this
chip holds (``experts_first`` .. ``+ n_routed_experts``); ``vocab_size`` is its
slice of the vocabulary; ``num_attention_heads`` on ``num_key_value_heads`` and
``linear_attn_config.num_heads`` are the heads it holds. The router, the bias,
the choice and the weights are over all ``router_experts``; ``y`` sums the held
experts' terms only, plus the shared expert's; a mixer's out projection sums
the held heads' terms only; logits and loss are over the slice. What the absent
chips would add is left out, as in the program. The caller sets
``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe_lm import masked_attention, swiglu
from benchmark.reference.kda_lm import L2_EPS, bias_update, recurrence, weigh  # noqa: F401
from benchmark.reference.ssm_lm import causal_conv
from benchmark.reference.transformer_lm import _rms_norm


def layer_kinds(config):
    """``"softmax"`` or ``"linear"`` for each layer run, from ``gqa_layers``."""
    return [
        "softmax" if i in config["gqa_layers"] else "linear"
        for i in range(config["num_hidden_layers"])
    ]


def rule_inputs(config, p, x):
    """``(q, k, v, g, beta, gate)`` of the linear layer with the parameters
    ``p`` of ``layer_i/kda`` on the block's normed input ``x``."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    linear = config["linear_attn_config"]
    h, d = linear["num_heads"], linear["head_dim"]
    batch, t, _ = x.shape
    x = f32(x)

    def conved(name):
        m = x @ f32(p[name + "_proj"]["kernel"])
        m = jax.nn.silu(causal_conv(m, f32(p[name + "_conv"]), 0.0))    # no bias
        return m.reshape(batch, t, h, d)

    q, k, v = conved("q"), conved("k"), conved("v")
    unit = lambda m: m / jnp.sqrt(jnp.sum(m * m, axis=-1, keepdims=True) + L2_EPS)  # noqa: E731
    q, k = unit(q) * d ** -0.5, unit(k)
    beta = jax.nn.sigmoid(x @ f32(p["b_proj"]["kernel"]))
    if config["kda_allow_neg_eigval"]:
        beta = 2.0 * beta
    if config["kda_use_full_proj"]:
        raise ValueError("solar_lm reference: the decay and the gate as low-rank pairs")
    f = (x @ f32(p["f_down"]["kernel"])) @ f32(p["f_up"]["kernel"])
    g = -jnp.exp(f32(p["A_log"]))[:, None] * jax.nn.softplus(
        f.reshape(batch, t, h, d) + f32(p["dt_bias"])
    )
    gate = (x @ f32(p["g_down"]["kernel"])) @ f32(p["g_up"]["kernel"]) + f32(p["g_up"]["bias"])
    return q, k, v, g, beta, gate.reshape(batch, t, h, d)


def kda_mixer(config, p, x):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    q, k, v, g, beta, gate = rule_inputs(config, p, x)
    o, _ = recurrence(q, k, v, g, beta)
    o = _rms_norm(o, f32(p["norm"]), config["rms_norm_eps"]) * jax.nn.sigmoid(gate)
    return o.reshape(o.shape[:2] + (-1,)) @ f32(p["o_proj"]["kernel"])


def gqa_mixer(config, p, x):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    if config["use_rope"] or not config["use_gqa_gate"]:
        raise ValueError("solar_lm reference: no rotation and a gate, as published")
    x = f32(x)
    q, k, v, g = (
        jnp.einsum("btd,dhk->bthk", x, f32(p[name]["kernel"])) for name in "qkvg"
    )
    o = masked_attention(*(jnp.swapaxes(m, 1, 2) for m in (q, k, v)))
    o = jnp.swapaxes(o, 1, 2) * jax.nn.sigmoid(g)                # [B, T, H, d]
    return jnp.einsum("bthk,hkd->btd", o, f32(p["o"]["kernel"]))


def route(config, logits, bias):
    """``(weights [N, k], experts [N, k], margin [N], scores [N, E])`` from the
    router's logits over all the model's experts: the top-k of ``s + b``,
    weighted by ``s``, and how far the k-th of ``s + b`` stands above the
    (k+1)-th (the room a rounding has before it changes the choice)."""
    k = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(logits)
    ranked = jnp.argsort(-(scores + bias), axis=-1)
    experts = ranked[:, :k]
    kth = jnp.take_along_axis(scores + bias, ranked[:, k - 1:k + 1], axis=-1)
    return weigh(config, scores, experts), experts, kth[:, 0] - kth[:, 1], scores


def mixture(config, p, bias, x, chosen=None):
    """This chip's part of the expert layer on tokens ``x`` [N, D] with
    parameters ``p`` (``layer_i/moe``) and the layer's ``bias`` [E]: the held
    experts' terms and the shared expert's. Returns ``(y, info)``. ``chosen``
    [N, k], if given, are the experts ``y`` is computed with, each weighted by
    the reference's OWN score for it (``reference/kda_lm.py:mixture`` says
    why); ``info`` reports the reference's own choice all the same."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    share = config["share"]
    e, first, held = share["router_experts"], share["experts_first"], config["n_routed_experts"]
    n = x.shape[0]
    logits = x @ f32(p["router"]["kernel"])
    weights, experts, margin, scores = route(config, logits, bias)
    own = experts
    if chosen is not None:
        experts, weights = chosen, weigh(config, scores, chosen)
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
        (f32(p["gate"]), f32(p["up"]), f32(p["down"]), dense.T[first:first + held]),
    )
    if config["n_shared_experts"] != 1:
        raise ValueError("solar_lm reference: %d shared experts" % config["n_shared_experts"])
    y = y + swiglu(p["shared"], x)
    counts = jnp.zeros((e,), jnp.int32).at[experts.reshape(-1)].add(1)
    info = {
        "experts": own, "margin": margin, "router_logits": logits,
        "scores": scores, "counts": counts, "bias_after": bias_update(config, bias, counts),
        "rows_held": jnp.sum(counts[first:first + held]) / experts.size,
    }
    return y, info


def forward(config, params, stats, tokens, chosen=None):
    """``(logits [B, T, vocab slice] in float32, info)`` for ``tokens`` [B, T].
    ``info`` stacks the expert layers': ``experts`` [L, B*T, k] (the
    reference's own choice), ``margin`` [L, B*T], ``router_logits`` and
    ``scores`` [L, B*T, E], ``counts`` and ``bias_after`` [L, E] (of the
    experts computed with), ``rows_held`` [L]. ``chosen`` [L, B*T, k]: see
    :func:`mixture`."""
    eps = config["rms_norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    if config["first_k_dense_replace"]:
        raise ValueError("solar_lm reference: every layer an expert layer, as published")
    x = f32(params["embed"]["embedding"])[tokens]
    b, t, d = x.shape
    infos = []
    for i, kind in enumerate(layer_kinds(config)):
        p = params["layer_%d" % i]
        h = _rms_norm(x, f32(p["ln1"]["scale"]), eps)
        if kind == "linear":
            x = x + kda_mixer(config, p["kda"], h)
        else:
            x = x + gqa_mixer(config, p["attn"], h)
        h = _rms_norm(x, f32(p["ln2"]["scale"]), eps)
        y, info = mixture(
            config, p["moe"], stats["layer_%d" % i]["moe"]["router_bias"],
            h.reshape(b * t, d), None if chosen is None else chosen[i],
        )
        infos.append(info)
        x = x + y.reshape(b, t, d)
    x = _rms_norm(x, f32(params["ln_f"]["scale"]), eps)
    logits = x @ f32(params["lm_head"]["kernel"])
    return logits, {key: jnp.stack([info[key] for info in infos]) for key in infos[0]}


def cross_entropy(logits, targets):
    """Mean next-token cross-entropy over every position, over the slice."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(config, params, stats, tokens, targets, chosen=None):
    """The training objective: the cross-entropy, and nothing beside it."""
    return cross_entropy(forward(config, params, stats, tokens, chosen)[0], targets)
