"""Plain reference for the ``nemotron_h_lm`` family: one chip's share of the
decoder that NVIDIA's Nemotron-3-Super-120B-A12B ``config.json`` (``model_type``
``nemotron_h``) describes, written from the public ``modeling_nemotron_h.py``,
Nemotron-H (arXiv:2504.03624), Mamba-2 (arXiv:2405.21060) and, for the router,
DeepSeek-V3 (arXiv:2412.19437, section 2.1.2). The ``config`` key of each form
is in brackets; what no key carries is in the configuration's ``assumed``. In
float32, for tokens ``[B, T]``::

    h = E[token]                                untied head, logits unscaled
    h = h + Branch_i(N_i(h))                    ONE branch a block, by the i-th letter of
                                                hybrid_override_pattern; RMSNorm with a learned
                                                scale, layer_norm_epsilon
    logits = N_f(h) W_head

    M, Mamba-2: H = mamba_num_heads heads of P = mamba_head_dim, G = n_groups groups of
    N = ssm_state_size, d_inner = H P:
        [z | xBC | dt] = n W_in                 widths d_inner | d_inner + 2 G N | H
        xBC = silu(conv1d_causal_depthwise_{conv_kernel}(xBC) + b_conv)     [use_conv_bias]
        x, B, C = split(xBC);   dt_t = softplus(dt_t + dt_bias)   per head
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t      A = -exp(A_log); S_0 = 0; a group's
        y_t = S_t C_t + D x_t                              B, C shared by its H / G heads
        out = W_out (RMSNorm_group(y * silu(z)) * w)      the norm over each group's
                                                d_inner / G channels (norm_before_gate false)

    *, attention: q, k, v = n W_{q,k,v} as num_attention_heads : num_key_value_heads heads
        of head_dim, no bias [attention_bias], NO position term;
        out = W_o softmax(q k^T head_dim^-1/2 + causal mask) v

    E, the expert layer (LatentMoE), per token n, router in float32:
        s = sigmoid(W_r n)                      over all the model's experts
        e = the num_experts_per_tok largest of s + b   (b: the bias, no gradient; n_group 1
                                                and topk_group 1: every expert eligible)
        w = s[e] / (sum s[e] + 1e-20) [norm_topk_prob] * routed_scaling_factor
        u = W_latent_down n                     hidden_size -> moe_latent_size
        r = sum_j w_j W2[e_j] relu(W1[e_j] u)^2          moe_intermediate_size wide
                                                [mlp_hidden_act relu2; no gate; mlp_bias false]
        y = W_latent_up r + W2_s relu(W1_s n)^2          the shared expert on n, at
                                                moe_shared_expert_intermediate_size, unscaled
    and after a step, from its counts c_i of assignments (Wang et al., arXiv:2408.15664):
        delta = expert_bias_rate * sign(mean(c) - c);   b <- b + delta - mean(delta)

The state-space layer is the **sequential recurrence** of ``reference/ssm_lm.py``
(a ``lax.scan`` over time, one step a token: the equation is the same), the
convolution shifted products, attention dense and masked a few query heads at a
time, the experts one after another over all tokens. Nothing is imported from
``edl_tpu``. It reads the program's parameter tree by its names
(``layer_i/ln1``; ``layer_i/mamba/{in_proj,out_proj}`` kernels, ``conv_kernel``
``[conv_kernel, C]`` whose last tap meets the current token, ``conv_bias``,
``A_log``, ``dt_bias``, ``D``, ``norm``; ``layer_i/attn/{q,k,v,o}``;
``layer_i/moe`` with ``router``, ``latent_down``, ``latent_up``, the banks
``up`` / ``down`` and ``shared/{up,down}``; ``ln_f``, ``embed``, ``lm_head``)
and the biases from ``stats["layer_i"]["moe"]["router_bias"]``.

**The share.** ``config["share"]`` says which of the ``router_experts`` this
chip holds (``experts_first`` .. ``+ n_routed_experts``); ``vocab_size`` is its
slice of the vocabulary; ``mamba_num_heads`` / ``n_groups`` and
``num_attention_heads`` / ``num_key_value_heads`` are the heads and groups held
here. The router, the bias, the choice and the weights are over all
``router_experts``; ``r`` sums the held experts' terms only (what the others
would add is computed on other chips and left out here, as in the program); the
latent projections, the shared expert and the norms are whole; logits and loss
are over the slice. The caller sets ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe_lm import masked_attention
from benchmark.reference.ssm_lm import causal_conv, recurrence
from benchmark.reference.transformer_lm import _rms_norm

RENORM_EPS = 1e-20
KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


def relu2(a):
    return jnp.square(jax.nn.relu(a))


def mamba_inner(config, p, n):
    """``RMSNorm_group(y * silu(z)) * w`` ``[B, T, d_inner]``: the Mamba-2
    layer before ``W_out``, on normalised input ``n`` [B, T, hidden] with the
    parameters ``p`` of ``layer_i/mamba``."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    heads, width = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, state = config["n_groups"], config["ssm_state_size"]
    d_inner, gn = heads * width, groups * state
    batch, t, _ = n.shape
    zxbcdt = f32(n) @ f32(p["in_proj"]["kernel"])
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * gn], axis=-1)
    bias = f32(p["conv_bias"]) if config["use_conv_bias"] else 0.0
    xbc = jax.nn.silu(causal_conv(xbc, f32(p["conv_kernel"]), bias))
    x, b, c = jnp.split(xbc, [d_inner, d_inner + gn], axis=-1)
    y, _ = recurrence(
        x.reshape(batch, t, heads, width),
        jax.nn.softplus(dt + f32(p["dt_bias"])),
        -jnp.exp(f32(p["A_log"])),
        b.reshape(batch, t, groups, state), c.reshape(batch, t, groups, state),
        f32(p["D"]),
    )
    gated = (y.reshape(batch, t, d_inner) * jax.nn.silu(z)).reshape(
        batch, t, groups, d_inner // groups
    )
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + config["layer_norm_epsilon"]
    )
    return normed.reshape(batch, t, d_inner) * f32(p["norm"])


def mamba_mixer(config, p, n):
    return mamba_inner(config, p, n) @ p["out_proj"]["kernel"].astype(jnp.float32)


def attention_mixer(config, p, n):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    q, k, v = (
        jnp.einsum("btd,dhk->bhtk", f32(n), f32(p[name]["kernel"]))
        for name in ("q", "k", "v")
    )
    a = masked_attention(q, k, v)          # scores / sqrt(head_dim), causal, no window
    return jnp.einsum("bhtk,hkd->btd", a, f32(p["o"]["kernel"]))


def ungated(p, x):
    """``W_down relu(W_up x)^2``, the shared expert's form."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    return relu2(x @ f32(p["up"]["kernel"])) @ f32(p["down"]["kernel"])


def weigh(config, scores, experts):
    """The weights [N, k] of ``experts`` [N, k]: their own scores over the
    scores' sum, times ``routed_scaling_factor``."""
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if config["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + RENORM_EPS)
    return config["routed_scaling_factor"] * weights


def route(config, logits, bias):
    """``(weights [N, k], experts [N, k], margin [N], scores [N, E])`` from the
    router's logits over all the model's experts: the top-k of ``s + b``,
    weighted by ``s``, and how far the k-th of ``s + b`` stands above the
    (k+1)-th (the room a rounding has before it changes the choice)."""
    k = config["num_experts_per_tok"]
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("nemotron_h_lm: one group of experts, as published")
    scores = jax.nn.sigmoid(logits)
    ranked = jnp.argsort(-(scores + bias), axis=-1)
    experts = ranked[:, :k]
    kth = jnp.take_along_axis(scores + bias, ranked[:, k - 1:k + 1], axis=-1)
    return weigh(config, scores, experts), experts, kth[:, 0] - kth[:, 1], scores


def bias_update(config, bias, counts):
    """The bias after a step whose assignments counted ``counts`` [E]."""
    load = counts.astype(jnp.float32)
    delta = config["train"]["expert_bias_rate"] * jnp.sign(jnp.mean(load) - load)
    return bias + delta - jnp.mean(delta)


def routed_latent(config, p, x, experts, weights):
    """``r`` [N, latent]: the held experts' terms of the routed sum, in the
    latent, for tokens ``x`` [N, D] whose ``experts`` [N, k] weigh ``weights``."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    share = config["share"]
    e, first, held = share["router_experts"], share["experts_first"], config["n_routed_experts"]
    n = x.shape[0]
    u = x @ f32(p["latent_down"]["kernel"])
    # [N, E]: a token's weight for each expert, 0 where it was not chosen
    dense = jnp.zeros((n, e), jnp.float32).at[jnp.arange(n)[:, None], experts].set(weights)

    def one_expert(r, expert):  # on every token, weighted by the column of ``dense``
        up, down, weight = expert
        return r + weight[:, None] * (relu2(u @ up) @ down), None

    r, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (f32(p["up"]), f32(p["down"]), dense.T[first:first + held]),
    )
    return r


def mixture(config, p, bias, x, chosen=None):
    """This chip's part of the expert layer on tokens ``x`` [N, D] with
    parameters ``p`` (``layer_i/moe``) and the layer's ``bias`` [E]: the held
    experts' terms through the latent, and the shared expert's. Returns
    ``(y, info)``.

    ``chosen`` [N, k], if given, are the experts ``y`` is computed with, each
    weighted by the reference's OWN score for it, in place of the reference's
    own choice (which ``info`` reports all the same, with its margin): near a
    tie a rounding rightly picks the other expert, and a layer that mixes
    tokens carries one token's other expert into its neighbours' streams. With
    the choice given, both sides compute the same function of the same
    discrete decisions, and each decision is judged on its own."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    share = config["share"]
    e, first, held = share["router_experts"], share["experts_first"], config["n_routed_experts"]
    if config["n_shared_experts"] != 1 or config["mlp_hidden_act"] != "relu2":
        raise ValueError("nemotron_h_lm: one shared expert and relu2, as published")
    logits = x @ f32(p["router"]["kernel"])
    weights, experts, margin, scores = route(config, logits, bias)
    own = experts
    if chosen is not None:
        experts, weights = chosen, weigh(config, scores, chosen)
    r = routed_latent(config, p, x, experts, weights)
    y = r @ f32(p["latent_up"]["kernel"]) + ungated(p["shared"], x)
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
    eps = config["layer_norm_epsilon"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(params["embed"]["embedding"])[tokens]
    b, t, d = x.shape
    infos = []
    for i, letter in enumerate(config["hybrid_override_pattern"]):
        p = params["layer_%d" % i]
        n = _rms_norm(x, f32(p["ln1"]["scale"]), eps)
        if letter == "M":
            x = x + mamba_mixer(config, p["mamba"], n)
        elif letter == "*":
            x = x + attention_mixer(config, p["attn"], n)
        elif letter == "E":
            y, info = mixture(
                config, p["moe"], stats["layer_%d" % i]["moe"]["router_bias"],
                n.reshape(b * t, d), None if chosen is None else chosen[len(infos)],
            )
            infos.append(info)
            x = x + y.reshape(b, t, d)
        else:
            raise ValueError("nemotron_h_lm reference: block %r" % (letter,))
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
