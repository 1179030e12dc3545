"""Plain reference for the ``kda_lm`` family: one chip's share of the decoder
that inclusionAI's Ling-3.0-flash-VL ``config.json`` describes (the language
model's keys), written from three papers: the linear layer from Kimi Linear
(arXiv:2510.26692; ``flash-linear-attention``'s ``KimiDeltaAttention``), the
softmax layer from DeepSeek-V2's multi-head latent attention (arXiv:2405.04434,
section 2.1) and the expert layer from DeepSeek-V3 (arXiv:2412.19437, section
2.1.2). The ``config`` key or the source of each form is in brackets; what no
key carries is in the configuration's ``assumed``. In float32, for tokens
``[B, T]``::

    h = E[token]                                    untied head, logits unscaled
    h = h + Mixer_l(N(h));  h = h + FF_l(N(h))      RMSNorm, learned scale, rms_norm_eps
    logits = N_f(h) W_head

    linear_attention (Kimi delta attention), H = num_attention_heads heads of head_dim:
        q, k, v = silu(conv1d_causal_depthwise_{short_conv_kernel_size}(x W_{q,k,v}))   [linear_silu]
        q = q / sqrt(|q|^2 + 1e-6) * d^-1/2;  k = k / sqrt(|k|^2 + 1e-6)     per head [use_qk_norm]
        beta_t = sigmoid(x_t W_b)                                            per head
        g_t = kda_lower_bound * sigmoid(exp(A_log) * (x_t W_f + dt_bias))    per head and key
              channel, in (kda_lower_bound, 0) [kda_safe_gate; W_f full rank: no_kda_lora]
        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T;   o_t = S_t^T q_t
        o = RMSNorm_d(o) * w * sigmoid(x W_g)       per head [group_norm_size 1], W_g full rank
        out = o W_o

    full_attention (latent attention, q_lora_rank null):
        q = x W_q                   H heads of qk_nope_head_dim + qk_rope_head_dim
        [c | k_r] = x W_a           kv_lora_rank + qk_rope_head_dim
        [k_n | v] = RMSNorm(c) W_b  H heads of qk_nope_head_dim + v_head_dim
        q's last qk_rope_head_dim values and k_r (one a token, shared by the heads) rotated,
        half-split, base rope_theta [rotary_dim 64; use_mla_nope false; no rope_scaling key]
        o = softmax((q_n . k_n + q_r . k_r) (nope + rope)^-1/2 + causal mask) v
        out = (o * sigmoid(x W_gamma)) W_o          one gate a head
                                                    [gated_attention_proj_granularity_type head_wise]

    the first first_k_dense_replace layers feed forward through a SwiGLU of
    intermediate_size; the others, per token x, router in float32:
        s = sigmoid(W_r x)          over all the model's experts [score_function]
        c = s + b                   b: the bias, no gradient [moe_router_enable_expert_bias]
        a group's score = the sum of its two best c; the best topk_group of n_group groups kept
        e = the num_experts_per_tok largest c inside the kept groups
        w = s[e] / (sum s[e] + 1e-20) [norm_topk_prob] * routed_scaling_factor
        y = sum_j w_j SwiGLU_{e_j}(x) + SwiGLU_shared(x)    both of moe_intermediate_size
    and after a step, from its counts c_i of assignments (Wang et al., arXiv:2408.15664):
        delta = expert_bias_rate * sign(mean(c) - c);   b <- b + delta - mean(delta)

The delta rule is the **sequential recurrence** (a ``lax.scan`` over time, one
step a token: no chunks, no triangular solve), the convolutions shifted
products, attention dense and masked a few heads at a time, the experts one
after another over all tokens. Nothing is imported from ``edl_tpu``. It reads
the program's parameter tree by its names (``layer_i/kda/{q,k,v,f,b,g,o}_proj``
kernels, ``{q,k,v}_conv`` ``[taps, H d]`` whose last tap meets the current
token, ``A_log`` ``[H]``, ``dt_bias`` ``[H, d]``, ``norm``;
``layer_i/attn/{q,kv_a,kv_b,g,o}`` and ``kv_norm``; ``layer_i/mlp`` or
``layer_i/moe`` with ``router``, the banks ``gate``/``up``/``down`` and
``shared``; ``ln1``/``ln2``/``ln_f``; ``embed``, ``lm_head``) and the biases
from ``stats["layer_i"]["moe"]["router_bias"]``.

**The share.** ``config["share"]`` says which of the ``router_experts`` this
chip holds (``experts_first`` .. ``+ num_experts``) and ``vocab_size`` is its
slice of the vocabulary. The router, the bias, the groups, the choice and the
weights are over all ``router_experts``; ``y`` sums the held experts' terms
only (what the others would add is computed on other chips and left out here,
as in the program), plus the shared expert's; logits and loss are over the
slice. The caller sets ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe_lm import swiglu
from benchmark.reference.ssm_lm import causal_conv
from benchmark.reference.transformer_lm import _rms_norm, _rope

L2_EPS = 1e-6
RENORM_EPS = 1e-20
HEADS_AT_ONCE = 4  # query heads whose [T, T] scores are alive together


def recurrence(q, k, v, g, beta, state=None):
    """``(o [B, T, H, d_v], final state [B, H, d_k, d_v])``, one step a token.
    q, k, g [B, T, H, d_k]; v [B, T, H, d_v]; beta [B, T, H]."""
    batch, _, h, d_k = q.shape
    d_v = v.shape[-1]

    def step(state, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs         # [B,H,dk] x2 [B,H,dv] [B,H,dk] [B,H]
        state = jnp.exp(g_t)[..., None] * state                     # Diag(alpha) S
        held = jnp.sum(state * k_t[..., None], axis=-2)             # S^T k: [B,H,dv]
        u_t = beta_t[..., None] * (v_t - held)
        state = state + k_t[..., None] * u_t[..., None, :]
        return state, jnp.sum(state * q_t[..., None], axis=-2)

    if state is None:
        state = jnp.zeros((batch, h, d_k, d_v), jnp.float32)
    state, o = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(m, 1, 0) for m in (q, k, v, g, beta))
    )
    return jnp.moveaxis(o, 0, 1), state


def rule_inputs(config, p, x):
    """``(q, k, v, g, beta, gate)`` of the linear-attention layer with the
    parameters ``p`` of ``layer_i/kda`` on the block's normed input ``x``."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h, d = config["num_attention_heads"], config["head_dim"]
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
    f = (x @ f32(p["f_proj"]["kernel"])).reshape(batch, t, h, d)
    g = config["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(f32(p["A_log"]))[:, None] * (f + f32(p["dt_bias"]))
    )
    gate = (x @ f32(p["g_proj"]["kernel"])).reshape(batch, t, h, d)
    return q, k, v, g, beta, gate


def kda_mixer(config, p, x):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    q, k, v, g, beta, gate = rule_inputs(config, p, x)
    o, _ = recurrence(q, k, v, g, beta)
    o = _rms_norm(o, f32(p["norm"]), config["rms_norm_eps"]) * jax.nn.sigmoid(gate)
    return o.reshape(o.shape[:2] + (-1,)) @ f32(p["o_proj"]["kernel"])


def dense_causal_attention(q, k, v, scale):
    """Dense causal softmax attention, ``HEADS_AT_ONCE`` heads at a time.
    q, k [B, H, T, d_qk]; v [B, H, T, d_v]."""
    t = q.shape[2]
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    outs = []
    for first in range(0, q.shape[1], HEADS_AT_ONCE):
        heads = slice(first, first + HEADS_AT_ONCE)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q[:, heads], k[:, heads]) * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqk,bhkd->bhqd", probs, v[:, heads]))
    return jnp.concatenate(outs, axis=1)


def mla_mixer(config, p, x):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    rank, nope, rot = (
        config["kv_lora_rank"], config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    )
    theta = config["rope_theta"]
    x = f32(x)
    q = jnp.einsum("btd,dhk->bthk", x, f32(p["q"]["kernel"]))
    latent = x @ f32(p["kv_a"]["kernel"])
    c = _rms_norm(latent[..., :rank], f32(p["kv_norm"]["scale"]), config["rms_norm_eps"])
    kv = jnp.einsum("btr,rhk->bthk", c, f32(p["kv_b"]["kernel"]))
    k_r = _rope(latent[:, :, None, rank:], theta)                   # [B, T, 1, rot]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, kv.shape[:3] + (rot,))], axis=-1
    )
    o = dense_causal_attention(
        *(jnp.swapaxes(m, 1, 2) for m in (q, k, kv[..., nope:])), (nope + rot) ** -0.5
    )
    o = jnp.swapaxes(o, 1, 2) * jax.nn.sigmoid(x @ f32(p["g"]["kernel"]))[..., None]
    return jnp.einsum("bthk,hkd->btd", o, f32(p["o"]["kernel"]))


def weigh(config, scores, experts):
    """The weights [N, k] of ``experts`` [N, k]: their own scores over the
    scores' sum, times ``routed_scaling_factor``."""
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if config["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + RENORM_EPS)
    return config["routed_scaling_factor"] * weights


def route(config, logits, bias):
    """``(weights [N, k], experts [N, k], margin [N], scores [N, E])`` from the
    router's logits over all the model's experts: the groups' scores, the kept
    groups, the top-k of ``s + b`` inside them, weighted by ``s``; and the room
    a rounding has before it changes the choice: the smaller of the k-th's lead
    over the (k+1)-th inside the kept groups and HALF the last kept group's
    lead over the first dropped one (a group's score is a sum of two)."""
    k, groups, kept = (
        config["num_experts_per_tok"], config["n_group"], config["topk_group"]
    )
    if config["score_function"] != "sigmoid":
        raise ValueError("kda_lm: score_function %r" % config["score_function"])
    n, e = logits.shape
    scores = jax.nn.sigmoid(logits)
    chosen_by = scores + bias if config["moe_router_enable_expert_bias"] else scores
    by_group = jnp.sort(chosen_by.reshape(n, groups, e // groups), axis=-1)
    group_score = by_group[..., -1] + by_group[..., -2]             # [N, G]
    ranked_groups = jnp.argsort(-group_score, axis=-1)
    keep = jnp.zeros((n, groups), bool).at[
        jnp.arange(n)[:, None], ranked_groups[:, :kept]
    ].set(True)
    inside = jnp.where(jnp.repeat(keep, e // groups, axis=1), chosen_by, -jnp.inf)
    ranked = jnp.argsort(-inside, axis=-1)
    experts = ranked[:, :k]
    kth = jnp.take_along_axis(inside, ranked[:, k - 1:k + 1], axis=-1)
    margin = kth[:, 0] - kth[:, 1]
    if kept < groups:
        edge = jnp.take_along_axis(group_score, ranked_groups[:, kept - 1:kept + 1], axis=-1)
        margin = jnp.minimum(margin, 0.5 * (edge[:, 0] - edge[:, 1]))
    return weigh(config, scores, experts), experts, margin, scores


def bias_update(config, bias, counts):
    """The bias after a step whose assignments counted ``counts`` [E]."""
    load = counts.astype(jnp.float32)
    delta = config["train"]["expert_bias_rate"] * jnp.sign(jnp.mean(load) - load)
    return bias + delta - jnp.mean(delta)


def mixture(config, p, bias, x, chosen=None):
    """This chip's part of the expert layer on tokens ``x`` [N, D] with
    parameters ``p`` (``layer_i/moe``) and the layer's ``bias`` [E]: the held
    experts' terms and the shared expert's. Returns ``(y, info)``.

    ``chosen`` [N, k], if given, are the experts ``y`` is computed with, each
    weighted by the reference's OWN score for it, in place of the reference's
    own choice (which ``info`` reports all the same, with its margin): near a
    tie a rounding rightly picks the other expert, and a layer that mixes
    tokens carries one token's other expert into its neighbours' streams. With
    the choice given, both sides compute the same function of the same
    discrete decisions, and each decision is judged on its own."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    share = config["share"]
    e, first, held = share["router_experts"], share["experts_first"], config["num_experts"]
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
    y = y + swiglu(p["shared"], x)
    counts = jnp.zeros((e,), jnp.int32).at[experts.reshape(-1)].add(1)
    groups = experts // (e // config["n_group"])
    info = {
        "experts": own, "margin": margin, "router_logits": logits,
        "scores": scores, "counts": counts, "bias_after": bias_update(config, bias, counts),
        "rows_held": jnp.sum(counts[first:first + held]) / experts.size,
        "groups_live": jnp.mean(jnp.sum(
            jnp.any(groups[..., None] == jnp.arange(config["n_group"]), axis=1), axis=-1
        ).astype(jnp.float32)),
    }
    return y, info


def forward(config, params, stats, tokens, chosen=None):
    """``(logits [B, T, vocab slice] in float32, info)`` for ``tokens`` [B, T].
    ``info`` stacks the expert layers': ``experts`` [L, B*T, k] (the
    reference's own choice), ``margin`` [L, B*T], ``router_logits`` and
    ``scores`` [L, B*T, E], ``counts`` and ``bias_after`` [L, E] (of the
    experts computed with), ``rows_held`` and ``groups_live`` [L]. ``chosen``
    [L, B*T, k]: see :func:`mixture`."""
    eps = config["rms_norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(params["embed"]["embedding"])[tokens]
    b, t, d = x.shape
    infos = []
    for i, kind in enumerate(config["layer_types"]):
        p = params["layer_%d" % i]
        h = _rms_norm(x, f32(p["ln1"]["scale"]), eps)
        if kind == "linear_attention":
            x = x + kda_mixer(config, p["kda"], h)
        elif kind == "full_attention":
            x = x + mla_mixer(config, p["attn"], h)
        else:
            raise ValueError("kda_lm reference: layer type %r" % (kind,))
        h = _rms_norm(x, f32(p["ln2"]["scale"]), eps)
        if i < config["first_k_dense_replace"]:
            y = swiglu(p["mlp"], h)
        else:
            y, info = mixture(
                config, p["moe"], stats["layer_%d" % i]["moe"]["router_bias"],
                h.reshape(b * t, d), None if chosen is None else chosen[len(infos)],
            )
            infos.append(info)
            y = y.reshape(b, t, d)
        x = x + y
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
