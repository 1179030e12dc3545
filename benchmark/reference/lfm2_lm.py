"""Plain reference for the ``lfm2_lm`` family: one chip's share of the decoder
that Liquid AI's LFM2-24B-A2B ``config.json`` (``model_type`` ``lfm2_moe``) and
the public ``modeling_lfm2_moe.py`` / ``modeling_lfm2.py`` of Hugging Face
``transformers`` describe. The ``config`` key or the source of each form is in
brackets.

Embedding ``h = E[token]``, no multiplier; the head is the embedding's own
matrix [LFM2 ties them]; one last RMSNorm before it [``embedding_norm``]. A
block has a norm **before** each branch (RMSNorm with a learned scale,
``norm_eps``)::

    h = h + Op(N_op(h));   h = h + FF(N_ff(h))

``Op`` of a ``conv`` layer [``layer_types``; ``Lfm2ShortConv``], on ``x = N_op(h)``::

    [B_g | C_g | x~] = x W_in              2048 -> 3 x 2048, cut in this order, no bias
    u   = B_g * x~
    c_t = sum_{k < L} w[k] * u_{t - (L - 1) + k}     L = conv_L_cache taps, depthwise,
                                           zeros before the start, no bias [conv_bias]
    out = (C_g * c) W_out                  no activation anywhere

``Op`` of a ``full_attention`` layer: ``q = x W_q`` as ``num_attention_heads``
heads of ``hidden_size / num_attention_heads``, ``k``, ``v`` as
``num_key_value_heads`` heads, no biases; q and k each through an RMSNorm over
the head's own values (one learned scale for q, one for k) [``q_layernorm``,
``k_layernorm``]; rotary positions (half-split "rotate_half", all of the head)
at base ``rope_parameters.rope_theta``; causal over the whole sequence, scores
times ``head ** -0.5``, softmax, times v; ``out = o W_o``.

The first ``num_dense_layers`` blocks feed forward through a SwiGLU of
``intermediate_size``. The others, per token ``x``, router in float32::

    s = sigmoid(W_r x)                     over all the model's experts
    e = the num_experts_per_tok largest of s + b   [use_expert_bias; b: no gradient]
    w = s[e];  w = w / (sum(w) + 1e-6) [norm_topk_prob];  w = routed_scaling_factor * w
    y = sum_j w_j * SwiGLU_{e_j}(x)        of width moe_intermediate_size; no shared expert

with no auxiliary term in the objective. After a step, from that step's counts
``c_i`` of assignments, layer by layer [the aux-loss-free rule of Wang et al.,
arXiv:2408.15664; ``train.expert_bias_rate``: assumed, the config has no key]::

    delta = rate * sign(mean(c) - c);   b <- b + delta - mean(delta)

**The share.** ``config["share"]`` says which of the ``router_experts`` this
chip holds (``experts_first`` .. ``+ num_experts``) and ``vocab_size`` is its
slice of the vocabulary. The router, the bias, the choice and the weights are
over all ``router_experts``; ``y`` sums the held experts' terms only (what the
others would add is computed on other chips and left out here, as in the
program); logits and loss are over the slice.

Straightforward ``jax.numpy`` in float32: the convolution is ``L`` shifted
products, attention a dense masked softmax (a few query heads at a time: the
[T, T] scores are dense), the experts are computed one after another over ALL
tokens and masked by the routing weights (a ``lax.scan`` over the held
experts), nothing is imported from ``edl_tpu``. It reads the program's
parameter tree by its names (``layer_i/sconv/{in_proj,out_proj}`` kernels and
``conv_kernel``; ``layer_i/attn/{q,k,v,o}`` kernels, ``{q_norm,k_norm}``
scales; ``ln1``/``ln2`` scales; ``layer_i/mlp`` or ``layer_i/moe`` with
``router`` and the banks ``gate``/``up``/``down``; ``embed``, ``ln_f``) and the
biases from ``stats["layer_i"]["moe"]["router_bias"]``.

Departures from the published model, each on purpose: the counts ``c`` are
those of the tokens of the call (one chip's step); ``rope_type`` is
``default`` in the published config and no scaling is implemented. The caller
sets ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe_lm import masked_attention, swiglu
from benchmark.reference.transformer_lm import _rms_norm

RENORM_EPS = 1e-6  # modeling_lfm2_moe.py: routing_weights / (sum + 1e-6)


def rotate(x, theta):
    """Rotary positions 0 .. T - 1 on ``x`` [B, T, H, D], written out: value
    ``i`` of the first half and value ``i`` of the second are one pair, turned
    by the angle ``position * theta ** (-2 i / D)``."""
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    frequency = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * frequency[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1
    )


def shifted(u, steps):
    """``u`` [B, T, C] moved ``steps`` later along T, zeros before the start."""
    return jnp.pad(u, ((0, 0), (steps, 0), (0, 0)))[:, :u.shape[1]]


def gated_conv(b_gate, c_gate, inner, taps):
    """``C_g * conv(B_g * x~)`` as ``L`` shifted products; ``taps`` [L, C]."""
    u = b_gate * inner
    length = taps.shape[0]
    c = sum(taps[k] * shifted(u, length - 1 - k) for k in range(length))
    return c_gate * c


def short_conv(p, x):
    """The ``conv`` layer's operator on ``x`` [B, T, D], parameters ``p``
    (``layer_i/sconv``)."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    b_gate, c_gate, inner = jnp.split(x @ f32(p["in_proj"]["kernel"]), 3, axis=-1)
    y = gated_conv(b_gate, c_gate, inner, f32(p["conv_kernel"]))
    return y @ f32(p["out_proj"]["kernel"])


def weigh(config, scores, experts):
    """The weights [N, k] of ``experts`` [N, k]: their own scores over the
    scores' sum plus 1e-6, times ``routed_scaling_factor``."""
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
    scores = jax.nn.sigmoid(logits)
    chosen_by = scores + bias if config["use_expert_bias"] else scores
    ranked = jnp.argsort(-chosen_by, axis=-1)
    experts = ranked[:, :k]
    kth = jnp.take_along_axis(chosen_by, ranked[:, k - 1:k + 1], axis=-1)
    return weigh(config, scores, experts), experts, kth[:, 0] - kth[:, 1], scores


def bias_update(config, bias, counts):
    """The bias after a step whose assignments counted ``counts`` [E]."""
    load = counts.astype(jnp.float32)
    delta = config["train"]["expert_bias_rate"] * jnp.sign(jnp.mean(load) - load)
    return bias + delta - jnp.mean(delta)


def mixture(config, p, bias, x, chosen=None):
    """This chip's part of the expert layer on tokens ``x`` [N, D] with
    parameters ``p`` (``layer_i/moe``) and the layer's ``bias`` [E]: the held
    experts' terms, and nothing else. Returns ``(y, info)``.

    ``chosen`` [N, k], if given, are the experts ``y`` is computed with, each
    weighted by the reference's OWN score for it, in place of the reference's
    own choice (which ``info`` reports all the same, with its margin). A
    comparison hands over the program's choice: near a tie a rounding rightly
    picks the other expert, and a layer that convolves over neighbouring
    tokens (or attends to them) carries one token's other expert into its
    neighbours' streams in every later layer. With the choice given, the two
    sides compute the same function of the same discrete decisions, and each
    decision is judged on its own (the caller holds it to the margin)."""
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
        (f32(p["gate"]), f32(p["up"]), f32(p["down"]),
         dense.T[first:first + held]),
    )
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
    eps, theta = config["norm_eps"], config["rope_parameters"]["rope_theta"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    embedding = f32(params["embed"]["embedding"])
    x = embedding[tokens]
    b, t, d = x.shape
    infos = []
    for i, kind in enumerate(config["layer_types"]):
        p = params["layer_%d" % i]
        h = _rms_norm(x, p["ln1"]["scale"], eps)
        if kind == "conv":
            x = x + short_conv(p["sconv"], h)
        elif kind == "full_attention":
            a = p["attn"]
            q = jnp.einsum("btd,dhk->bthk", h, f32(a["q"]["kernel"]))
            k = jnp.einsum("btd,dhk->bthk", h, f32(a["k"]["kernel"]))
            v = jnp.einsum("btd,dhk->bthk", h, f32(a["v"]["kernel"]))
            q = rotate(_rms_norm(q, a["q_norm"]["scale"], eps), theta)
            k = rotate(_rms_norm(k, a["k_norm"]["scale"], eps), theta)
            o = masked_attention(
                jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
            )
            x = x + jnp.einsum(
                "bthk,hkd->btd", jnp.swapaxes(o, 1, 2), f32(a["o"]["kernel"])
            )
        else:
            raise ValueError("lfm2_lm: layer type %r" % kind)
        h = _rms_norm(x, p["ln2"]["scale"], eps)
        if i < config["num_dense_layers"]:
            y = swiglu(p["mlp"], h)
        else:
            y, info = mixture(
                config, p["moe"], stats["layer_%d" % i]["moe"]["router_bias"],
                h.reshape(b * t, d),
                None if chosen is None else chosen[len(infos)],
            )
            infos.append(info)
            y = y.reshape(b, t, d)
        x = x + y
    x = _rms_norm(x, params["ln_f"]["scale"], eps)
    logits = x @ embedding.T
    return logits, {key: jnp.stack([info[key] for info in infos]) for key in infos[0]}


def cross_entropy(logits, targets):
    """Mean next-token cross-entropy over every position, over the slice."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(config, params, stats, tokens, targets):
    """The training objective: the cross-entropy, and nothing beside it."""
    return cross_entropy(forward(config, params, stats, tokens)[0], targets)
