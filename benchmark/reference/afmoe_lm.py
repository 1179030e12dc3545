"""Plain reference for the ``afmoe_lm`` family: one chip's share of the decoder
that Arcee's Trinity-Mini ``config.json`` (``model_type`` ``afmoe``) and the
public ``modeling_afmoe.py`` of Hugging Face ``transformers`` describe. The
``config`` key or the ``afmoe`` source of each form is in brackets.

Embedding ``h = E[token] * sqrt(hidden_size)`` [``mup_enabled``]; an untied
head, logits unscaled. A block has a norm before **and after** each branch
(RMSNorm with a learned scale, ``rms_norm_eps``) [afmoe]::

    h = h + N_post_attn(Attn(N_in(h)));   h = h + N_post_mlp(FF(N_pre_mlp(h)))

Attention on ``x = N_in(h)``: ``q = x W_q`` as ``num_attention_heads`` heads of
``head_dim``, ``k``, ``v`` as ``num_key_value_heads`` heads, ``g = x W_g`` of
q's width, no biases; q and k each through an RMSNorm over the head's own
``head_dim`` values (one learned scale for q, one for k) [afmoe]. In a
``sliding_attention`` layer rotary positions (half-split "rotate_half",
``rope_theta``, all of ``head_dim``) on q and k, and query ``i`` sees keys
``j`` with ``i - sliding_window < j <= i``; in a ``full_attention`` layer no
position term at all, causal [``layer_types``, ``sliding_window``; which
layers rotate: afmoe]. Scores times ``head_dim ** -0.5``, softmax, times v;
``out = (o * sigmoid(g)) W_o``, the gate elementwise [afmoe].

The first ``num_dense_layers`` blocks feed forward through a SwiGLU of
``intermediate_size``. The others, per token ``x``, router in float32::

    s = sigmoid(W_r x)                     over all the model's experts [score_func]
    e = the num_experts_per_tok largest of s + b   (b: the bias, no gradient)
    w = s[e];  w = w / (sum(w) + 1e-20) [route_norm];  w = route_scale * w
    y = sum_j w_j * SwiGLU_{e_j}(x) + SwiGLU_shared(x)      [num_shared_experts]

with every expert and the shared one of width ``moe_intermediate_size``, and
no auxiliary term in the objective. After a step, from that step's counts
``c_i`` of assignments, layer by layer [``load_balance_coeff``; the
aux-loss-free rule of Wang et al., arXiv:2408.15664, as the model's training
code applies it]::

    delta = load_balance_coeff * sign(mean(c) - c);   b <- b + delta - mean(delta)

**The share.** ``config["share"]`` says which of the ``router_experts`` this
chip holds (``experts_first`` .. ``+ num_experts``) and ``vocab_size`` is its
slice of the vocabulary. The router, the bias, the choice and the weights are
over all ``router_experts``; ``y`` sums the held experts' terms only (what the
others would add is computed on other chips and left out here, as in the
program), plus the shared expert's; logits and loss are over the slice.

Straightforward ``jax.numpy`` in float32: attention is a dense masked softmax
for both kinds of layer (a few query heads at a time: the [T, T] scores are
dense), the experts are computed one after another over ALL tokens and masked
by the routing weights (a ``lax.scan`` over the held experts), nothing is
imported from ``edl_tpu``. It reads the program's parameter tree by its names
(``layer_i/attn/{q,k,v,g,o}`` kernels, ``{q_norm,k_norm}`` scales,
``ln1``/``ln1_post``/``ln2``/``ln2_post`` scales, ``layer_i/mlp`` or
``layer_i/moe`` with ``router``, the banks ``gate``/``up``/``down`` and
``shared``; ``embed``, ``ln_f``, ``lm_head``) and the biases from
``stats["layer_i"]["moe"]["router_bias"]``.

Departures from the published model, each on purpose: the counts ``c`` are
those of the tokens of the call (one chip's step), where the published run
summed them over its data-parallel group; ``rope_scaling`` is null in the
published config and not implemented; grouped routing (``n_group``,
``topk_group``) is 1 in the published config, which is no grouping. The caller
sets ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.transformer_lm import _rms_norm, _rope

HEADS_AT_ONCE = 4  # query heads whose [T, T] scores are alive together


def masked_attention(q, k, v, window=None):
    """Dense causal softmax attention, over the ``window`` newest keys if one
    is given. q: [B, H, T, D]; k, v: [B, Hkv, T, D], each kv head serving
    H / Hkv query heads."""
    b, h, t, d = q.shape
    group = h // k.shape[1]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)
    outs = []
    for first in range(0, h, HEADS_AT_ONCE):
        heads = range(first, min(first + HEADS_AT_ONCE, h))
        kv = jnp.asarray([head // group for head in heads])
        scores = jnp.einsum(
            "bhqd,bhkd->bhqk", q[:, first:first + len(heads)], k[:, kv]
        ) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqk,bhkd->bhqd", probs, v[:, kv]))
    return jnp.concatenate(outs, axis=1)


def swiglu(p, x):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    hidden = jax.nn.silu(x @ f32(p["gate"]["kernel"])) * (x @ f32(p["up"]["kernel"]))
    return hidden @ f32(p["down"]["kernel"])


def route(config, logits, bias):
    """``(weights [N, k], experts [N, k], margin [N], scores [N, E])`` from the
    router's logits over all the model's experts: the top-k of ``s + b``,
    weighted by ``s``, and how far the k-th of ``s + b`` stands above the
    (k+1)-th (the room a rounding has before it changes the choice)."""
    k = config["num_experts_per_tok"]
    if config["score_func"] != "sigmoid":
        raise ValueError("afmoe_lm: score_func %r" % config["score_func"])
    scores = jax.nn.sigmoid(logits)
    ranked = jnp.argsort(-(scores + bias), axis=-1)
    experts = ranked[:, :k]
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if config["route_norm"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = config["route_scale"] * weights
    kth = jnp.take_along_axis(scores + bias, ranked[:, k - 1:k + 1], axis=-1)
    return weights, experts, kth[:, 0] - kth[:, 1], scores


def bias_update(config, bias, counts):
    """The bias after a step whose assignments counted ``counts`` [E]."""
    load = counts.astype(jnp.float32)
    delta = config["load_balance_coeff"] * jnp.sign(jnp.mean(load) - load)
    return bias + delta - jnp.mean(delta)


def mixture(config, p, bias, x):
    """This chip's part of the expert layer on tokens ``x`` [N, D] with
    parameters ``p`` (``layer_i/moe``) and the layer's ``bias`` [E]: the held
    experts' terms and the shared expert's. Returns ``(y, info)``."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    share = config["share"]
    e, first, held = share["router_experts"], share["experts_first"], config["num_experts"]
    n = x.shape[0]
    logits = x @ f32(p["router"]["kernel"])
    weights, experts, margin, scores = route(config, logits, bias)
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
    if config["num_shared_experts"] != 1:
        raise ValueError("afmoe_lm: %d shared experts" % config["num_shared_experts"])
    y = y + swiglu(p["shared"], x)
    counts = jnp.zeros((e,), jnp.int32).at[experts.reshape(-1)].add(1)
    info = {
        "experts": experts, "margin": margin, "router_logits": logits,
        "scores": scores, "counts": counts, "bias_after": bias_update(config, bias, counts),
        "rows_held": jnp.sum(counts[first:first + held]) / experts.size,
    }
    return y, info


def forward(config, params, stats, tokens):
    """``(logits [B, T, vocab slice] in float32, info)`` for ``tokens`` [B, T].
    ``info`` stacks the expert layers': ``experts`` [L, B*T, k], ``margin``
    [L, B*T], ``router_logits`` and ``scores`` [L, B*T, E], ``counts`` and
    ``bias_after`` [L, E], ``rows_held`` [L]."""
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(params["embed"]["embedding"])[tokens]
    if config["mup_enabled"]:
        x = x * jnp.sqrt(jnp.float32(config["hidden_size"]))
    b, t, d = x.shape
    infos = []
    for i, kind in enumerate(config["layer_types"]):
        p = params["layer_%d" % i]
        a = p["attn"]
        h = _rms_norm(x, p["ln1"]["scale"], eps)
        q = jnp.einsum("btd,dhk->bthk", h, f32(a["q"]["kernel"]))
        k = jnp.einsum("btd,dhk->bthk", h, f32(a["k"]["kernel"]))
        v = jnp.einsum("btd,dhk->bthk", h, f32(a["v"]["kernel"]))
        g = jnp.einsum("btd,dhk->bthk", h, f32(a["g"]["kernel"]))
        q = _rms_norm(q, a["q_norm"]["scale"], eps)  # over each head's own values
        k = _rms_norm(k, a["k_norm"]["scale"], eps)
        if kind == "sliding_attention":
            q, k, window = _rope(q, theta), _rope(k, theta), config["sliding_window"]
        elif kind == "full_attention":
            window = None
        else:
            raise ValueError("afmoe_lm: layer type %r" % kind)
        o = masked_attention(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
            window,
        )
        o = jnp.swapaxes(o, 1, 2) * jax.nn.sigmoid(g)            # [B, T, H, D]
        o = jnp.einsum("bthk,hkd->btd", o, f32(a["o"]["kernel"]))
        x = x + _rms_norm(o, p["ln1_post"]["scale"], eps)
        h = _rms_norm(x, p["ln2"]["scale"], eps)
        if i < config["num_dense_layers"]:
            y = swiglu(p["mlp"], h)
        else:
            y, info = mixture(
                config, p["moe"], stats["layer_%d" % i]["moe"]["router_bias"],
                h.reshape(b * t, d),
            )
            infos.append(info)
            y = y.reshape(b, t, d)
        x = x + _rms_norm(y, p["ln2_post"]["scale"], eps)
    x = _rms_norm(x, params["ln_f"]["scale"], eps)
    logits = x @ f32(params["lm_head"]["kernel"])
    return logits, {key: jnp.stack([info[key] for info in infos]) for key in infos[0]}


def cross_entropy(logits, targets):
    """Mean next-token cross-entropy over every position, over the slice."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(config, params, stats, tokens, targets):
    """The training objective: the cross-entropy, and nothing beside it."""
    return cross_entropy(forward(config, params, stats, tokens)[0], targets)
