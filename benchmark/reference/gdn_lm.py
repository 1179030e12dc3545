"""Plain reference for the ``gdn_lm`` family: a decoder whose layers are, by
``layer_types``, gated-delta-rule linear attention or full softmax attention
(three to one in Ai2's Olmo-Hybrid, ``model_type`` ``olmo_hybrid``), as the
public ``config.json`` describes it. The linear layer is the ``GatedDeltaNet``
layer of Yang, Kautz and Hatamizadeh (arXiv:2412.06464) as
``flash-linear-attention`` and Hugging Face's Qwen3-Next lay it out; the
``config`` key or the source of each form is in brackets. In float32, for
tokens ``[B, T]``::

    h = E[token]                                        untied head, logits unscaled
    h = h + N_attn(Mixer_l(h));  h = h + N_ff(SwiGLU(h))        a norm on each branch's
    logits = N_f(h) W_head                                      OUTPUT, none on its input
                                   [Olmo 2's reordered norm, arXiv:2501.00656; assumed]

    full_attention:  q, k, v = h W_q, h W_k, h W_v   num_attention_heads heads of head_dim
        q, k each through an RMSNorm over the WHOLE projected width [Olmo 2; assumed]
        no rotation, no position term [rope_parameters.rope_theta null; assumed]
        out = W_o softmax(q k^T head_dim^-1/2 + causal mask) v

    linear_attention, H = linear_num_key_heads = linear_num_value_heads heads of
    d_k = linear_key_head_dim and d_v = linear_value_head_dim:
        [q | k | v] = silu(conv1d_causal_depthwise_{linear_conv_kernel_dim}(x [W_q | W_k | W_v]))   no bias
        q = q / sqrt(|q|^2 + 1e-6) * d_k^-1/2;  k = k / sqrt(|k|^2 + 1e-6)        per head
        beta_t = sigmoid(x_t W_b) * (2 if linear_allow_neg_eigval else 1)      per head
        g_t = -exp(A_log) * softplus(x_t W_a + dt_bias)                        per head
        S_t = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S_t^T k_t);  S_t += k_t u_t^T;  o_t = S_t^T q_t
        o = RMSNorm_{d_v}(o) * w * silu(x W_g)       per head, one scale w of d_v for all heads
        out = o W_o

The delta rule is the **sequential recurrence** (a ``lax.scan`` over time, one
step a token: no chunks, no triangular solve), the convolution shifted
products, attention dense and masked a few heads at a time. Nothing is
imported from ``edl_tpu``. It reads the program's parameter tree by its names
(``layer_i/gdn/in_proj`` kernel, the columns ``[q | k | v | gate | b | a]``;
``conv_kernel`` ``[taps, 2 H d_k + H d_v]`` whose last tap meets the current
token; ``A_log``, ``dt_bias``, ``norm``, ``out_proj``; ``layer_i/attn/{q,k,v,o}``
and ``{q_norm,k_norm}`` scales; ``layer_i/mlp/{gate,up,down}``;
``ln1_post``/``ln2_post``/``ln_f`` scales; ``embed``, ``lm_head``).

The share: the counts of heads are those this chip HOLDS of a layer (the
configuration's ``share`` says which of how many chips), at their published
sizes: their columns of the projections and of the convolution, their
``A_log`` and ``dt_bias``, their rows of ``W_o``; what the other heads would
add to ``W_o``'s output is computed on another chip and left out here, as in
the program, and the full layer's QK norm takes its mean square over the held
width. ``vocab_size`` is this chip's slice of the vocabulary; token ids are
drawn from it and logits and loss are over it. The SwiGLU and every norm are
whole.

Departures from the published model: what its code does where the config has
no key is assumed as above and listed in the configuration's ``assumed``; a
model code that rotates q and k in the full layers would change that line and
nothing else. The caller sets ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.ssm_lm import causal_attention, causal_conv  # dense, a kv head at a time; shifted products
from benchmark.reference.transformer_lm import _rms_norm, loss  # noqa: F401 — loss is this family's too

L2_EPS = 1e-6


def recurrence(q, k, v, g, beta, state=None):
    """``(o [B, T, H, d_v], final state [B, H, d_k, d_v])``, one step a token.
    q, k [B, T, H, d_k]; v [B, T, H, d_v]; g, beta [B, T, H]."""
    batch, _, h, d_k = q.shape
    d_v = v.shape[-1]

    def step(state, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs            # [B,H,dk] x2 [B,H,dv] [B,H] x2
        state = jnp.exp(g_t)[..., None, None] * state
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


def shapes(config):
    """(H, d_k, d_v) of a linear-attention layer."""
    return (config["linear_num_key_heads"], config["linear_key_head_dim"],
            config["linear_value_head_dim"])


def rule_inputs(config, p, x):
    """``(q, k, v, g, beta, gate)`` of the linear-attention layer with the
    parameters ``p`` of ``layer_i/gdn`` on the block's input ``x``: what the
    recurrence is run on, and the output gate's projection."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h, d_k, d_v = shapes(config)
    batch, t, _ = x.shape
    proj = f32(x) @ f32(p["in_proj"]["kernel"])
    qkv, gate, b, a = jnp.split(
        proj, [2 * h * d_k + h * d_v, 2 * h * d_k + 2 * h * d_v,
               2 * h * d_k + 2 * h * d_v + h], axis=-1,
    )
    qkv = jax.nn.silu(causal_conv(qkv, f32(p["conv_kernel"]), 0.0))   # no bias
    q, k, v = jnp.split(qkv, [h * d_k, 2 * h * d_k], axis=-1)
    q, k = (m.reshape(batch, t, h, d_k) for m in (q, k))
    unit = lambda m: m / jnp.sqrt(jnp.sum(m * m, axis=-1, keepdims=True) + L2_EPS)  # noqa: E731
    q, k = unit(q) * d_k ** -0.5, unit(k)
    beta = jax.nn.sigmoid(b) * (2.0 if config["linear_allow_neg_eigval"] else 1.0)
    g = -jnp.exp(f32(p["A_log"])) * jax.nn.softplus(a + f32(p["dt_bias"]))
    return q, k, v.reshape(batch, t, h, d_v), g, beta, gate.reshape(batch, t, h, d_v)


def linear_attention_mixer(config, p, x):
    """The gated-delta-rule layer on the block's input ``x`` [B, T, hidden]."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    q, k, v, g, beta, gate = rule_inputs(config, p, x)
    o, _ = recurrence(q, k, v, g, beta)
    o = _rms_norm(o, f32(p["norm"]), config["rms_norm_eps"]) * jax.nn.silu(gate)
    return o.reshape(o.shape[:2] + (-1,)) @ f32(p["out_proj"]["kernel"])


def attention_mixer(config, p, x):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    eps = config["rms_norm_eps"]
    q, k, v = (
        jnp.einsum("btd,dhk->bthk", f32(x), f32(p[name]["kernel"]))
        for name in ("q", "k", "v")
    )
    whole = lambda m, scale: _rms_norm(  # noqa: E731 — over all heads together
        m.reshape(m.shape[:2] + (-1,)), f32(scale), eps
    ).reshape(m.shape)
    q, k = whole(q, p["q_norm"]["scale"]), whole(k, p["k_norm"]["scale"])
    q, k, v = (jnp.swapaxes(m, 1, 2) for m in (q, k, v))
    a = causal_attention(q, k, v, q.shape[-1] ** -0.5)
    return jnp.einsum("bhtk,hkd->btd", a, f32(p["o"]["kernel"]))


def forward(config, params, tokens):
    """Logits [B, T, vocab] in float32 for ``tokens`` [B, T]."""
    eps = config["rms_norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(params["embed"]["embedding"])[tokens]
    for i, kind in enumerate(config["layer_types"]):
        p = params["layer_%d" % i]
        if kind == "linear_attention":
            mixed = linear_attention_mixer(config, p["gdn"], x)
        elif kind == "full_attention":
            mixed = attention_mixer(config, p["attn"], x)
        else:
            raise ValueError("gdn_lm reference: layer type %r" % (kind,))
        x = x + _rms_norm(mixed, f32(p["ln1_post"]["scale"]), eps)
        gate = jax.nn.silu(x @ f32(p["mlp"]["gate"]["kernel"]))
        up = x @ f32(p["mlp"]["up"]["kernel"])
        ff = (gate * up) @ f32(p["mlp"]["down"]["kernel"])
        x = x + _rms_norm(ff, f32(p["ln2_post"]["scale"]), eps)
    x = _rms_norm(x, f32(params["ln_f"]["scale"]), eps)
    return x @ f32(params["lm_head"]["kernel"])
