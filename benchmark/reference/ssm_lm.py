"""Plain reference for the ``ssm_lm`` family: IBM's Granite 4.0-H decoder
(``model_type`` ``granitemoehybrid`` with no experts) as its public
``config.json`` and the Hugging Face ``GraniteMoeHybridForCausalLM`` describe
it. In float32, for tokens ``[B, T]``::

    x_0     = embedding_multiplier * E[tokens]
    h_l     = x_l + residual_multiplier * Mixer_l(RMSNorm(x_l))       by layer_types[l]
    x_{l+1} = h_l + residual_multiplier * W_down(silu(W_gate n) * W_up n),  n = RMSNorm(h_l)
    logits  = (RMSNorm(x_L) E^T) / logits_scaling                     E is the embedding: tied

    attention: q, k, v = W_q n, W_k n, W_v n  (no bias, no rotation, no position term)
               out = W_o softmax(attention_multiplier * q k^T + causal mask) v

    Mamba-2:   [z | xBC | dt] = W_in n             widths d_inner | d_inner + 2 G N | H
               xBC = silu(conv1d_causal_depthwise(xBC) + b_conv)
               x, B, C = split(xBC)                 x: H heads of P; B, C: G groups of N
               dt_t = softplus(dt_t + dt_bias)      per head
               S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t      A = -exp(A_log); S_0 = 0
               y_t = S_t C_t + D x_t
               out = W_out RMSNorm(y * silu(z))     over all d_inner, learned scale

The state-space layer is the **sequential recurrence** (a ``lax.scan`` over
time, one step a token, no chunks), the convolution ``mamba_d_conv`` shifted
products, attention dense and masked (one kv head at a time: the ``[T, T]``
scores are dense). Nothing is imported from ``edl_tpu.models`` or
``edl_tpu.ops``. It reads the program's parameter tree by its names
(``layer_i/mamba/{in_proj,out_proj}`` kernels, ``conv_kernel`` ``[d_conv,
C]`` whose last tap meets the current token, ``conv_bias``, ``A_log``,
``dt_bias``, ``D``, ``norm``; ``layer_i/attn/{q,k,v,o}``;
``layer_i/mlp/{gate,up,down}``; ``ln1``/``ln2``/``ln_f`` scales; ``embed``).

Departures from the published model: none in the equations. Hugging Face
clamps ``dt`` to ``time_step_limit`` (0, inf), which changes nothing. The
caller sets ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.transformer_lm import _rms_norm, loss  # noqa: F401 — loss is this family's too


def causal_attention(q, k, v, scale, q_offset=None):
    """Dense causal softmax attention with the scores times ``scale``.
    q: [B, H, Tq, D]; k, v: [B, Hkv, T, D], H a multiple of Hkv. Query row
    ``i`` sits at position ``q_offset + i`` (default: the last ``Tq``)."""
    b, h, tq, d = q.shape
    h_kv, t = k.shape[1], k.shape[2]
    if q_offset is None:
        q_offset = t - tq
    visible = (q_offset + jnp.arange(tq))[:, None] >= jnp.arange(t)[None, :]

    def one_kv_head(qkv):
        q, k, v = qkv                                    # [B, G, Tq, D], [B, T, D]
        scores = jnp.einsum("bgqd,bkd->bgqk", q, k) * scale
        scores = jnp.where(visible, scores, -jnp.inf)
        return jnp.einsum("bgqk,bkd->bgqd", jax.nn.softmax(scores, axis=-1), v)

    grouped = jnp.moveaxis(q.reshape(b, h_kv, h // h_kv, tq, d), 1, 0)
    out = jax.lax.map(
        one_kv_head, (grouped, jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0))
    )
    return jnp.moveaxis(out, 0, 1).reshape(b, h, tq, d)


def causal_conv(x, kernel, bias):
    """x [B, T, C]; ``y_t = sum_k kernel[k] x_{t - (K - 1) + k} + bias``."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(kernel[k] * padded[:, k:k + t] for k in range(taps)) + bias


def recurrence(x, dt, a, b, c, d, state=None):
    """``(y [B, T, H, P], final state [B, H, P, N])``, one step a token.
    x [B, T, H, P]; dt [B, T, H] positive; a, d [H]; b, c [B, T, G, N]."""
    batch, _, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    b, c = (jnp.repeat(m, h // g, axis=2) for m in (b, c))  # a head's own B, C

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs                        # [B,H,P] [B,H] [B,H,N] x 2
        decay = jnp.exp(dt_t * a)
        state = (
            decay[..., None, None] * state
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        )
        y_t = jnp.sum(state * c_t[:, :, None, :], axis=-1) + d[:, None] * x_t
        return state, y_t

    if state is None:
        state = jnp.zeros((batch, h, p, n), jnp.float32)
    state, y = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(m, 1, 0) for m in (x, dt, b, c))
    )
    return jnp.moveaxis(y, 0, 1), state


def mamba_mixer(config, p, n):
    """The Mamba-2 layer on normalised input ``n`` [B, T, hidden], with the
    parameters ``p`` of ``layer_i/mamba``."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    heads, width = config["mamba_n_heads"], config["mamba_d_head"]
    groups, state = config["mamba_n_groups"], config["mamba_d_state"]
    d_inner, gn = heads * width, groups * state
    batch, t, _ = n.shape
    zxbcdt = f32(n) @ f32(p["in_proj"]["kernel"])
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * gn], axis=-1)
    bias = f32(p["conv_bias"]) if config["mamba_conv_bias"] else 0.0
    xbc = jax.nn.silu(causal_conv(xbc, f32(p["conv_kernel"]), bias))
    x, b, c = jnp.split(xbc, [d_inner, d_inner + gn], axis=-1)
    y, _ = recurrence(
        x.reshape(batch, t, heads, width),
        jax.nn.softplus(dt + f32(p["dt_bias"])),
        -jnp.exp(f32(p["A_log"])),
        b.reshape(batch, t, groups, state), c.reshape(batch, t, groups, state),
        f32(p["D"]),
    )
    gated = y.reshape(batch, t, d_inner) * jax.nn.silu(z)
    return _rms_norm(gated, f32(p["norm"]), config["rms_norm_eps"]) @ f32(
        p["out_proj"]["kernel"]
    )


def attention_mixer(config, p, n):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    q, k, v = (
        jnp.einsum("btd,dhk->bhtk", f32(n), f32(p[name]["kernel"]))
        for name in ("q", "k", "v")
    )
    a = causal_attention(q, k, v, config["attention_multiplier"])
    return jnp.einsum("bhtk,hkd->btd", a, f32(p["o"]["kernel"]))


def forward(config, params, tokens):
    """Logits [B, T, vocab] in float32 for ``tokens`` [B, T]."""
    eps, res = config["rms_norm_eps"], config["residual_multiplier"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    embedding = f32(params["embed"]["embedding"])
    x = config["embedding_multiplier"] * embedding[tokens]
    for i, kind in enumerate(config["layer_types"]):
        p = params["layer_%d" % i]
        n = _rms_norm(x, f32(p["ln1"]["scale"]), eps)
        if kind == "mamba":
            x = x + res * mamba_mixer(config, p["mamba"], n)
        else:
            x = x + res * attention_mixer(config, p["attn"], n)
        n = _rms_norm(x, f32(p["ln2"]["scale"]), eps)
        gate = jax.nn.silu(n @ f32(p["mlp"]["gate"]["kernel"]))
        up = n @ f32(p["mlp"]["up"]["kernel"])
        x = x + res * ((gate * up) @ f32(p["mlp"]["down"]["kernel"]))
    x = _rms_norm(x, f32(params["ln_f"]["scale"]), eps)
    return (x @ embedding.T) / config["logits_scaling"]
