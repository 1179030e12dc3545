"""Plain reference for the ``transformer_lm`` family: the decoder of
Jiang et al., "Mistral 7B" (arXiv:2310.06825) as its public ``config.json``
and the Hugging Face ``MistralForCausalLM`` describe it — token embedding;
per layer a pre-norm RMSNorm, grouped-query causal self-attention with
rotary position embedding (half-split "rotate_half" convention, base
``rope_theta``), a residual, a second RMSNorm, a SwiGLU feed-forward
(``down(silu(gate(x)) * up(x))``), a residual; a final RMSNorm and an untied
output head; no biases. Straightforward ``jax.numpy`` in float32, dense
attention, no kernels, nothing imported from ``edl_tpu.models``. It reads
the program's parameter tree by its names (``layer_i/attn/{q,k,v,o}``,
``layer_i/mlp/{gate,up,down}``, ``ln1``/``ln2``/``ln_f`` scales, ``embed``,
``lm_head``).

Departures from the published model: ``rms_norm_eps`` comes from the
configuration file, which holds the program's 1e-6 and not the published
1e-5; the 4096-token sliding window is not applied (it equals full causal
attention for sequences up to 4096). The caller sets
``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [B, T, H, D]; rotates (x[..., :D/2], x[..., D/2:]) pairs."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_attention(q, k, v):
    """Dense causal softmax attention. q: [B, H, T, D]; k, v: [B, Hkv, T, D]
    with H a multiple of Hkv (each kv head serves H/Hkv query heads)."""
    b, h, t, d = q.shape
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def forward(config, params, tokens):
    """Logits [B, T, vocab] in float32 for ``tokens`` [B, T]."""
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(params["embed"]["embedding"])[tokens]
    for i in range(config["num_hidden_layers"]):
        p = params["layer_%d" % i]
        h = _rms_norm(x, p["ln1"]["scale"], eps)
        q = jnp.einsum("btd,dhk->bthk", h, f32(p["attn"]["q"]["kernel"]))
        k = jnp.einsum("btd,dhk->bthk", h, f32(p["attn"]["k"]["kernel"]))
        v = jnp.einsum("btd,dhk->bthk", h, f32(p["attn"]["v"]["kernel"]))
        q, k = _rope(q, theta), _rope(k, theta)
        # a few kv heads at a time: the [T, T] scores are dense
        outs = []
        group = q.shape[2] // k.shape[2]
        for j in range(k.shape[2]):
            outs.append(causal_attention(
                jnp.swapaxes(q[:, :, j * group:(j + 1) * group], 1, 2),
                jnp.swapaxes(k[:, :, j:j + 1], 1, 2),
                jnp.swapaxes(v[:, :, j:j + 1], 1, 2),
            ))
        a = jnp.swapaxes(jnp.concatenate(outs, axis=1), 1, 2)  # [B, T, H, D]
        x = x + jnp.einsum("bthk,hkd->btd", a, f32(p["attn"]["o"]["kernel"]))
        h = _rms_norm(x, p["ln2"]["scale"], eps)
        gate = jax.nn.silu(h @ f32(p["mlp"]["gate"]["kernel"]))
        up = h @ f32(p["mlp"]["up"]["kernel"])
        x = x + (gate * up) @ f32(p["mlp"]["down"]["kernel"])
    x = _rms_norm(x, params["ln_f"]["scale"], eps)
    return x @ f32(params["lm_head"]["kernel"])


def loss(logits, targets):
    """Mean next-token cross-entropy over every position."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
