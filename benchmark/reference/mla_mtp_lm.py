"""Plain reference for the ``mla_mtp_lm`` family: one chip's share of the decoder
that zai-org's GLM-4.7-Flash ``config.json`` describes (``model_type``
``glm4_moe_lite``), written from two papers: the attention of every layer is
DeepSeek-V2's multi-head latent attention WITH a query rank (arXiv:2405.04434,
section 2.1, equations 9 to 19), the expert layer DeepSeek-V3's sigmoid router
under a bias without groups (arXiv:2412.19437, section 2.1.2) and the last
module DeepSeek-V3's multi-token prediction (section 2.2, equations 21 to 25).
The ``config`` key of each form is in brackets; what no key carries is in the
configuration's ``assumed``. In float32, for tokens ``t`` ``[B, T]``::

    h = E[t]                                        untied head [tie_word_embeddings false]
    h = h + Attn_l(N(h));  h = h + FF_l(N(h))       RMSNorm, learned scale, rms_norm_eps
    hbar = N_f(h);  logits = hbar W_head;  L_main = mean cross-entropy against the next token

    latent attention, H = num_attention_heads heads:
        c_q = RMSNorm(x W_qa)                       [q_lora_rank]
        q = c_q W_qb                                H heads of qk_nope_head_dim + qk_rope_head_dim
        [c | k_r] = x W_kva                         kv_lora_rank + qk_rope_head_dim
        [k_n | v] = RMSNorm(c) W_kvb                H heads of qk_nope_head_dim + v_head_dim
        q's last qk_rope_head_dim values and k_r (one a token, shared by the heads)
        rotated, half-split, base rope_theta [partial_rotary_factor 1; rope_scaling null]
        o = softmax((q_n . k_n + q_r . k_r) (nope + rope)^-1/2 + causal mask) v
        out = o W_o                                 no gate

    layers before first_k_dense_replace feed forward through a SwiGLU of
    intermediate_size; the others, per token x, router in float32:
        s = sigmoid(W_r x)          over all the model's experts
        e = the num_experts_per_tok largest of s + b     b: the bias, no gradient [n_group 1]
        w = s[e] / (sum s[e] + 1e-20) [norm_topk_prob] * routed_scaling_factor
        y = sum_j w_j SwiGLU_{e_j}(x) + SwiGLU_shared(x)    both of moe_intermediate_size
    and after a step, from its counts c_i of assignments (Wang et al., arXiv:2408.15664):
        delta = expert_bias_rate * sign(mean(c) - c);   b <- b + delta - mean(delta)

    the multi-token module [num_nextn_predict_layers 1], positions i = 0 .. T-1:
        u_i = [N_e(E[t_{i+1}]) ; N_h(hbar_i)] W_eh  W_eh [2 hidden, hidden], the embedding half first
        g = u + Attn(N(u));  g = g + FF(N(g))       one more block, an expert layer, positions 0 .. T-1
        P_i = softmax(N_m(g_i) W_head)              E and W_head are the trunk's own
        L_mtp = mean over i = 0 .. T-3 of -log P_i[t_{i+2}]
    L = L_main + mtp_loss_weight * L_mtp

The module runs over all T positions as the program's does (``t_T`` and
``t_{T+1}`` read as id 0; positions T-2 and T-1 are not scored and, being last
under a causal mask, reach no scored one): a departure the configuration
lists. Attention is dense and masked a few heads at a time, the experts one
after another over all tokens. Nothing is imported from ``edl_tpu``. It reads
the program's parameter tree by its names (``layer_i/attn/{q_a,q_norm,q_b,
kv_a,kv_norm,kv_b,o}``; ``layer_i/mlp`` or ``layer_i/moe`` with ``router``, the
banks ``gate``/``up``/``down`` and ``shared``; ``ln1``/``ln2``/``ln_f``;
``embed``, ``lm_head``; the module's ``mtp_enorm``, ``mtp_hnorm``,
``mtp_eh_proj``, ``mtp_block`` (a layer's tree) and ``mtp_norm``) and the biases
from ``stats[<layer>]["moe"]["router_bias"]``.

**The share.** ``config["share"]`` says which of the ``router_experts`` this
chip holds (``experts_first`` .. ``+ n_routed_experts``) and ``vocab_size`` is
its slice of the vocabulary. The router, the bias, the choice and the weights
are over all ``router_experts``; ``y`` sums the held experts' terms only, plus
the shared expert's (``reference/solar_lm.py:mixture``, whose keys this
configuration shares); logits and both losses are over the slice. The heads are
whole. The caller sets ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe_lm import swiglu
from benchmark.reference.kda_lm import dense_causal_attention
from benchmark.reference.solar_lm import bias_update, mixture, route, weigh  # noqa: F401
from benchmark.reference.transformer_lm import _rms_norm, _rope


def queries(config, p, x):
    """``q`` [B, T, H, nope + rope] out of the query rank, before its rotation."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    c_q = _rms_norm(
        f32(x) @ f32(p["q_a"]["kernel"]), f32(p["q_norm"]["scale"]), config["rms_norm_eps"]
    )
    return jnp.einsum("btr,rhk->bthk", c_q, f32(p["q_b"]["kernel"]))


def latent_attention(config, p, x):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    rank, nope, rot = (
        config["kv_lora_rank"], config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    )
    theta = config["rope_theta"]
    x = f32(x)
    q = queries(config, p, x)
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
    return jnp.einsum("bthk,hkd->btd", jnp.swapaxes(o, 1, 2), f32(p["o"]["kernel"]))


def block(config, p, x, dense, bias=None, chosen=None):
    """One block on the stream ``x`` [B, T, D]: ``(x, info)``, ``info`` None
    for a ``dense`` block. ``bias`` and ``chosen``: ``solar_lm.mixture``'s."""
    eps = config["rms_norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    b, t, d = x.shape
    x = x + latent_attention(config, p["attn"], _rms_norm(x, f32(p["ln1"]["scale"]), eps))
    h = _rms_norm(x, f32(p["ln2"]["scale"]), eps)
    if dense:
        return x + swiglu(p["mlp"], h), None
    y, info = mixture(config, p["moe"], bias, h.reshape(b * t, d), chosen)
    return x + y.reshape(b, t, d), info


def expert_blocks(config):
    """The names of the blocks with an expert layer, in the order ``info`` and
    ``chosen`` stack them: the trunk's, then the module's."""
    trunk = range(config["first_k_dense_replace"], config["num_hidden_layers"])
    return ["layer_%d" % i for i in trunk] + ["mtp_block"]


def forward(config, params, stats, tokens, chosen=None):
    """``(logits, module's logits, info)``, both logits [B, T, vocab slice] in
    float32, for ``tokens`` [B, T]. ``info`` stacks the expert layers' in
    ``expert_blocks``' order: ``experts`` [L, B*T, k] (the reference's own
    choice), ``margin`` [L, B*T], ``router_logits`` and ``scores`` [L, B*T, E],
    ``counts`` and ``bias_after`` [L, E] (of the experts computed with),
    ``rows_held`` [L]. ``chosen`` [L, B*T, k]: see ``solar_lm.mixture``."""
    eps = config["rms_norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    if config["num_nextn_predict_layers"] != 1:
        raise ValueError("mla_mtp_lm reference: one multi-token module, as published")
    table, head = f32(params["embed"]["embedding"]), f32(params["lm_head"]["kernel"])
    names = expert_blocks(config)
    infos = []

    def bias_and_choice(name):
        given = None if chosen is None else chosen[names.index(name)]
        return stats[name]["moe"]["router_bias"], given

    x = table[tokens]
    for i in range(config["num_hidden_layers"]):
        name = "layer_%d" % i
        if i < config["first_k_dense_replace"]:
            x, _ = block(config, params[name], x, dense=True)
        else:
            x, info = block(config, params[name], x, False, *bias_and_choice(name))
            infos.append(info)
    hbar = _rms_norm(x, f32(params["ln_f"]["scale"]), eps)
    logits = hbar @ head
    # t_{i+1} beside hbar_i; past the end id 0, at positions that are not scored
    ahead = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    u = jnp.concatenate([
        _rms_norm(table[ahead], f32(params["mtp_enorm"]["scale"]), eps),
        _rms_norm(hbar, f32(params["mtp_hnorm"]["scale"]), eps),
    ], axis=-1) @ f32(params["mtp_eh_proj"]["kernel"])
    g, info = block(config, params["mtp_block"], u, False, *bias_and_choice("mtp_block"))
    infos.append(info)
    ahead_logits = _rms_norm(g, f32(params["mtp_norm"]["scale"]), eps) @ head
    return logits, ahead_logits, {
        key: jnp.stack([info[key] for info in infos]) for key in infos[0]
    }


def cross_entropy(logits, targets):
    """Mean cross-entropy over every position, over the slice."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def losses(logits, ahead_logits, tokens, targets):
    """``(L_main, L_mtp)``: the next token's cross-entropy over every position
    (``targets`` [B, T], the tokens shifted by one) and the module's over
    positions 0 .. T-3 against ``tokens`` shifted by two."""
    return (
        cross_entropy(logits, targets),
        cross_entropy(ahead_logits[:, :-2], tokens[:, 2:]),
    )


def objective(config, params, stats, tokens, targets, chosen=None):
    """The training objective: ``L_main + mtp_loss_weight * L_mtp``."""
    logits, ahead_logits, _ = forward(config, params, stats, tokens, chosen)
    main, ahead = losses(logits, ahead_logits, tokens, targets)
    return main + config["train"]["mtp_loss_weight"] * ahead
