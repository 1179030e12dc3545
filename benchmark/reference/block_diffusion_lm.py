"""Plain reference for the ``block_diffusion_lm`` family: one chip's share of
the decoder that JetLM's SDAR-30B-A3B-Chat ``config.json`` describes
(``model_type`` ``sdar_moe``; SDAR, arXiv:2510.06303), under the training step
of block diffusion (BD3-LM, arXiv:2503.09573). The ``config`` key of each form
is in brackets; what no key carries is in the configuration's ``assumed``. In
float32::

    data: x_0, L ids. Forward process, by block b(i) = i // B of B positions
    [train.block_diffusion.block]:
      t_b ~ U(t_min, 1],  u_i ~ U[0, 1),  m_i = [u_i < t_b(i)]
      x_t,i = MASK if m_i else x_0,i            [train.block_diffusion.mask_id]
    the model's input is [x_0 ; x_t], 2 L positions, position i of either half
    at rotary position i

    block l, on the stream x [2 L, D]
      h   = x + Attn(N_1 x)                     RMSNorm, learned scale [rms_norm_eps]
      u   = N_2 h
      r   = softmax(u W_r)                      over all the model's experts, float32
      e   = the num_experts_per_tok largest of r
      w_e = r_e / sum_chosen r                  [norm_topk_prob]
      y   = sum_{e chosen and held} w_e W_down,e(silu(W_gate,e u) * W_up,e u)
      x'  = h + y                               [moe_intermediate_size; no shared expert]

    Attn(n): q = N_q(n W_q), k = N_k(n W_k) normed over each head's head_dim values,
      v = n W_v  (num_attention_heads, num_key_value_heads heads, no bias)
      q, k rotated (half-split, rope_theta, rope_scaling null) at position i mod L
      s_ij = q_i . k_j / sqrt(head_dim), query head g reading key head g // group
      softmax over the VISIBLE j;  (heads' outputs) W_o
    Visible(i, j), c(i) = [i < L] (clean), b(i) = (i mod L) // B:
      i clean:  j clean and b(j) <= b(i)
      i noised: (j clean and b(j) < b(i))  or  (j noised and b(j) = b(i))

    logits_i = N_f(x^last_{L+i}) W_head  for the L noised positions [tie_word_embeddings false]
    loss = 1 / (batch L) sum_i (m_i / t_b(i)) CE(logits_i, x_0,i)          no shift
           + mean over the layers of load_balance_coef * E * sum_e f_e P_e

with ``f_e`` the share of the assignments (all ``2 L`` positions') that went to
expert ``e`` and ``P_e`` the mean over the positions of ``r_e``.

**The share.** ``config["share"]`` says which of the ``router_experts`` this
chip holds (``experts_first`` .. ``+ num_experts``) and ``vocab_size`` is its
slice of the vocabulary. The router, the choice and the weights are over all
``router_experts``; ``y`` sums the held experts' terms only; logits and loss
are over the slice. The heads are whole.

Straightforward ``jax.numpy``: the mask is a dense boolean array from the
definition above, attention a dense masked softmax over ALL ``2 L`` keys,
``QUERY_BLOCK`` queries at a time (a ``lax.map``: at 16,384 positions a head's
whole scores would be a gigabyte), the experts one after another over all
positions and masked by the routing weights; nothing is imported from
``edl_tpu``. It reads the program's parameter tree by its names
(``layer_i/attn/{q,k,v,o}`` kernels, ``q_norm``/``k_norm``/``ln1``/``ln2``
scales, ``layer_i/moe`` with ``router`` and the banks ``gate``/``up``/``down``;
``embed``, ``ln_f``, ``lm_head``). The caller sets
``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.transformer_lm import _rms_norm

QUERY_BLOCK = 512  # queries whose scores against all 2 L keys are alive together


def forward_process(x0, t, u, block, mask_id):
    """``(x_t [B, L], m [B, L] bool, weights [B, L])`` from ``x_0`` [B, L],
    a noise level a block ``t`` [B, L / block] and a draw a position ``u``
    [B, L]: ``m_i = [u_i < t_b(i)]``, ``x_t,i = MASK if m_i else x_0,i``,
    ``weights_i = m_i / t_b(i)``. numpy, in float64."""
    x0, t, u = np.asarray(x0), np.asarray(t, np.float64), np.asarray(u, np.float64)
    length = x0.shape[1]
    of_position = np.stack(
        [t[:, i // block] for i in range(length)], axis=1
    )
    m = u < of_position
    x_t = np.where(m, mask_id, x0)
    return x_t, m, m / of_position


def visible(i, j, length, block):
    """The definition, on broadcastable position arrays: whether query ``i``
    sees key ``j`` of the ``2 * length`` positions."""
    clean_i, clean_j = i < length, j < length
    block_i, block_j = (i % length) // block, (j % length) // block
    return jnp.where(
        clean_i,
        clean_j & (block_j <= block_i),
        (clean_j & (block_j < block_i)) | (~clean_j & (block_j == block_i)),
    )


def masked_attention(q, k, v, length, block, rows=QUERY_BLOCK):
    """Dense softmax attention under the block-diffusion mask, ``rows`` queries
    at a time. q: [B, H, 2 L, D]; k, v: [B, Hkv, 2 L, D], each kv head serving
    H / Hkv query heads."""
    b, h, t, d = q.shape
    h_kv = k.shape[1]
    rows = min(rows, t)
    if t != 2 * length or t % rows:
        raise ValueError(
            "block_diffusion_lm: %d queries over 2 x %d in blocks of %d" % (t, length, rows)
        )
    grouped = q.reshape(b, h_kv, h // h_kv, t // rows, rows, d)

    def some_rows(args):
        q_rows, first = args                                 # [B, Hkv, G, rows, D]
        seen = visible(
            first + jnp.arange(rows)[:, None], jnp.arange(t)[None, :], length, block
        )
        scores = jnp.einsum("bngqd,bnkd->bngqk", q_rows, k) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bngqk,bnkd->bngqd", probs, v)

    out = jax.lax.map(
        jax.checkpoint(some_rows),
        (jnp.moveaxis(grouped, 3, 0), jnp.arange(t // rows) * rows),
    )
    return jnp.moveaxis(out, 0, 3).reshape(b, h, t, d)


def _rope(x, positions, theta):
    """x: [B, T, H, D] rotated at ``positions`` [T]; pairs (x[..., :D/2], x[..., D/2:])."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def weigh(probs, experts):
    """The chosen experts' probabilities over their sum."""
    chosen = jnp.take_along_axis(probs, experts, axis=-1)
    return chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def route(config, logits):
    """``(weights [N, k], experts [N, k], margin [N])`` from the router's logits
    over all the model's experts: the k largest of the softmax, renormalised,
    and how far the k-th logit stands above the (k+1)-th (the room a rounding
    has before it changes the choice)."""
    k = config["num_experts_per_tok"]
    if not config["norm_topk_prob"]:
        raise ValueError("block_diffusion_lm: weights renormalised over the chosen, as published")
    probs = jax.nn.softmax(logits, axis=-1)
    ranked = jnp.argsort(-probs, axis=-1)
    experts = ranked[:, :k]
    kth = jnp.take_along_axis(logits, ranked[:, k - 1:k + 1], axis=-1)
    return weigh(probs, experts), experts, kth[:, 0] - kth[:, 1]


def mixture(config, p, x, chosen=None):
    """This chip's part of the expert layer with parameters ``p``
    (``layer_i/moe``) on the normed stream ``x`` [N, D]. Returns ``(y, info)``.
    ``chosen`` [N, k], if given, are the experts ``y`` and the counts are
    computed with, each weighted by the reference's OWN probabilities for it
    (attention carries a position's other expert into its neighbours' streams,
    so a comparison of streams computes with one choice); ``info["experts"]``
    is the reference's own choice all the same."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    share = config["share"]
    e, first = share["router_experts"], share["experts_first"]
    held, k = config["num_experts"], config["num_experts_per_tok"]
    n = x.shape[0]
    logits = x @ f32(p["router"]["kernel"])
    weights, experts, margin = route(config, logits)
    own = experts
    probs = jax.nn.softmax(logits, axis=-1)
    if chosen is not None:
        experts, weights = chosen, weigh(probs, chosen)
    # [N, E]: a position's weight for each expert, 0 where it was not chosen
    dense = jnp.zeros((n, e), jnp.float32).at[jnp.arange(n)[:, None], experts].set(weights)
    picked = jnp.zeros((n, e), bool).at[jnp.arange(n)[:, None], experts].set(True)

    def one_expert(y, expert):  # on every position, weighted by the column of ``dense``
        gate, up, down, weight = expert
        hidden = jax.nn.silu(x @ gate) * (x @ up)
        return y + weight[:, None] * (hidden @ down), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (f32(p["gate"]), f32(p["up"]), f32(p["down"]), dense.T[first:first + held]),
    )
    counts = jnp.sum(picked, axis=0)
    info = {
        "load_balance": config["train"]["load_balance_coef"] * e
        * jnp.sum(counts / (n * k) * jnp.mean(probs, axis=0)),
        "experts": own, "margin": margin, "router_logits": logits, "counts": counts,
        "rows_held": jnp.sum(counts[first:first + held]) / (n * k),
    }
    return y, info


def attention(config, a, x, length):
    """``Attn`` on the normed stream ``x`` [B, 2 L, D] with parameters ``a``
    (``layer_i/attn``)."""
    f32 = lambda m: m.astype(jnp.float32)  # noqa: E731
    eps = config["rms_norm_eps"]
    if config["rope_scaling"] is not None or config["use_sliding_window"]:
        raise ValueError("block_diffusion_lm: rope_scaling null and no window, as published")
    q = jnp.einsum("btd,dhk->bthk", x, f32(a["q"]["kernel"]))
    k = jnp.einsum("btd,dhk->bthk", x, f32(a["k"]["kernel"]))
    v = jnp.einsum("btd,dhk->bthk", x, f32(a["v"]["kernel"]))
    q = _rms_norm(q, f32(a["q_norm"]["scale"]), eps)   # over each head's own values
    k = _rms_norm(k, f32(a["k_norm"]["scale"]), eps)
    positions = jnp.arange(2 * length) % length
    q, k = _rope(q, positions, config["rope_theta"]), _rope(k, positions, config["rope_theta"])
    o = masked_attention(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        length, config["train"]["block_diffusion"]["block"],
    )
    return jnp.einsum("bthk,hkd->btd", jnp.swapaxes(o, 1, 2), f32(a["o"]["kernel"]))


def forward(config, params, tokens, chosen=None):
    """``(logits [B, L, vocab slice] in float32 of the noised half, info)`` for
    ``tokens`` [B, 2 L], ``x_0`` then ``x_t``. ``info``: ``load_balance`` (the
    mean over the layers), and stacked over the layers ``experts`` [layers,
    B * 2 L, k] (the reference's own choice), ``margin``, ``router_logits``,
    ``counts`` [layers, E] and ``rows_held``. ``chosen`` [layers, B * 2 L, k]:
    see ``mixture``."""
    eps = config["rms_norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    if config["tie_word_embeddings"] or config["mlp_only_layers"] or (
        config["decoder_sparse_step"] != 1
    ):
        raise ValueError(
            "block_diffusion_lm: an untied head and an expert layer in every block, as published"
        )
    x = f32(params["embed"]["embedding"])[tokens]
    b, t, d = x.shape
    length = t // 2
    infos = []
    for i in range(config["num_hidden_layers"]):
        p = params["layer_%d" % i]
        x = x + attention(
            config, p["attn"], _rms_norm(x, f32(p["ln1"]["scale"]), eps), length
        )
        y, info = mixture(
            config, p["moe"], _rms_norm(x, f32(p["ln2"]["scale"]), eps).reshape(b * t, d),
            None if chosen is None else chosen[i],
        )
        infos.append(info)
        x = x + y.reshape(b, t, d)
    x = _rms_norm(x[:, length:], f32(params["ln_f"]["scale"]), eps)
    logits = x @ f32(params["lm_head"]["kernel"])
    stacked = {key: jnp.stack([info[key] for info in infos]) for key in infos[0]}
    stacked["load_balance"] = jnp.mean(stacked["load_balance"])
    return logits, stacked


def weighted_cross_entropy(logits, labels, weights):
    """``1 / (B L) sum_i weights_i CE(logits_i, labels_i)``: a masked position
    predicts its own token, at ``1 / t`` of its block."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(weights.astype(jnp.float32) * ce) / ce.size


def loss(config, params, tokens, labels, weights, chosen=None):
    """The training objective: the weighted cross-entropy plus the load-balance term."""
    logits, info = forward(config, params, tokens, chosen)
    return weighted_cross_entropy(logits, labels, weights) + info["load_balance"]
