"""Plain reference for the ``smallthinker_lm`` family: one chip's share of the
decoder that PowerInfer's SmallThinker-21BA3B-Instruct ``config.json``
describes (``model_name`` ``smallthinker_21b_instruct``; arXiv:2507.20984).
The ``config`` key of each form is in brackets; what no key carries is in the
configuration's ``assumed``. In float32, for a residual stream ``x`` [T, D]::

    block l, input x (the stream as the block receives it)
      r   = x W_r                       the router reads x ITSELF: before the first norm,
                                        before attention [described_as: "router placed
                                        before attention"; modeling_smallthinker.py]
      h   = x + Attn_l(N_1(x))          RMSNorm, learned scale, rms_norm_eps
      e   = the moe_num_active_primary_experts largest of r
      w   = softmax(r[e])               over the chosen logits alone
                                        [moe_primary_router_apply_softmax, norm_topk_prob]
      u   = N_2(h)                      the experts read the stream AFTER attention
      y   = sum_j w_j W_down[e_j](relu(W_gate[e_j] u) * W_up[e_j] u)   [moe_ffn_hidden_size;
                                        the ReLU gate: described_as "sparse ReGLU"]
      out = h + y                       no shared expert, no dense layer

    Attn_l on n = N_1(x): q = n W_q as num_attention_heads heads of head_dim, k and v as
      num_key_value_heads heads, no bias, no norm on q or k, no gate;
      rope_layout[l] = 1: q and k rotated (half-split, rope_theta, all of head_dim,
        rope_scaling null), else no position term at all;
      sliding_window_layout[l] = 1: query i sees key j iff 0 <= i - j < sliding_window_size,
        else iff j <= i;
      o = softmax(q k^T head_dim^-1/2 + mask) v;  Attn = o W_o

    h^0 = E[token];  logits = N_f(h^L) W_head   [tie_word_embeddings false]
    L = mean next-token cross-entropy
        + mean over the layers of load_balance_coef * E * sum_i f_i P_i
        + mean over the layers of router_z_coef * mean(logsumexp(r)^2)

with ``f_i`` the share of the assignments that went to expert ``i`` and ``P_i``
the mean over the tokens of ``softmax(r)_i`` over all ``E`` experts (the two
coefficients are ``train`` keys: the row has none).

**The share.** ``config["share"]`` says which of the ``router_experts`` this
chip holds (``experts_first`` .. ``+ moe_num_primary_experts``) and
``vocab_size`` is its slice of the vocabulary. The router, the choice and the
weights are over all ``router_experts``; ``y`` sums the held experts' terms
only (what the others would add is computed on other chips and left out here,
as in the program); logits and loss are over the slice. The heads are whole.

Straightforward ``jax.numpy``: attention is a dense masked softmax over ALL the
keys, ``QUERY_BLOCK`` queries at a time (a ``lax.map``: at 16,384 positions a
head's whole ``[T, T]`` scores would be a gigabyte), the experts one after
another over all tokens and masked by the routing weights; nothing is imported
from ``edl_tpu``. It reads the program's parameter tree by its names
(``layer_i/attn/{q,k,v,o}`` kernels, ``ln1``/``ln2`` scales, ``layer_i/moe``
with ``router`` and the banks ``gate``/``up``/``down``; ``embed``, ``ln_f``,
``lm_head``). The caller sets ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.transformer_lm import _rms_norm, _rope

QUERY_BLOCK = 512  # queries whose scores against all T keys are alive together


def masked_attention(q, k, v, window=None, block=QUERY_BLOCK):
    """Dense causal softmax attention, over the ``window`` newest keys if one
    is given, ``block`` queries at a time. q: [B, H, T, D]; k, v: [B, Hkv, T,
    D], each kv head serving H / Hkv query heads."""
    b, h, t, d = q.shape
    h_kv = k.shape[1]
    block = min(block, t)
    if t % block:
        raise ValueError("smallthinker_lm: %d queries in blocks of %d" % (t, block))
    grouped = q.reshape(b, h_kv, h // h_kv, t // block, block, d)

    def rows(args):
        q_rows, first = args                                 # [B, Hkv, G, block, D]
        i = first + jnp.arange(block)[:, None]
        j = jnp.arange(t)[None, :]
        seen = j <= i
        if window is not None:
            seen = seen & (j > i - window)
        scores = jnp.einsum("bngqd,bnkd->bngqk", q_rows, k) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bngqk,bnkd->bngqd", probs, v)

    out = jax.lax.map(
        jax.checkpoint(rows),
        (jnp.moveaxis(grouped, 3, 0), jnp.arange(t // block) * block),
    )
    return jnp.moveaxis(out, 0, 3).reshape(b, h, t, d)


def weigh(logits, experts):
    """The softmax over the chosen experts' logits alone."""
    return jax.nn.softmax(jnp.take_along_axis(logits, experts, axis=-1), axis=-1)


def route(config, logits):
    """``(weights [N, k], experts [N, k], margin [N])`` from the router's logits
    over all the model's experts: the k largest, and how far the k-th logit
    stands above the (k+1)-th (the room a rounding has before it changes the
    choice)."""
    k = config["moe_num_active_primary_experts"]
    if not (config["moe_primary_router_apply_softmax"] and config["norm_topk_prob"]):
        raise ValueError("smallthinker_lm: a softmax over the chosen logits, as published")
    ranked = jnp.argsort(-logits, axis=-1)
    experts = ranked[:, :k]
    kth = jnp.take_along_axis(logits, ranked[:, k - 1:k + 1], axis=-1)
    return weigh(logits, experts), experts, kth[:, 0] - kth[:, 1]


def mixture(config, p, route_x, x, chosen=None):
    """This chip's part of the expert layer with parameters ``p``
    (``layer_i/moe``): routed from ``route_x`` [N, D] (the block's input), the
    held experts computed on ``x`` [N, D] (the normed stream after attention).
    Returns ``(y, info)``. ``chosen`` [N, k], if given, are the experts ``y``
    and the counts are computed with, each weighted by the reference's OWN
    logits for it (attention carries a token's other expert into its
    neighbours' streams, so a comparison of streams computes with one choice);
    ``info["experts"]`` is the reference's own choice all the same."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    share, coefs = config["share"], config["train"]
    e, first = share["router_experts"], share["experts_first"]
    held, k = config["moe_num_primary_experts"], config["moe_num_active_primary_experts"]
    n = x.shape[0]
    logits = route_x @ f32(p["router"]["kernel"])
    weights, experts, margin = route(config, logits)
    own = experts
    if chosen is not None:
        experts, weights = chosen, weigh(logits, chosen)
    # [N, E]: a token's weight for each expert, 0 where it was not chosen
    dense = jnp.zeros((n, e), jnp.float32).at[jnp.arange(n)[:, None], experts].set(weights)
    picked = jnp.zeros((n, e), bool).at[jnp.arange(n)[:, None], experts].set(True)

    def one_expert(carry, expert):  # on every token, weighted by the column of ``dense``
        y, dead = carry
        gate, up, down, weight, mine = expert
        opened = x @ gate
        hidden = jax.nn.relu(opened) * (x @ up)
        dead = dead + jnp.sum(mine[:, None] & (opened <= 0))
        return (y + weight[:, None] * (hidden @ down), dead), None

    (y, dead), _ = jax.lax.scan(
        one_expert, (jnp.zeros_like(x), jnp.zeros((), jnp.int32)),
        (f32(p["gate"]), f32(p["up"]), f32(p["down"]),
         dense.T[first:first + held], picked.T[first:first + held]),
    )
    counts = jnp.sum(picked, axis=0)
    rows = jnp.sum(counts[first:first + held])
    assigned = counts / (n * k)
    mean_prob = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
    info = {
        "load_balance": coefs["load_balance_coef"] * e * jnp.sum(assigned * mean_prob),
        "router_z": coefs["router_z_coef"]
        * jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
        "experts": own, "margin": margin, "router_logits": logits, "counts": counts,
        "rows_held": rows / (n * k),
        # of the held experts' rows' gate values, the share the ReLU zeroes
        "gate_dead": dead / (jnp.maximum(rows, 1) * config["moe_ffn_hidden_size"]),
    }
    return y, info


def attention(config, a, x, rotated, windowed):
    """``Attn_l`` on the normed stream ``x`` [B, T, D] with parameters ``a``
    (``layer_i/attn``)."""
    f32 = lambda m: m.astype(jnp.float32)  # noqa: E731
    q = jnp.einsum("btd,dhk->bthk", x, f32(a["q"]["kernel"]))
    k = jnp.einsum("btd,dhk->bthk", x, f32(a["k"]["kernel"]))
    v = jnp.einsum("btd,dhk->bthk", x, f32(a["v"]["kernel"]))
    if rotated:
        if config["rope_scaling"] is not None:
            raise ValueError("smallthinker_lm: rope_scaling null, as published")
        q, k = _rope(q, config["rope_theta"]), _rope(k, config["rope_theta"])
    o = masked_attention(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        config["sliding_window_size"] if windowed else None,
    )
    return jnp.einsum("bthk,hkd->btd", jnp.swapaxes(o, 1, 2), f32(a["o"]["kernel"]))


def forward(config, params, tokens, chosen=None):
    """``(logits [B, T, vocab slice] in float32, info)`` for ``tokens`` [B, T].
    ``info``: ``load_balance`` and ``router_z`` (means over the layers), and
    stacked over the layers ``experts`` [L, B*T, k] (the reference's own
    choice), ``margin`` [L, B*T], ``router_logits`` [L, B*T, E], ``counts``
    [L, E], ``rows_held`` and ``gate_dead`` [L]. ``chosen`` [L, B*T, k]: see
    ``mixture``."""
    eps = config["rms_norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    layers = config["num_hidden_layers"]
    if not len(config["rope_layout"]) == len(config["sliding_window_layout"]) == layers:
        raise ValueError("smallthinker_lm: one entry of each layout a layer")
    if config["tie_word_embeddings"]:
        raise ValueError("smallthinker_lm: an untied head, as published")
    x = f32(params["embed"]["embedding"])[tokens]
    b, t, d = x.shape
    infos = []
    for i in range(layers):
        p = params["layer_%d" % i]
        block_input = x
        x = x + attention(
            config, p["attn"], _rms_norm(x, f32(p["ln1"]["scale"]), eps),
            config["rope_layout"][i], config["sliding_window_layout"][i],
        )
        y, info = mixture(
            config, p["moe"], block_input.reshape(b * t, d),
            _rms_norm(x, f32(p["ln2"]["scale"]), eps).reshape(b * t, d),
            None if chosen is None else chosen[i],
        )
        infos.append(info)
        x = x + y.reshape(b, t, d)
    x = _rms_norm(x, f32(params["ln_f"]["scale"]), eps)
    logits = x @ f32(params["lm_head"]["kernel"])
    stacked = {key: jnp.stack([info[key] for info in infos]) for key in infos[0]}
    stacked["load_balance"] = jnp.mean(stacked["load_balance"])
    stacked["router_z"] = jnp.mean(stacked["router_z"])
    return logits, stacked


def cross_entropy(logits, targets):
    """Mean next-token cross-entropy over every position, over the slice."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(config, params, tokens, targets, chosen=None):
    """The training objective: cross-entropy plus both auxiliary terms."""
    logits, info = forward(config, params, tokens, chosen)
    return cross_entropy(logits, targets) + info["load_balance"] + info["router_z"]
