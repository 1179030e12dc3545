"""Plain reference for the ``sparse_lm`` family: one chip's share of the
language model that Kwai's Keye-VL-2.0-30B-A3B ``config.json`` (``model_type``
``KeyeVL2``) describes, with the indexer of DeepSeek sparse attention
(arXiv:2512.02556 section 2; the ``Indexer`` of its published inference code)
at the sizes of ``sa_config``. The ``config`` key or the source of each form is
in brackets; what no key carries is listed under ``assumed`` in the
configuration's file.

Embedding ``h = E[token]``; an untied head [``tie_word_embeddings``]; a last
RMSNorm; every layer the same, a norm before each branch [Qwen3-MoE]::

    h = h + Attn(N_a(h));   h = h + MoE(N_f(h))

**Main attention** on ``x = N_a(h)``: ``q``, ``k``, ``v`` as
``num_attention_heads``, ``num_key_value_heads`` and ``num_key_value_heads``
heads of ``head_dim``, no biases [``attention_bias``]; an RMSNorm over each
head's own values of q and of k [Qwen3-MoE]; rotary positions over all of
``head_dim``, half-split, base ``rope_theta``, **in three streams**
[``rope_scaling.mrope_section``]: frequency ``i`` of the ``head_dim / 2`` turns
by the position of stream 0 (time) for the first ``section[0]`` frequencies, of
stream 1 (height) for the next ``section[1]``, of stream 2 (width) for the
rest. A text token has the same index in all three; ``positions`` defaults to
that.

**The indexer** [DeepSeek sparse attention; the sizes from ``sa_config``] on
the same ``x``, detached::

    q_I = x W_q            as indexer_num_heads heads of indexer_head_dim
    k_I = LayerNorm(x W_k) one head, scale and bias
    both rotated over all of indexer_head_dim, same base, same three streams
    (sections scaled to the smaller head)
    w   = x W_w * indexer_num_heads ** -0.5 * indexer_head_dim ** -0.5
    I[t, s] = sum_j w[t, j] * relu(q_I[t, j] . k_I[s])            s <= t

**The selection** ``S_t``: the ``min(topk, t + 1)`` keys ``s <= t`` of largest
``I[t, s]`` (``lax.top_k`` over the masked row: a tie to the lower index).
**Attention over it**: ``o[t] = sum_{s in S_t} softmax_{s in S_t}(q_t . k_s *
head_dim ** -0.5) v_s``, every head over the same ``S_t``; ``out = o W_o``.
**The indexer's loss**: ``p[t, .]`` the mean over the heads of those
probabilities, detached; ``L_I = mean_t KL(p[t, S_t] || softmax_{s in S_t}
I[t, s])``, each layer's term added to the objective at
``indexer_loss_weight``.

**Expert layer**, per token ``x``, router in float32::

    s = softmax(W_r x)          over all the model's experts
    e = top num_experts_per_tok of s
    w = s[e] / (sum s[e] + 1e-20)                          [norm_topk_prob]
    y = sum_j w_j * W_down[e_j](silu(W_gate[e_j] x) * W_up[e_j] x)

and the load-balancing loss ``router_aux_loss_coef * E * sum_i f_i P_i`` with
``f_i`` the share of the N * k assignments on expert ``i`` and ``P_i`` the mean
of ``s_i``, over all ``E`` experts. **The share**: ``config["share"]`` says
which of the ``router_experts`` this chip holds; ``y`` sums the held experts'
terms only; logits and loss are over the vocabulary slice.

Straightforward ``jax.numpy`` in float32; nothing is imported from
``edl_tpu``. The scores are the one ``einsum`` the equation is and attention a
softmax under a boolean mask, both **in blocks of ``QUERY_BLOCK`` queries** (a
``lax.map`` whose body is recomputed in a backward pass) so that ``[T, T]``
rectangles of 16,384 fit; the experts run one after another over all tokens.
The caller sets ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.transformer_lm import _rms_norm

QUERY_BLOCK = 512
RENORM_EPS = 1e-20


def stream_of_frequency(half, sections):
    """Which of the three position streams each of the ``half`` frequencies
    turns by: ``sections`` [a, b, c] scaled to ``half`` if it sums to another
    width (the indexer's smaller head)."""
    total = sum(sections)
    bounds = [round(half * sum(sections[:i + 1]) / total) for i in range(3)]
    index = jnp.arange(half)
    return (index >= bounds[0]).astype(jnp.int32) + (index >= bounds[1]).astype(jnp.int32)


def rotate(x, positions, theta, sections):
    """Rotary positions on ``x`` [B, T, H, D] in three streams: ``positions``
    [3, B, T]; value ``i`` of the first half and value ``i`` of the second are
    one pair, turned by ``positions[stream(i)] * theta ** (-2 i / D)``."""
    d = x.shape[-1]
    half = d // 2
    frequency = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / d)
    stream = stream_of_frequency(half, sections)
    # [B, T, half]: each frequency's own stream's position
    position = jnp.moveaxis(positions.astype(jnp.float32), 0, -1)[..., stream]
    angle = (position * frequency)[:, :, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1
    )


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _in_query_blocks(fn, t, *rowwise):
    """``fn(rows..., first row)`` over blocks of ``QUERY_BLOCK`` queries
    (arrays with the queries on axis 0), results stacked back along axis 0."""
    block = min(QUERY_BLOCK, t)
    if t % block:
        raise ValueError("sparse_lm: %d queries in blocks of %d" % (t, block))
    split = [a.reshape((t // block, block) + a.shape[1:]) for a in rowwise]
    out = jax.lax.map(
        jax.checkpoint(lambda args: fn(*args[:-1], args[-1])),
        (*split, jnp.arange(t // block) * block),
    )
    return jax.tree.map(lambda a: a.reshape((t,) + a.shape[2:]), out)


def index_scores(index_q, index_k, index_w):
    """``I [T, T]`` of one sequence, every pair (the caller masks): ``index_q``
    [T, J, Di], ``index_k`` [T, Di], ``index_w`` [T, J]."""
    def block(q, w, first):
        s = jnp.einsum("tjd,sd->tjs", q, index_k)
        return jnp.einsum("tj,tjs->ts", w, jax.nn.relu(s))

    return _in_query_blocks(block, index_q.shape[0], index_q, index_w)


def select(scores, topk):
    """The selection [T, T] (bool) and each row's k-th score [T] from scores
    [T, T]."""
    t = scores.shape[0]
    k = min(topk, t)

    def block(rows, first):
        at = first + jnp.arange(rows.shape[0])[:, None]
        masked = jnp.where(jnp.arange(t)[None, :] <= at, rows, -jnp.inf)
        values, idx = jax.lax.top_k(masked, k)
        keep = jnp.arange(k)[None, :] < jnp.minimum(topk, at + 1)
        picked = jnp.zeros(rows.shape, bool).at[
            jnp.arange(rows.shape[0])[:, None], idx
        ].max(keep)
        kth = jnp.take_along_axis(values, jnp.minimum(topk, at + 1) - 1, axis=-1)
        return picked, kth[:, 0]

    return _in_query_blocks(block, t, scores)


def selected_attention(q, k, v, picked):
    """``(o [T, H, D], p [T, T])`` of one sequence: softmax attention of ``q``
    [T, H, D] over the keys ``picked`` [T, T] marks (``k``, ``v`` [T, Hkv,
    D]), and the heads' mean of its probabilities."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scale = q.shape[-1] ** -0.5

    def block(rows, seen, first):
        s = jnp.einsum("thd,shd->hts", rows, k) * scale
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p, v), jnp.mean(p, axis=0)

    return _in_query_blocks(block, q.shape[0], q, picked)


def index_kl(scores, picked, target):
    """``mean_t KL(target[t] || softmax over picked[t] of scores[t])``."""
    def block(rows, seen, p, first):
        logq = jax.nn.log_softmax(jnp.where(seen, rows, -jnp.inf), axis=-1)
        live = seen & (p > 0)
        return jnp.sum(
            jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0)) - jnp.where(live, logq, 0.0)), 0.0),
            axis=-1,
        )

    return jnp.mean(_in_query_blocks(block, scores.shape[0], scores, picked, target))


def indexer(config, a, x, positions):
    """``(q_I [B, T, J, Di], k_I [B, T, Di], w [B, T, J])`` from the attention
    branch's input ``x`` [B, T, D] and the layer's ``attn`` parameters."""
    f32 = lambda m: m.astype(jnp.float32)  # noqa: E731
    sa, theta = config["sa_config"], float(config["rope_theta"])
    sections = config["rope_scaling"]["mrope_section"]
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    q = jnp.einsum("btd,djk->btjk", x, f32(a["index_q"]["kernel"]))
    k = layer_norm(
        x @ f32(a["index_k"]["kernel"]), a["index_k_norm"]["scale"],
        a["index_k_norm"]["bias"], config["rms_norm_eps"],
    )
    q = rotate(q, positions, theta, sections)
    k = rotate(k[:, :, None, :], positions, theta, sections)[:, :, 0]
    w = (x @ f32(a["index_w"]["kernel"])) * (heads ** -0.5 * dim ** -0.5)
    return q, k, w


def attention(config, a, x, positions, given=None):
    """The attention branch on ``x`` [B, T, D]: ``(out [B, T, D], L_I, info)``.
    ``given`` [B, T, T] (bool), if handed over, is the selection the attention
    and ``L_I`` are computed under in place of the reference's own (which
    ``info`` judges it against: see ``forward``)."""
    f32 = lambda m: m.astype(jnp.float32)  # noqa: E731
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    sections = config["rope_scaling"]["mrope_section"]
    topk = config["sa_config"]["topk"]
    q = jnp.einsum("btd,dhk->bthk", x, f32(a["q"]["kernel"]))
    k = jnp.einsum("btd,dhk->bthk", x, f32(a["k"]["kernel"]))
    v = jnp.einsum("btd,dhk->bthk", x, f32(a["v"]["kernel"]))
    q = rotate(_rms_norm(q, a["q_norm"]["scale"], eps), positions, theta, sections)
    k = rotate(_rms_norm(k, a["k_norm"]["scale"], eps), positions, theta, sections)
    index_q, index_k, index_w = indexer(config, a, jax.lax.stop_gradient(x), positions)
    outs, kls, infos = [], [], []
    for b in range(x.shape[0]):
        scores = index_scores(index_q[b], index_k[b], index_w[b])
        own, kth = select(jax.lax.stop_gradient(scores), topk)
        picked = own if given is None else given[b]
        o, p = selected_attention(q[b], k[b], v[b], picked)
        outs.append(o)
        kls.append(index_kl(scores, picked, jax.lax.stop_gradient(p)))
        t = scores.shape[0]
        causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        differs = own != picked
        infos.append({
            "scores": scores, "own": own,
            "selected": jnp.sum(own), "flipped": jnp.sum(differs & own),
            # how far from its row's k-th score the farthest disagreeing pair lies
            "widest_flip": jnp.max(jnp.where(differs, jnp.abs(scores - kth[:, None]), 0.0)),
            "score_scale": jnp.max(jnp.where(causal, jnp.abs(scores), 0.0)),
        })
    out = jnp.einsum("bthk,hkd->btd", jnp.stack(outs), f32(a["o"]["kernel"]))
    return out, jnp.mean(jnp.stack(kls)), infos


def weigh(config, scores, experts):
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if config["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + RENORM_EPS)
    return weights


def route(config, logits):
    """``(weights [N, k], experts [N, k], margin [N], scores [N, E])``: the
    top-k of the softmax scores, and how far the k-th stands above the
    (k+1)-th."""
    k = config["num_experts_per_tok"]
    scores = jax.nn.softmax(logits, axis=-1)
    ranked = jnp.argsort(-scores, axis=-1)
    experts = ranked[:, :k]
    kth = jnp.take_along_axis(scores, ranked[:, k - 1:k + 1], axis=-1)
    return weigh(config, scores, experts), experts, kth[:, 0] - kth[:, 1], scores


def mixture(config, p, x, chosen=None):
    """This chip's part of the expert layer on tokens ``x`` [N, D]: ``(y,
    load-balancing loss, info)``. ``chosen`` [N, k]: the experts ``y`` and the
    loss's counts are computed with, each weighted by the reference's own
    score, in place of the reference's own choice (``lfm2_lm.mixture``)."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    share = config["share"]
    e, first, held = share["router_experts"], share["experts_first"], config["num_experts"]
    n = x.shape[0]
    logits = x @ f32(p["router"]["kernel"])
    weights, experts, margin, scores = route(config, logits)
    own = experts
    if chosen is not None:
        experts, weights = chosen, weigh(config, scores, chosen)
    dense = jnp.zeros((n, e), jnp.float32).at[jnp.arange(n)[:, None], experts].set(weights)

    def one_expert(y, expert):
        gate, up, down, weight = expert
        hidden = jax.nn.silu(x @ gate) * (x @ up)
        return y + weight[:, None] * (hidden @ down), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (f32(p["gate"]), f32(p["up"]), f32(p["down"]), dense.T[first:first + held]),
    )
    counts = jnp.zeros((e,), jnp.int32).at[experts.reshape(-1)].add(1)
    balance = config["router_aux_loss_coef"] * e * jnp.sum(
        counts.astype(jnp.float32) / experts.size * jnp.mean(scores, axis=0)
    )
    info = {
        "experts": own, "margin": margin, "router_logits": logits, "scores": scores,
        "rows_held": jnp.sum(counts[first:first + held]) / experts.size,
    }
    return y, balance, info


def text_positions(tokens):
    """A text token's position in the three streams: its index in each."""
    b, t = tokens.shape
    return jnp.broadcast_to(jnp.arange(t)[None, None, :], (3, b, t))


def embed(params, tokens):
    return params["embed"]["embedding"].astype(jnp.float32)[tokens]


def layer(config, p, x, positions, chosen=None, given=None):
    """One block on the stream ``x`` [B, T, D]: ``(x after both branches, L_I,
    the load-balancing loss, the selections' infos, the router's info)``."""
    eps = config["rms_norm_eps"]
    b, t, d = x.shape
    out, kl, picks = attention(
        config, p["attn"], _rms_norm(x, p["ln1"]["scale"], eps), positions, given
    )
    x = x + out
    y, balance, router = mixture(
        config, p["moe"], _rms_norm(x, p["ln2"]["scale"], eps).reshape(b * t, d), chosen
    )
    return x + y.reshape(b, t, d), kl, balance, picks, router


def head(config, params, x):
    x = _rms_norm(x, params["ln_f"]["scale"], config["rms_norm_eps"])
    return x @ params["lm_head"]["kernel"].astype(jnp.float32)


def forward(config, params, tokens, chosen=None, selections=None, positions=None):
    """``(logits [B, T, vocab slice], losses, info)`` for ``tokens`` [B, T]:
    ``losses`` holds ``index_kl`` (the layers' SUM of ``L_I``, unweighted) and
    ``load_balance`` (the layers' sum, weighted); ``info`` the routers' and the
    selections' per-layer lists. ``chosen`` [L, B*T, k] and ``selections`` [L,
    B, T, T]: the program's discrete decisions to compute under (each is
    judged against the reference's own in ``info``)."""
    x = embed(params, tokens)
    positions = text_positions(tokens) if positions is None else positions
    routers, picks, kls, balances = [], [], [], []
    for i in range(config["num_hidden_layers"]):
        x, kl, balance, infos, info = layer(
            config, params["layer_%d" % i], x, positions,
            None if chosen is None else chosen[i],
            None if selections is None else selections[i],
        )
        kls.append(kl)
        picks.append(infos)
        balances.append(balance)
        routers.append(info)
    logits = head(config, params, x)
    losses = {"index_kl": sum(kls), "load_balance": sum(balances)}
    info = {key: jnp.stack([r[key] for r in routers]) for key in routers[0]}
    info["index_kl"] = jnp.stack(kls)
    info["selection"] = picks
    return logits, losses, info


def cross_entropy(logits, targets):
    """Mean next-token cross-entropy over every position, over the slice."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(config, params, tokens, targets, chosen=None, selections=None):
    """The training objective: the cross-entropy, the indexer's KL of every
    layer at ``indexer_loss_weight`` and the load-balancing losses."""
    logits, losses, _ = forward(config, params, tokens, chosen, selections)
    return (
        cross_entropy(logits, targets)
        + config["indexer_loss_weight"] * losses["index_kl"] + losses["load_balance"]
    )
