"""Solar-Open2's layers through the program: ``kda_rule`` for ANY ``g <= 0``
(plain and kernels, value and the five gradients, against the float32
step-by-step recurrence with log-decays down to -40 a step and beta in (0, 2),
where the parent's form is not finite), ``KimiDeltaMixer`` with Kimi
Linear's own gate and low-rank pairs against the reference's layer, the toy
model against ``benchmark/reference/solar_lm.py`` (logits, loss, gradients,
a whole step), the shares adding up to the uncut layers (forty of an expert
layer, eight of each mixer's heads), and Ling's range of decays agreeing
with the parent's form of the pairs."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import loss_logits_gradients, value_and_gradients

from benchmark.families import solar_lm as family
from benchmark.reference import solar_lm as reference
from edl_tpu.models import DroplessMoE, KimiDeltaMixer, KimiDeltaSpec
from edl_tpu.models.transformer import Attention
from edl_tpu.obs import trace as obs_trace
from edl_tpu.ops import gated_delta as G
from edl_tpu.ops import kda_rule
from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs", "solar_open2_250b.json")) as f:
    TOY = json.load(f)


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    scale = max(np.max(np.abs(want)), 1e-12)
    assert np.max(np.abs(got - want)) / scale <= tol, np.max(np.abs(got - want)) / scale


# -- the rule for any g <= 0 ---------------------------------------------------

LEAVES = ("q", "k", "v", "g", "beta")


def deep_inputs(seed=0, b=1, t=192, h=2, d=128):
    """Unit keys, log-decays in (-2, 0) with a quarter of the channel-steps in
    (-40, -5) and one head at -40 on every channel for sixteen steps running
    (e^-640: the parent's form took e^+320 there), beta in (0, 2)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    unit = lambda m: m / jnp.linalg.norm(m, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (b, t, h, d))) * d ** -0.5
    k = unit(jax.random.normal(keys[1], (b, t, h, d)))
    v = jax.random.normal(keys[2], (b, t, h, d))
    g = -jax.random.uniform(keys[3], (b, t, h, d), minval=0.0, maxval=2.0)
    deep = jax.random.uniform(keys[4], g.shape) < 0.25
    g = jnp.where(deep, -jax.random.uniform(keys[5], g.shape, minval=5.0, maxval=40.0), g)
    run = (jnp.arange(t) >= 70) & (jnp.arange(t) < 86)
    g = jnp.where(run[None, :, None, None] & (jnp.arange(h) == 0)[None, None, :, None], -40.0, g)
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(keys[6], (b, t, h)))
    return q, k, v, g, beta


bf16_args = lambda args: tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]  # noqa: E731


@functools.lru_cache(maxsize=None)
def rule_against_the_recurrence(path, carried):
    """``((o, state), gradients)`` of the chunked rule (``path``: the plain form
    on float32 operands, or the kernels in the interpreter on bfloat16 ones)
    and of the float32 recurrence on the same numbers, under one random
    cotangent of the output and the final state."""
    args = deep_inputs()
    state = 0.1 * jax.random.normal(jax.random.PRNGKey(6), (1, 2, 128, 128)) if carried else None
    if path == "kernels":
        args = bf16_args(args)
    wide = tuple(a.astype(jnp.float32) for a in args)
    rule = lambda *a: kda_rule(  # noqa: E731
        *a, chunk=64, initial_state=state, return_final_state=True,
        interpret=path == "kernels",
    )
    step_by_step = lambda *a: reference.recurrence(*a, state=state)  # noqa: E731
    w = (jax.random.normal(jax.random.PRNGKey(9), args[2].shape),
         jax.random.normal(jax.random.PRNGKey(10), (1, 2, 128, 128)))
    found = []
    with jax.default_matmul_precision("highest"):
        for fn, operands in ((rule, args), (step_by_step, wide)):
            values, pull = jax.jit(lambda *a, fn=fn: jax.vjp(fn, *a))(*operands)
            grads = jax.jit(pull)(tuple(c.astype(a.dtype) for c, a in zip(w, values)))
            found.append((values, grads))
    return found


@pytest.mark.parametrize("carried", [False, True], ids=["from_zeros", "a_state_in"])
@pytest.mark.parametrize("path", ["plain", "kernels"])
@pytest.mark.parametrize("what", ["o", "state"] + list(LEAVES))
def test_the_rule_holds_for_any_decay_against_the_recurrence(what, path, carried):
    """Value and the five gradients, with and without a state in and out. The
    plain form on float32 operands is held to the arithmetic; the kernels on
    bfloat16 operands to the rounding of a chunk's matmul operands."""
    (got, got_grads), (want, want_grads) = rule_against_the_recurrence(path, carried)
    if what in ("o", "state"):
        a, b = got[what == "state"], want[what == "state"]
    else:
        a, b = got_grads[LEAVES.index(what)], want_grads[LEAVES.index(what)]
    assert a.shape == b.shape
    _close(a.astype(jnp.float32), b, tol=5e-4 if path == "plain" else 3e-2)


def parents_pairs(q32, k32, gamma, sub=16):
    """``G._plain_pairs``'s two as the rule had them until PR 51, written out:
    the rows of a sub-block of ``sub`` steps under one reference, the running
    sum at the sub-block's middle step, which holds while ``|g|`` stays under
    88 / ``sub`` = 5.5 a step. ``[b n c h k]`` float32 in, ``[b n h c s]`` out."""
    b, n, c, h, d = gamma.shape
    in_blocks = lambda a: a.reshape(b, n, c // sub, sub, h, d)  # noqa: E731
    ref = in_blocks(gamma)[:, :, :, (sub - 1) // 2]                       # [b n i h k]
    rows = jnp.exp(in_blocks(gamma) - ref[:, :, :, None])                 # [b n i c h k]
    reach = jnp.arange(c)[None, :] // sub <= jnp.arange(c // sub)[:, None]    # [i s]
    cols = jnp.exp(jnp.where(
        reach[:, :, None, None], ref[:, :, :, None] - gamma[:, :, None], -jnp.inf
    ))                                                                    # [b n i s h k]
    k_cols = k32[:, :, None] * cols
    against = lambda a: jnp.einsum(  # noqa: E731
        "bnichk,bnishk->bnhics", in_blocks(a) * rows, k_cols
    ).reshape(b, n, h, c, c)
    return against(k32), against(q32)


def pairs_inputs(g):
    b, t, h, d = g.shape
    in_chunks = lambda a: a.reshape(b, t // 64, 64, h, d)  # noqa: E731
    q, k = deep_inputs()[:2]
    return in_chunks(q), in_chunks(k), jnp.cumsum(in_chunks(g), axis=2)


def test_the_inputs_reach_what_the_parents_form_cannot_run():
    q, k, v, g, beta = deep_inputs()
    assert float(jnp.min(g)) == -40.0 and float(jnp.max(g)) <= 0.0
    assert 1.0 < float(jnp.max(beta)) < 2.0
    assert float(jnp.mean(g < -5.5)) > 0.2


@pytest.mark.parametrize("what", ["kk", "scores"])
def test_the_parents_form_is_not_finite_there(what):
    """Sixteen steps at -40 are e^+320 under a middle reference: ``inf * 0``."""
    at = what == "scores"
    args = pairs_inputs(deep_inputs()[3])
    lower = jnp.tril(jnp.ones((64, 64), bool), 0 if at else -1)
    assert not bool(jnp.isfinite(jnp.where(lower, parents_pairs(*args)[at], 0.0)).all())
    assert bool(jnp.isfinite(G._plain_pairs(*args, jnp.float32)[at]).all())


@pytest.mark.parametrize("what", ["kk", "scores"])
@pytest.mark.parametrize("bound", [1.0, 5.0, 5.5])
def test_one_form_for_both_callers_agrees_with_the_parents_inside_its_bound(what, bound):
    """Ling's caller (the safe gate, ``g`` in (-5, 0)) had the parent's form
    until PR 51: within the bound the parent's form held, the pairs are the
    same numbers (a share of the channels AT the bound on every step)."""
    at = what == "scores"
    g = deep_inputs()[3]
    g = jnp.where(g < -2.0, -bound, g * bound / 2.0)
    args = pairs_inputs(g)
    lower = jnp.tril(jnp.ones((64, 64), bool), 0 if at else -1)
    with jax.default_matmul_precision("highest"):
        got, want = G._plain_pairs(*args, jnp.float32)[at], parents_pairs(*args)[at]
    _close(jnp.where(lower, got, 0.0), jnp.where(lower, want, 0.0), tol=1e-5)


@pytest.mark.parametrize("chunk", [8, 32, 64])
def test_the_halving_form_takes_no_exp_of_a_positive_argument(chunk, monkeypatch):
    """Every ``exp`` the plain form takes, read as it runs (no jit): of a
    running sum, of a distance to a chunk's end, or of a level's distance to
    its boundary, none above zero."""
    args = deep_inputs(t=128, d=16)
    seen = []
    exp = jnp.exp

    def watched(x):
        seen.append(float(jnp.max(x)))
        return exp(x)

    monkeypatch.setattr(G.jnp, "exp", watched)
    with jax.disable_jit():
        kda_rule(*args, chunk=chunk)
        assert seen and max(seen) <= 0.0


def test_the_halving_pairs_are_the_pairs_themselves():
    """A level at a time, the pairs' products are ``sum_d a_i k_j exp(Gamma_i -
    Gamma_j)`` for every ``i >= j`` of a chunk, nothing outside it."""
    b, n, c, h, d = 1, 2, 16, 2, 8
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q32 = jax.random.normal(keys[0], (b, n, c, h, d))
    k32 = jax.random.normal(keys[1], (b, n, c, h, d))
    gamma = jnp.cumsum(-3.0 * jax.random.uniform(keys[2], (b, n, c, h, d)), axis=2)
    with jax.default_matmul_precision("highest"):
        kk, scores = G._plain_pairs(q32, k32, gamma, jnp.float32)
        apart = gamma[:, :, :, None] - gamma[:, :, None]         # [b n c s h d]
        lower = (jnp.arange(c)[:, None] >= jnp.arange(c)[None, :])[None, None, :, :, None, None]
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, apart, 0.0)), 0.0)
        want_scores = jnp.einsum("bnchd,bnshd,bncshd->bnhcs", q32, k32, decay)
        want_kk = jnp.einsum("bnchd,bnshd,bncshd->bnhcs", k32, k32, decay)
    _close(scores, want_scores, tol=1e-5)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    _close(jnp.where(strict, kk, 0.0), jnp.where(strict, want_kk, 0.0), tol=1e-5)


# -- what the instant says of the rule's callers -----------------------------

def test_the_instant_tells_the_rules_callers_apart():
    """Ling's defaults (the safe gate at -5), a bound past what the parent's
    form could hold, and Kimi Linear's own gate: one form of the pairs, and the
    instant says which caller it was."""
    x = jnp.zeros((1, 64, 32), jnp.bfloat16)
    tracer = obs_trace.get_tracer()
    found = {}
    for name, changes in (
        ("ling", {}), ("deep", dict(lower_bound=-12.0)),
        ("solar", dict(lower_bound=None, neg_eigval=True, gate_rank=16)),
    ):
        tracer.reset_notes()
        before = len([e for e in tracer.to_events() if e["name"] == "kda_chunks"])
        mixer = KimiDeltaMixer(KimiDeltaSpec(num_heads=2, key_dim=16, value_dim=16, **changes))
        jax.eval_shape(lambda x, m=mixer: m.init(jax.random.PRNGKey(0), x), x)
        found[name] = [
            e["args"] for e in tracer.to_events() if e["name"] == "kda_chunks"
        ][before:][0]
    pick = lambda a: (a["gate"], a["bound"], a["pairs"], a["beta_max"], a["rank"])  # noqa: E731
    assert pick(found["ling"]) == ("safe", -5.0, "halving", 1.0, None)
    assert pick(found["deep"]) == ("safe", -12.0, "halving", 1.0, None)
    assert pick(found["solar"]) == ("softplus", None, "halving", 2.0, 16)


# -- the mixer with Kimi Linear's own gate and low-rank pairs ---------------------

H, DK, DM = 4, 16, 64
MIXER_CONFIG = {
    "linear_attn_config": {"num_heads": H, "head_dim": DK, "short_conv_kernel_size": 4,
                           "num_kv_heads": None},
    "kda_allow_neg_eigval": True, "kda_use_full_proj": False, "rms_norm_eps": 1e-5,
}


def solar_mixer(heads=H, dtype=jnp.float32):
    spec = KimiDeltaSpec(num_heads=heads, key_dim=DK, value_dim=DK, chunk=32,
                         lower_bound=None, neg_eigval=True, gate_rank=DK)
    return KimiDeltaMixer(spec, dtype, 1e-5)


@pytest.fixture(scope="module")
def whole_mixer():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 96, DM), jnp.float32)
    params = jax.jit(solar_mixer().init)(jax.random.PRNGKey(1), x)["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 40))
    shake = lambda a: a + 0.3 * jax.random.normal(next(keys), a.shape)  # noqa: E731
    params = dict(params, norm=shake(params["norm"]), dt_bias=shake(params["dt_bias"]) + 2.0,
                  g_up=dict(params["g_up"], bias=shake(params["g_up"]["bias"])))
    return params, x


def test_the_mixers_leaves_are_the_published_forms(whole_mixer):
    params, _ = whole_mixer
    assert set(params) == {"q_proj", "k_proj", "v_proj", "b_proj", "o_proj", "f_down", "f_up",
                           "g_down", "g_up", "q_conv", "k_conv", "v_conv", "A_log", "dt_bias",
                           "norm"}
    assert params["f_down"]["kernel"].shape == (DM, DK) == params["g_down"]["kernel"].shape
    assert params["f_up"]["kernel"].shape == (DK, H * DK) and set(params["f_up"]) == {"kernel"}
    assert params["g_up"]["bias"].shape == (H * DK,)            # the gate's second, with a bias
    assert params["q_proj"]["kernel"].shape == (DM, H * DK)     # 4 x 16 = 64: the stream's own
    assert params["dt_bias"].shape == (H, DK) and params["A_log"].shape == (H,)


@pytest.fixture(scope="module")
def mixer_both_ways(whole_mixer):
    """``(value, gradients)`` of the mixer and of the reference's layer under
    one cotangent, once for the cases that each look at one of them."""
    params, x = whole_mixer
    mixer = solar_mixer()
    weigh = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    with jax.default_matmul_precision("highest"):
        return [
            value_and_gradients(fn, params, weight=weigh, argnums=0) for fn in (
                lambda p: mixer.apply({"params": p}, x),
                lambda p: reference.kda_mixer(MIXER_CONFIG, p, x),
            )
        ]


@pytest.mark.parametrize("what", ["value", "gradients", "rule_inputs"])
def test_the_mixer_equals_the_references_layer(whole_mixer, mixer_both_ways, what):
    params, x = whole_mixer
    mixer = solar_mixer()
    (value, got), (want_value, want) = mixer_both_ways
    with jax.default_matmul_precision("highest"):
        if what == "value":
            _close(value, want_value)
            return
        if what == "rule_inputs":
            _, left = jax.jit(lambda p: mixer.apply(
                {"params": p}, x, mutable=["intermediates", "metrics"]
            ))(params)
            got = left["intermediates"]["rule_inputs"][0]
            want = reference.rule_inputs(MIXER_CONFIG, params, x)[:5]
            for a, b in zip(got, want):
                _close(a, b, tol=1e-5)
            g, beta = got[3], got[4]
            assert float(jnp.max(beta)) > 1.0 and float(jnp.min(g)) < -5.5  # past both old limits
            assert float(left["metrics"]["kda_log_decay_min"][0]) == float(jnp.min(g))
            return
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        assert float(jnp.linalg.norm(b)) > 0, jax.tree_util.keystr(path)
        _close(a, b, tol=1e-3)


@pytest.mark.parametrize("form", ["safe_gate", "beta_to_one", "full_rank"])
def test_another_published_form_is_another_function(whole_mixer, form):
    """Ling's forms in place of Solar's read far from the reference's layer."""
    params, x = whole_mixer
    changes = {"safe_gate": dict(lower_bound=-5.0), "beta_to_one": dict(neg_eigval=False),
               "full_rank": dict(gate_rank=None)}[form]
    spec = KimiDeltaSpec(**{**dict(num_heads=H, key_dim=DK, value_dim=DK, chunk=32,
                                   lower_bound=None, neg_eigval=True, gate_rank=DK), **changes})
    other = KimiDeltaMixer(spec, jnp.float32, 1e-5)
    if form == "full_rank":
        fresh = jax.jit(other.init)(jax.random.PRNGKey(1), x)["params"]
        assert "f_proj" in fresh and "f_down" not in fresh
        assert fresh["f_proj"]["kernel"].shape == (DM, H * DK) and set(fresh["g_proj"]) == {"kernel"}
        return
    with jax.default_matmul_precision("highest"):
        got = other.apply({"params": params}, x)
        want = reference.kda_mixer(MIXER_CONFIG, params, x)
    assert float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))) > 0.05


def heads_of(params, first, count):
    """A chip's share of the mixer's heads: the second matrices, the
    convolutions and the per-head vectors cut by heads, the pairs' first
    matrices whole."""
    cols = slice(first * DK, (first + count) * DK)
    cut = dict(params)
    for name in ("q_proj", "k_proj", "v_proj", "f_up"):
        cut[name] = {"kernel": params[name]["kernel"][:, cols]}
    cut["g_up"] = {"kernel": params["g_up"]["kernel"][:, cols], "bias": params["g_up"]["bias"][cols]}
    cut["b_proj"] = {"kernel": params["b_proj"]["kernel"][:, first:first + count]}
    cut["o_proj"] = {"kernel": params["o_proj"]["kernel"][cols]}
    for name in ("q_conv", "k_conv", "v_conv"):
        cut[name] = params[name][:, cols]
    cut["A_log"], cut["dt_bias"] = params["A_log"][first:first + count], params["dt_bias"][first:first + count]
    return cut


def test_the_shares_of_a_linear_mixers_heads_add_up_to_the_uncut_layer():
    """Eight chips hold one head each of eight; what their out projections put
    out sums to the reference's uncut layer."""
    heads = 8
    config = dict(MIXER_CONFIG, linear_attn_config=dict(MIXER_CONFIG["linear_attn_config"], num_heads=heads))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 64, DM), jnp.float32)
    params = jax.jit(solar_mixer(heads).init)(jax.random.PRNGKey(4), x)["params"]
    share = jax.jit(lambda p: solar_mixer(1).apply({"params": p}, x))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: reference.kda_mixer(config, p, x))(params)
        parts = [share(heads_of(params, i, 1)) for i in range(heads)]
    _close(sum(parts), want)
    assert float(jnp.max(jnp.abs(parts[0]))) > 0


def test_the_shares_of_the_softmax_layers_heads_add_up_to_the_uncut_layer():
    """Sixteen query heads on eight key heads: eight chips hold one whole group
    each (two query heads on one key head), gate and all."""
    hq, hkv, hd = 16, 8, 16
    config = {"use_rope": False, "use_gqa_gate": True}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, DM), jnp.float32)
    layer = lambda q, kv: Attention(  # noqa: E731
        num_heads=q, num_kv_heads=kv, head_dim=hd, dtype=jnp.float32, rope=False, gate=True,
    )
    positions = jnp.arange(x.shape[1])[None]                   # read by no layer: no rotation
    params = jax.jit(layer(hq, hkv).init)(jax.random.PRNGKey(6), x, positions)["params"]
    group = hq // hkv
    share = jax.jit(lambda p: layer(group, 1).apply({"params": p}, x, positions))
    with jax.default_matmul_precision("highest"):
        want = reference.gqa_mixer(config, params, x)
        _close(jax.jit(layer(hq, hkv).apply)({"params": params}, x, positions), want)
        parts = []
        for i in range(hkv):
            qs = slice(i * group, (i + 1) * group)
            cut = {
                "q": {"kernel": params["q"]["kernel"][:, qs]},
                "g": {"kernel": params["g"]["kernel"][:, qs]},
                "k": {"kernel": params["k"]["kernel"][:, i:i + 1]},
                "v": {"kernel": params["v"]["kernel"][:, i:i + 1]},
                "o": {"kernel": params["o"]["kernel"][qs]},
            }
            parts.append(share(cut))
    _close(sum(parts), want)


E, K, F = 40, 4, 24


def expert_layer(held=None):
    return DroplessMoE(
        num_experts=E, top_k=K, d_ff=F, norm_topk_prob=True, aux_weight=0.0, z_weight=0.0,
        score_func="sigmoid", route_scale=1.0, bias_rate=1e-3, shared_d_ff=F, held=held,
        dtype=jnp.float32,
    )


def layer_config(held):
    first, count = held or (0, E)
    return {
        "num_experts_per_tok": K, "n_routed_experts": count, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "n_shared_experts": 1,
        "train": {"expert_bias_rate": 1e-3},
        "share": {"router_experts": E, "experts_first": first},
    }


def test_the_forty_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Forty chips hold one expert each of forty: the router, its bias, the
    top-k and the renormalisation at the whole width on every chip; what they
    put out, the shared expert counted once, is the reference's uncut layer."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32), jnp.float32)
    params = jax.jit(expert_layer().init)(jax.random.PRNGKey(1), x)["params"]
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(2), (E,))
    stats = {"router_bias": bias - jnp.mean(bias)}
    tokens = x.reshape(-1, x.shape[-1])
    # ``held`` is a field of the layer: a share is its own program, forty of
    # them, each jitted (the reference's forty run eagerly on one set of shapes)
    program = jax.jit(lambda first, p: expert_layer((first, 1)).apply(
        {"params": p, "batch_stats": stats}, x
    ), static_argnums=0)
    plain = lambda first, p: reference.mixture(  # noqa: E731
        layer_config((first, 1)), p, stats["router_bias"], tokens
    )[0]
    with jax.default_matmul_precision("highest"):
        want, info = reference.mixture(layer_config(None), params, stats["router_bias"], tokens)
        shared = reference.swiglu(params["shared"], tokens)
        outputs = []
        for first in range(E):
            banks = {name: params[name][first:first + 1] for name in ("gate", "up", "down")}
            outputs.append(program(first, {**params, **banks}).reshape(tokens.shape))
            _close(outputs[-1], plain(first, {**params, **banks}))
    _close(sum(outputs) - (E - 1) * shared, want)
    assert int(jnp.sum(info["counts"])) == tokens.shape[0] * K


# -- the toy model against the reference ---------------------------------------

def shaken(params, seed=7):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 400))
    return jax.tree.map(
        lambda a: a * (1 + 0.2 * jax.random.normal(next(keys), a.shape))
        if a.ndim <= 2 and a.size < 4096 else a,
        params,
    )


def toy_lm(remat=False, dtype=jnp.float32):
    # the head as the class draws it: the cell's start puts it at zero
    return family.build(family.as_drawn(TOY), 1, 0)["model"].clone(remat=remat, dtype=dtype)


def toy_batch(seed=0, b=2):
    return family.host_batches(TOY, b, seed, n_batches=1)[0]


def lm_loss(logits, targets):
    return cross_entropy_loss(logits.reshape(-1, logits.shape[-1]), targets.reshape(-1))


@pytest.fixture(scope="module")
def toy_variables():
    lm = toy_lm()
    x, y = toy_batch()
    variables = jax.jit(lm.init)(jax.random.PRNGKey(3), x)
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 8))

    def some_bias(a):  # as the rule leaves it: its mean at zero
        b = 0.02 * jax.random.normal(next(keys), a.shape)
        return b - jnp.mean(b)

    return shaken(variables["params"]), jax.tree.map(some_bias, variables["batch_stats"]), x, y


def test_the_toy_is_the_first_period_with_an_expert_layer_in_every_block(toy_variables):
    params = toy_variables[0]
    kinds = ["attn" if "attn" in params["layer_%d" % i] else "kda" for i in range(4)]
    assert kinds == ["attn", "kda", "kda", "kda"] == [
        {"softmax": "attn", "linear": "kda"}[k] for k in reference.layer_kinds(TOY)
    ]
    assert all("moe" in params["layer_%d" % i] and "mlp" not in params["layer_%d" % i]
               for i in range(4))
    assert set(params["layer_0"]["attn"]) == {"q", "k", "v", "g", "o"}       # a gate, no QK norm
    moe = params["layer_1"]["moe"]
    assert moe["router"]["kernel"].shape == (TOY["hidden_size"], TOY["share"]["router_experts"])
    assert moe["gate"].shape[0] == TOY["n_routed_experts"] and "shared" in moe


@pytest.fixture(scope="module")
def reference_outputs(toy_variables):
    """``(loss, logits, gradients)`` of the plain reference at the toy's batch."""
    params, stats, x, y = toy_variables

    def plain(p):
        return reference.loss(TOY, p, stats, x, y), reference.forward(TOY, p, stats, x)[0]

    with jax.default_matmul_precision("highest"):
        return loss_logits_gradients(plain, params)


@pytest.fixture(scope="module")
def reference_gradients(reference_outputs):
    return reference_outputs[2]


@pytest.fixture(scope="module")
def program_outputs(toy_variables):
    """``remat -> (loss, logits, gradients)`` of the toy LM, each computed once."""
    params, stats, x, y = toy_variables

    @functools.lru_cache(maxsize=None)
    def outputs(remat):
        lm = toy_lm(remat=remat)

        def program(p):
            logits = lm.apply({"params": p, "batch_stats": stats}, x)
            return lm_loss(logits, y)[0], logits

        with jax.default_matmul_precision("highest"):
            return loss_logits_gradients(program, params)

    return outputs


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("what", ["logits", "loss", "gradients"])
def test_the_toy_lm_equals_the_plain_reference(program_outputs, reference_outputs, remat, what):
    (loss, logits, got), (want_loss, want_logits, want) = program_outputs(remat), reference_outputs
    if what == "logits":
        _close(logits, want_logits)
        return
    if what == "loss":
        assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
        return
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        assert float(jnp.linalg.norm(b)) > 0, jax.tree_util.keystr(path)
        _close(a, b, tol=1e-3)


def test_a_whole_steps_gradients_are_the_references(toy_variables, reference_gradients):
    """Through ``create_state`` and ``make_train_step`` as the trainer calls
    them, with plain SGD at rate 1: what the step takes off every parameter is
    the gradient of the reference's loss, and the bias it leaves is the
    reference's rule on the step's own counts."""
    params, stats, x, y = toy_variables
    lm = toy_lm(remat=True)
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.sgd(1.0))
    state = state.replace(params=params, batch_stats=stats)
    with jax.default_matmul_precision("highest"):
        after, metrics = make_train_step(lm_loss, numerics=False, donate=False)(state, (x, y))
        _, info = reference.forward(TOY, params, stats, x)
    taken = jax.tree.map(lambda before, now: before - now, params, after.params)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(taken), jax.tree.leaves(reference_gradients)
    ):
        assert float(jnp.linalg.norm(b)) > 0, jax.tree_util.keystr(path)
        _close(a, b, tol=2e-3)
    for i in range(TOY["num_hidden_layers"]):
        _close(
            after.batch_stats["layer_%d" % i]["moe"]["router_bias"], info["bias_after"][i],
            tol=1e-5,
        )
    assert float(metrics["moe_rows_held"]) == pytest.approx(float(jnp.mean(info["rows_held"])))
    assert float(metrics["kda_log_decay_min"]) < 0.0 < float(metrics["kda_decay_mean"]) < 1.0


def test_the_lm_trains_through_the_step_and_exports_its_gauges():
    lm = toy_lm(remat=True, dtype=jnp.bfloat16)
    x, y = toy_batch(seed=1)
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.adamw(1e-3))
    gauges = ("kda_log_decay_min", "kda_decay_mean", "kda_beta_mean", "moe_rows_held",
              "moe_held_load_max", "moe_bias_absmax")
    assert set(gauges) <= set(state.sown)
    step = make_train_step(lm_loss, numerics=True, donate=False)
    first = None
    for _ in range(5):
        state, metrics = step(state, (x, y))
        first = first if first is not None else float(metrics["loss"])
    assert float(metrics["loss"]) < first and np.isfinite(float(metrics["loss"]))
    assert float(metrics["moe_rows_dropped"]) == 0
    from edl_tpu.obs import metrics as obs_metrics
    from edl_tpu.obs import numerics as obs_numerics

    obs_numerics.publish_sown({k: np.asarray(metrics[k]) for k in state.sown})
    rendered = obs_metrics.default_registry().render()
    for gauge in gauges:
        assert "edl_train_%s " % gauge in rendered
