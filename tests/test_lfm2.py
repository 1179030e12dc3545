"""The short-convolution hybrid's path end to end on the CPU at toy widths: the
gated convolution against its shifted products and the mixer against a
step-by-step recurrence over its two-step state (values and gradients), the
rotation at a configuration's own base, the expert layer with sigmoid scores, a
bias, the source's renormalisation and no shared expert (alone, and the shares
of a layer added up to the uncut one), the grouped matmul at an expert width
its measured tiling does not divide, a ``TransformerLM`` of the published
pattern against the benchmark's plain reference, and the scopes, instants and
gauge the model leaves for the tracing."""

import functools
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import loss_logits_gradients

from benchmark.families import lfm2_lm as family
from benchmark.reference import lfm2_lm as reference
from benchmark.reference.transformer_lm import _rope as rope_at_a_base
from edl_tpu.models import ArchSpec, MoESpec, ShortConvMixer, ShortConvSpec
from edl_tpu.models import transformer as transformer_module
from edl_tpu.models.moe import DroplessMoE
from edl_tpu.models.short_conv import SCONV_SCOPES
from edl_tpu.models.transformer import rope
from edl_tpu.obs import profile as obs_profile
from edl_tpu.obs import trace as obs_trace
from edl_tpu.ops import causal_conv as conv_module
from edl_tpu.ops import gated_causal_conv, grouped_matmul
from edl_tpu.train import create_state, make_train_step

gmm_module = importlib.import_module("edl_tpu.ops.grouped_matmul")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rehearsal(name):
    with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs", name + ".json")) as f:
        return json.load(f)


TOY = rehearsal("lfm2_24b_a2b")
D = TOY["hidden_size"]


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    scale = max(np.max(np.abs(want)), 1e-12)
    assert np.max(np.abs(got - want)) / scale <= tol


def shaken(params, seed=7):
    """Every vector (a norm's scale) off 1, so that a misplaced one shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 400))
    return jax.tree.map(
        lambda a: a * (1 + 0.2 * jax.random.normal(next(keys), a.shape)) if a.ndim == 1 else a,
        params,
    )


# -- the gated convolution -----------------------------------------------------


def conv_inputs(dtype, b=2, t=37, c=24, taps=3, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (b, t, 3 * c), dtype)
    w = jax.random.uniform(keys[1], (taps, c), jnp.float32, -0.6, 0.6)
    dy = jax.random.normal(keys[2], (b, t, c), dtype)
    return x, w, dy


def shifted_products(x, w):
    return reference.gated_conv(*jnp.split(x.astype(jnp.float32), 3, axis=-1), w)


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("what", ["value", "d_b_gate", "d_c_gate", "d_inner", "d_taps"])
def test_the_gated_convolution_equals_its_shifted_products(what, taps):
    x, w, dy = conv_inputs(jnp.float32, taps=taps)
    c = w.shape[1]
    got, vjp = jax.vjp(gated_causal_conv, x, w)
    want, ref_vjp = jax.vjp(shifted_products, x, w)
    if what == "value":
        assert got.shape == (2, 37, c) and got.dtype == jnp.float32
        _close(got, want, tol=1e-6)
        return
    (dx, dw), (ref_dx, ref_dw) = vjp(dy), ref_vjp(dy)
    if what == "d_taps":
        _close(dw, ref_dw, tol=1e-6)
        return
    third = ("d_b_gate", "d_c_gate", "d_inner").index(what)
    _close(dx[..., third * c:(third + 1) * c], ref_dx[..., third * c:(third + 1) * c], tol=1e-6)


def test_the_gated_convolution_rounds_once_from_float32():
    """bfloat16 in and out, float32 between: the value is within half a unit
    in the last place of the float32 result (2^-8 of the element), where gates,
    taps and sums in bfloat16 round five times."""
    x, w, _ = conv_inputs(jnp.bfloat16, t=64, c=128)
    got = gated_causal_conv(x, w)
    assert got.dtype == jnp.bfloat16
    want = np.asarray(shifted_products(x, w))
    assert np.all(np.abs(np.asarray(got, np.float32) - want) <= 2.0 ** -8 * np.abs(want) + 1e-30)


def test_the_gated_convolution_refuses_an_input_that_is_not_three_thirds():
    with pytest.raises(ValueError, match=r"is not \[B, T, 3 \* 24\]"):
        gated_causal_conv(jnp.zeros((1, 8, 48)), jnp.zeros((3, 24)))


def test_causal_conv_silus_two_callers_keep_their_plain_form():
    """The third caller's function sits beside ``causal_conv_silu`` and
    changes nothing under it: with a bias and a SiLU, at an offset."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 20, 40))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 16)) * 0.3
    bias = jax.random.normal(jax.random.PRNGKey(2), (16,))
    got = conv_module.causal_conv_silu(x, w, bias, offset=8)
    padded = jnp.pad(x[..., 8:24], ((0, 0), (3, 0), (0, 0)))
    want = jax.nn.silu(sum(w[k] * padded[:, k:k + 20] for k in range(4)) + bias)
    _close(got, want, tol=1e-6)


# -- the mixer -------------------------------------------------------------------


def recurrence(params, x, taps):
    """The mixer one step at a time, float32: the only state is the ``taps -
    1`` newest values of ``u = B_g * x~``."""
    w_in, w_out = params["in_proj"]["kernel"], params["out_proj"]["kernel"]
    w = params["conv_kernel"]

    def step(state, x_t):  # state [taps - 1, B, D], oldest first
        b_gate, c_gate, inner = jnp.split(x_t @ w_in, 3, axis=-1)
        u = b_gate * inner
        window = jnp.concatenate([state, u[None]], axis=0)
        c = jnp.einsum("kd,kbd->bd", w, window)
        return window[1:], (c_gate * c) @ w_out

    state = jnp.zeros((taps - 1,) + x.shape[:1] + x.shape[2:], jnp.float32)
    _, out = jax.lax.scan(step, state, jnp.swapaxes(x, 0, 1))
    return jnp.swapaxes(out, 0, 1)


@pytest.fixture(scope="module")
def mixer():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 19, D), jnp.float32)
    layer = ShortConvMixer(ShortConvSpec(taps=3), jnp.float32)
    return layer, x, jax.jit(layer.init)(jax.random.PRNGKey(1), x)["params"]


def test_mixer_equals_the_step_by_step_recurrence(mixer):
    layer, x, params = mixer
    assert params["in_proj"]["kernel"].shape == (D, 3 * D)
    assert params["conv_kernel"].shape == (3, D) and "bias" not in params["in_proj"]
    with jax.default_matmul_precision("highest"):
        _close(layer.apply({"params": params}, x), recurrence(params, x, 3), tol=1e-5)


@pytest.mark.parametrize("leaf", ["in_proj", "out_proj", "conv_kernel", "x"])
def test_mixer_gradient_equals_the_recurrences(mixer, leaf):
    layer, x, params = mixer
    weight = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    program = lambda p, x: jnp.sum(layer.apply({"params": p}, x) * weight)  # noqa: E731
    plain = lambda p, x: jnp.sum(recurrence(p, x, 3) * weight)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got, want = (jax.grad(f, argnums=(0, 1))(params, x) for f in (program, plain))
    if leaf == "x":
        _close(got[1], want[1], tol=1e-5)
    else:
        _close(jax.tree.leaves(got[0][leaf])[0], jax.tree.leaves(want[0][leaf])[0], tol=1e-5)


def test_mixer_initialises_its_taps_as_a_depthwise_conv1d_does():
    layer = ShortConvMixer(ShortConvSpec(taps=3), jnp.float32)
    taps = jax.jit(layer.init)(jax.random.PRNGKey(5), jnp.zeros((1, 4, 512)))["params"]["conv_kernel"]
    assert float(jnp.max(jnp.abs(taps))) <= 3 ** -0.5
    assert float(jnp.max(jnp.abs(taps))) > 0.95 * 3 ** -0.5 and abs(float(jnp.mean(taps))) < 0.05


def test_a_future_step_never_reaches_an_earlier_output(mixer):
    layer, x, params = mixer
    changed = x.at[:, 11:].add(1.0)
    a, b = (layer.apply({"params": params}, v) for v in (x, changed))
    np.testing.assert_array_equal(np.asarray(a[:, :11]), np.asarray(b[:, :11]))
    assert float(jnp.max(jnp.abs(a[:, 11:] - b[:, 11:]))) > 0


# -- the rotation ----------------------------------------------------------------


@pytest.mark.parametrize("base", [1e6, 1e4, 5e5])
def test_rope_at_a_base_equals_the_rotation_written_out(base):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 50, 3, 16))
    positions = jnp.broadcast_to(jnp.arange(50)[None], (2, 50))
    _close(rope(x, positions, base), reference.rotate(x, base), tol=1e-5)
    _close(rope(x, positions, base), rope_at_a_base(x, base), tol=1e-5)


def test_ropes_default_base_is_what_every_other_model_runs():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 33, 2, 8))
    positions = jnp.arange(33)[None]
    np.testing.assert_array_equal(
        np.asarray(rope(x, positions)), np.asarray(rope(x, positions, 10000.0))
    )
    assert ArchSpec().rope_theta == 10000.0
    assert float(jnp.max(jnp.abs(rope(x, positions) - rope(x, positions, 1e6)))) > 0.1


@pytest.mark.parametrize("base, passes", [(None, True), (1e4, False)])
def test_the_familys_rotation_check_tells_the_base(base, passes):
    result = family.rope_vs_reference(0, 8192, 2, 64, 1e6, base=base)
    assert result["positions"] == [8064, 8191]
    assert (result["rel_err"] <= family.ROPE_REL_TOL) is passes
    if not passes:
        assert result["rel_err"] > 1.0


def test_attention_rotates_at_the_specs_base():
    lm = toy_lm()
    x, _ = toy_batch()
    params = jax.jit(lm.init)(jax.random.PRNGKey(0), x)["params"]
    other = lm.clone(arch=toy_arch(rope_theta=10000.0))
    variables = {"params": params, "batch_stats": zero_bias(lm, x)}
    a, b = jax.jit(lm.apply)(variables, x), jax.jit(other.apply)(variables, x)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3


# -- the expert layer ------------------------------------------------------------

E, K, F = 8, 2, 48
LAYER = dict(
    TOY, num_experts=E, num_experts_per_tok=K, moe_intermediate_size=F,
    share={"router_experts": E, "experts_first": 0},
)


def _layer(held, **overrides):
    fields = dict(
        num_experts=E, top_k=K, d_ff=F, norm_topk_prob=True, norm_topk_eps=1e-6,
        aux_weight=0.0, z_weight=0.0, score_func="sigmoid", route_scale=1.0,
        bias_rate=1e-3, shared_d_ff=0, held=held, dtype=jnp.float32,
    )
    return DroplessMoE(**dict(fields, **overrides))


@pytest.fixture(scope="module")
def whole_layer():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, D), jnp.float32)
    variables = jax.jit(_layer(None).init)(jax.random.PRNGKey(2), x)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (E,), jnp.float32)
    return x, variables["params"], {"router_bias": bias - jnp.mean(bias)}


@pytest.mark.parametrize("what", ["value", "gradients"])
def test_the_expert_layer_equals_a_dense_loop(whole_layer, what):
    """Sigmoid scores, the bias under the choice, weights over their sum plus
    1e-6, no shared expert: against the reference's loop over every expert."""
    x, params, stats = whole_layer
    assert set(params) == {"router", "gate", "up", "down"}  # no shared expert

    def program(p, x):
        return _layer(None).apply({"params": p, "batch_stats": stats}, x)

    def plain(p, x):
        return reference.mixture(LAYER, p, stats["router_bias"], x.reshape(-1, D))[0].reshape(x.shape)

    with jax.default_matmul_precision("highest"):
        if what == "value":
            _close(program(params, x), plain(params, x), tol=1e-5)
            return
        weight = jax.random.normal(jax.random.PRNGKey(4), x.shape)
        got, want = (
            jax.grad(lambda p, x: jnp.sum(f(p, x) * weight), argnums=(0, 1))(params, x)
            for f in (program, plain)
        )
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        _close(a, b, tol=1e-4)


def test_the_renormalisations_epsilon_is_the_specs():
    """``w / (sum + eps)``: at the source's 1e-6 the k weights sum to ``s / (s
    + 1e-6)``, at the default 1e-20 to 1 as before; the default is what every
    other configuration runs."""
    assert MoESpec(8, 2, 16).norm_topk_eps == 1e-20
    assert DroplessMoE(8, 2, 16).norm_topk_eps == 1e-20
    # router logits near -14: sigmoid scores near 1e-6, where the epsilon shows
    x = jnp.full((1, 4, D), 1.0)
    for eps, total in ((1e-6, None), (1e-20, 1.0)):
        layer = _layer(None, norm_topk_eps=eps, bias_rate=0.0)
        params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)["params"]
        params = dict(params, router={"kernel": jnp.full((D, E), -14.0 / D)})
        params = dict(params, down=jnp.broadcast_to(jnp.eye(F, D)[None], (E, F, D)))
        scores = jax.nn.sigmoid(jnp.float32(-14.0))
        want = 2 * scores / (2 * scores + eps)
        hidden = jax.nn.silu(x[0] @ params["gate"]) * (x[0] @ params["up"])  # [E, 4, F]
        y = layer.apply({"params": params}, x)
        # every expert is chosen alike, so y = want * mean over the two chosen
        chosen = y[0, 0, :F] / ((hidden[0, 0] + hidden[1, 0]) / 2)
        assert float(jnp.median(chosen)) == pytest.approx(float(want), rel=1e-4)
        if total is not None:
            assert float(want) == pytest.approx(total)
        else:
            assert 0.6 < float(want) < 0.7


@pytest.mark.parametrize(
    "sizes", [(1,) * 8, (4, 4), (2, 6), (8,)], ids=lambda s: "x".join(map(str, s)),
)
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(whole_layer, sizes):
    """Every chip routes over all E experts and computes what its own give;
    nothing is on every chip alike (no shared expert), so the plain sum of the
    shares' outputs is the uncut layer, as the reference given all E experts as
    one share computes it; and the reference given a share agrees chip by chip."""
    x, params, stats = whole_layer
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.mixture(LAYER, params, stats["router_bias"], x.reshape(-1, D))
        total, first = 0.0, 0
        for count in sizes:
            here = dict(params, **{
                bank: params[bank][first:first + count] for bank in ("gate", "up", "down")
            })
            part, sown = _layer((first, count)).apply(
                {"params": here, "batch_stats": stats}, x, mutable=["metrics"]
            )
            assert float(sown["metrics"]["moe_rows_dropped"][0]) == 0
            want, _ = reference.mixture(
                dict(LAYER, num_experts=count,
                     share={"router_experts": E, "experts_first": first}),
                here, stats["router_bias"], x.reshape(-1, D),
            )
            _close(part.reshape(-1, D), want, tol=1e-5)
            total, first = total + part.reshape(-1, D), first + count
    _close(total, uncut, tol=1e-5)


def test_the_layer_sows_how_far_its_bias_leans(whole_layer):
    x, params, stats = whole_layer
    _, sown = _layer((0, 4)).apply(
        {"params": dict(params, **{b: params[b][:4] for b in ("gate", "up", "down")}),
         "batch_stats": stats}, x, mutable=["metrics"],
    )
    assert float(sown["metrics"]["moe_bias_absmax"][0]) == pytest.approx(
        float(jnp.max(jnp.abs(stats["router_bias"])))
    )
    _, sown = _layer(None, bias_rate=0.0).apply({"params": params}, x, mutable=["metrics"])
    assert "moe_bias_absmax" not in sown["metrics"]


# -- the grouped matmul at a width its measured tiling does not divide ---------


@functools.lru_cache(maxsize=None)
def megablox_and_a_loop(shape):
    """``[(value, d_lhs, d_rhs) by the kernels, by a float32 loop], live`` at a
    shape, once for the three cases that each look at one of them."""
    k, n = (256, 1536) if shape == "up" else (1536, 256)
    sizes = np.array([0, 130, 513, 7, 0, 250], np.int32)            # sums to 900
    m = 1024
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    lhs = jax.random.normal(keys[0], (m, k), jnp.float32)
    rhs = jax.random.normal(keys[1], (len(sizes), k, n), jnp.float32) * k ** -0.5
    dy = jax.random.normal(keys[2], (m, n), jnp.float32)
    live = (np.arange(m) < sizes.sum())[:, None]

    def kernels(lhs, rhs):
        out = grouped_matmul(lhs, rhs, jnp.asarray(sizes), "pallas", interpret=True)
        return jnp.where(live, out, 0.0)

    def loop(lhs, rhs):
        group = np.repeat(np.arange(len(sizes)), sizes)
        out = jnp.einsum("mk,mkn->mn", lhs[:sizes.sum()], rhs[group])
        return jnp.pad(out, ((0, m - sizes.sum()), (0, 0)))

    def both(fn):
        out, vjp = jax.vjp(fn, lhs, rhs)
        return (out, *vjp(dy))

    with jax.default_matmul_precision("highest"):
        return [jax.jit(lambda fn=fn: both(fn))() for fn in (kernels, loop)], live


@pytest.mark.parametrize("shape", ["up", "down"])
@pytest.mark.parametrize("what", ["value", "d_lhs", "d_rhs"])
def test_megablox_at_width_1536_with_ragged_and_empty_groups(shape, what):
    """The three kernels in the interpreter at the tiles the rule gives (rows
    of 128; 768 over the 1536 in N for gate/up, the 1536 whole in K for down;
    ``tests/test_gmm_tiles.py`` holds the rule itself), against a float32
    loop: groups that end inside a row tile, an empty one, rows past the sum."""
    (got, want), live = megablox_and_a_loop(shape)
    index = ("value", "d_lhs", "d_rhs").index(what)
    a, b = got[index], want[index]
    if what == "d_lhs":
        a = jnp.where(live, a, 0.0)  # rows past the sum hold whatever was there
    _close(a, b, tol=1e-5)


def test_each_traced_shape_leaves_one_gmm_tiles_instant_a_kernel():
    obs_trace.get_tracer().reset_notes()
    tracer = obs_trace.get_tracer()
    before = len([e for e in tracer.to_events() if e["name"] == "gmm_tiles"])
    sizes = jnp.asarray([100, 156], jnp.int32)
    lhs, rhs = jnp.ones((256, 128)), jnp.ones((2, 128, 1536))

    def loss(lhs, rhs):
        return jnp.sum(grouped_matmul(lhs, rhs, sizes, "pallas", interpret=True))

    for _ in range(2):  # the second trace of the same shape adds nothing
        jax.jit(jax.grad(loss, argnums=(0, 1))).lower(lhs, rhs)
    found = [e["args"] for e in tracer.to_events() if e["name"] == "gmm_tiles"][before:]
    assert sorted(e["kernel"] for e in found) == ["gmm", "gmm_dlhs", "tgmm"]
    by_kernel = {e["kernel"]: e for e in found}
    assert by_kernel["gmm"]["tiling"] == [128, 128, 768]
    assert by_kernel["gmm_dlhs"]["tiling"] == [128, 1536, 128]  # the contracted width whole
    assert by_kernel["tgmm"]["tiling"] == [128, 128, 768]
    for e in found:  # what the row tile was chosen from, and what it bounds
        assert (e["groups"], e["rows_a_group"], e["visits_bound"]) == (2, 128, 2 + 2 - 1)


# -- the model -------------------------------------------------------------------


def toy_arch(**overrides):
    return family.arch_spec(TOY) if not overrides else ArchSpec(
        **dict(family.arch_spec(TOY).__dict__, **overrides)
    )


def toy_lm(remat=False, dtype=jnp.float32):
    return family.build(TOY, 1, 0)["model"].clone(remat=remat, dtype=dtype)


def toy_batch(seed=0, b=2):
    return family.host_batches(TOY, b, seed, n_batches=1)[0]


def zero_bias(lm, x):
    return jax.tree.map(jnp.zeros_like, jax.jit(lm.init)(jax.random.PRNGKey(0), x)["batch_stats"])


def lm_loss(logits, targets):
    from edl_tpu.train import cross_entropy_loss

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


def test_the_toy_is_the_published_pattern(toy_variables):
    params = toy_variables[0]
    assert TOY["layer_types"] == ["conv", "full_attention", "conv", "conv", "conv"]
    assert set(params["layer_0"]) == {"sconv", "mlp", "ln1", "ln2"}     # the dense layer
    assert set(params["layer_1"]) == {"attn", "moe", "ln1", "ln2"}
    assert set(params["layer_2"]) == {"sconv", "moe", "ln1", "ln2"}
    assert params["layer_1"]["attn"]["q_norm"]["scale"].shape == (16,)  # a head's own
    assert set(params["layer_1"]["moe"]) == {"router", "gate", "up", "down"}
    assert params["layer_1"]["moe"]["router"]["kernel"].shape == (D, 8)  # the whole router
    assert params["layer_1"]["moe"]["gate"].shape == (4, D, 48)          # the held experts
    assert "lm_head" not in params                                        # a tied head


@pytest.fixture(scope="module")
def lm_and_reference(toy_variables):
    """``remat -> [(loss, logits, gradients) of the toy LM, of the plain
    reference]`` at one batch, once for the cases that each look at one."""
    params, stats, x, y = toy_variables

    def plain(p):
        return reference.loss(TOY, p, stats, x, y), reference.forward(TOY, p, stats, x)[0]

    @functools.lru_cache(maxsize=None)
    def outputs(fn):
        with jax.default_matmul_precision("highest"):
            return loss_logits_gradients(fn, params)

    @functools.lru_cache(maxsize=None)
    def program(remat):
        lm = toy_lm(remat=remat)

        def fn(p):
            logits = lm.apply({"params": p, "batch_stats": stats}, x)
            return lm_loss(logits, y)[0], logits

        return fn

    return lambda remat: (outputs(program(remat)), outputs(plain))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("what", ["logits", "loss", "gradients"])
def test_the_lm_equals_the_plain_reference(lm_and_reference, remat, what):
    (loss, logits, got), (want_loss, want_logits, want) = lm_and_reference(remat)
    if what == "logits":
        _close(logits, want_logits)
        return
    if what == "loss":
        assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
        return
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(b)) > 0, name  # the parameter is in the graph
        assert float(jnp.linalg.norm(a)) == pytest.approx(float(jnp.linalg.norm(b)), rel=1e-3), name
        _close(a, b, tol=1e-3)


@pytest.mark.parametrize("tokens", [1, 5], ids=["step", "prefill"])
def test_a_decode_call_on_a_conv_block_raises(tokens):
    lm = toy_lm().clone(decode=True, max_decode_len=16)
    with pytest.raises(NotImplementedError, match="short-convolution block has no decode"):
        lm.init(jax.random.PRNGKey(0), np.zeros((1, tokens), np.int32))


def test_an_unknown_layer_type_names_conv_among_the_known():
    lm = toy_lm().clone(arch=toy_arch(layer_types=("conv", "short", "conv", "conv", "conv")))
    with pytest.raises(ValueError, match="linear_attention, conv"):
        lm.init(jax.random.PRNGKey(0), toy_batch()[0])


# -- what the other configurations run is what it was ---------------------------

OTHERS = ["mistral_7b", "olmoe_1b_7b", "granite_4_0_h_micro", "trinity_mini",
          "olmo_hybrid_7b"]


def lowered_forward(name):
    config = rehearsal(name)
    family_of = importlib.import_module("benchmark.families." + config["family"])
    lm = family_of.build(config, 1, 0)["model"]
    x = family_of.host_batches(config, 1, 0, n_batches=1)[0][0]
    variables = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), x))
    variables = {k: v for k, v in variables.items() if k in ("params", "batch_stats")}
    text = jax.jit(lambda v: lm.apply(v, x)).lower(variables).as_text()
    return lm, text


@pytest.mark.parametrize("name", OTHERS)
def test_the_other_configurations_forward_is_what_it_was(name, monkeypatch):
    """Every new field at its default: the models of the benchmark's other
    configurations hand the expert layer no epsilon but 1e-20 and ``rope`` no
    base but its own 10,000: they lower to the same text with the parent's
    call of ``rope``, which took none from ``Attention``."""
    lm, text = lowered_forward(name)
    arch = lm.arch or ArchSpec()
    assert arch.rope_theta == 10000.0 and arch.short_conv is None
    assert "conv" not in (arch.layer_types or ())
    if lm.moe is not None:
        assert lm.moe.norm_topk_eps == 1e-20
    parents = transformer_module.rope

    def rope_without_a_base(x, positions, base=None):
        return parents(x, positions)

    monkeypatch.setattr(transformer_module, "rope", rope_without_a_base)
    assert lowered_forward(name)[1] == text


# -- the tracing -----------------------------------------------------------------


def test_the_lm_trains_through_the_step_and_exports_the_bias_gauge():
    lm = toy_lm(remat=True, dtype=jnp.bfloat16)
    x, y = toy_batch(b=1)
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.adamw(1e-2))
    assert set(state.sown) == {"moe_bias_absmax", "moe_held_load_max", "moe_load_max",
                               "moe_rows_dropped", "moe_rows_held"}
    step = make_train_step(lm_loss, numerics=True, donate=False)
    first = None
    for i in range(6):
        state, metrics = step(state, (x, y))
        first = first if first is not None else float(metrics["loss"])
        # the gauge is the bias the step's choice was made under: i moves of
        # +-1e-3 less their mean, so under 2e-3 each
        assert float(metrics["moe_bias_absmax"]) <= i * 2e-3
    assert float(metrics["loss"]) < first and np.isfinite(float(metrics["loss"]))
    assert float(metrics["moe_bias_absmax"]) > 0
    assert float(metrics["moe_rows_dropped"]) == 0
    from edl_tpu.obs import metrics as obs_metrics
    from edl_tpu.obs import numerics as obs_numerics

    obs_numerics.publish_sown({k: np.asarray(metrics[k]) for k in state.sown})
    assert "edl_train_moe_bias_absmax " in obs_metrics.default_registry().render()


@functools.lru_cache(maxsize=None)
def compiled_steps_scopes():
    lm = toy_lm(remat=True, dtype=jnp.bfloat16)
    x, y = toy_batch(b=1)
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.adamw(1e-3))
    compiled = make_train_step(lm_loss, numerics=False).lower(state, (x, y)).compile()
    return set(obs_profile.scopes_of_hlo(compiled.as_text(), SCONV_SCOPES).values())


@pytest.mark.parametrize("scope", SCONV_SCOPES)
def test_the_compiled_step_names_the_mixers_scopes(scope):
    assert scope in compiled_steps_scopes()


def test_each_traced_shape_leaves_one_sconv_shape_instant():
    obs_trace.get_tracer().reset_notes()
    tracer = obs_trace.get_tracer()
    before = len([e for e in tracer.to_events() if e["name"] == "sconv_shape"])
    x, w, _ = conv_inputs(jnp.bfloat16, b=1, t=256, c=128)
    for _ in range(2):
        jax.jit(gated_causal_conv).lower(x, w)
    found = [e["args"] for e in tracer.to_events() if e["name"] == "sconv_shape"][before:]
    assert len(found) == 1
    assert found[0]["channels"] == 128 and found[0]["taps"] == 3
    assert found[0]["implementation"] == "plain"
    assert found[0]["bytes"] == 4 * 256 * 128 * 2  # three reads and one write, bfloat16
