"""The Granite 4.0-H path end to end on the CPU at toy widths: the chunked
state-space scan against the sequential recurrence, the Mamba-2 mixer and a
hybrid ``TransformerLM`` (layer pattern, position-free attention at a given
score scale, tied and scaled embeddings) against the benchmark's plain
reference, and the programs the dense and the expert LM lower to, unchanged."""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import loss_logits_gradients, value_and_gradients

from benchmark.reference import ssm_lm as reference
from edl_tpu.models import ArchSpec, Mamba2Mixer, MambaSpec, MoESpec, TransformerLM
from edl_tpu.models.mamba import SSM_SCOPES
from edl_tpu.obs import profile as obs_profile
from edl_tpu.ops import ssd_scan
from edl_tpu.parallel.pipeline_lm import split_lm_params
from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

# a toy Granite: 8 Mamba-2 heads of 16 in ONE group, as published, over a state
# of 16, GQA 4:2 at head 16 in a model of width 48, so head_dim is not d_model /
# heads. One group, because ``reference/ssm_lm.py`` (Hugging Face's Granite layer)
# normalises over all d_inner, and a layer of more groups normalises each group's
# channels among themselves (``tests/test_nemotron_h.py`` holds 2 and 4 groups
# against ``reference/nemotron_h_lm.py``): with one group both are one function.
TOY = {
    "hidden_size": 48, "num_attention_heads": 4, "num_key_value_heads": 2,
    "layer_types": ["mamba", "attention", "mamba"], "num_hidden_layers": 3,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_n_groups": 1,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_conv_bias": True,
    "mamba_chunk_size": 8, "attention_multiplier": 1.0 / 64,
    "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
    "logits_scaling": 8.0, "rms_norm_eps": 1e-5, "vocab_size": 64,
    "shared_intermediate_size": 40,
}
SPEC = MambaSpec(num_heads=8, head_dim=16, d_state=16, n_groups=1, d_conv=4, chunk=8)


def toy_arch(**overrides):
    fields = dict(
        layer_types=tuple(TOY["layer_types"]), mamba=SPEC, head_dim=16, rope=False,
        attn_scale=TOY["attention_multiplier"], tie_embeddings=True,
        embedding_multiplier=TOY["embedding_multiplier"],
        residual_multiplier=TOY["residual_multiplier"],
        logits_scaling=TOY["logits_scaling"],
    )
    return ArchSpec(**dict(fields, **overrides))


def toy_lm(arch=None, dtype=jnp.float32, remat=False):
    arch = arch or toy_arch()
    return TransformerLM(
        vocab_size=64, d_model=48, num_heads=4, num_kv_heads=2,
        num_layers=len(arch.layer_types), d_ff=40, dtype=dtype, remat=remat,
        norm_eps=1e-5, arch=arch,
    )


def toy_batch(seed=0, b=2, t=24):
    tokens = np.random.default_rng(seed).integers(0, 64, (b, t + 1)).astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]


def shaken(params, seed=5):
    """Every leaf moved off its initial value: the ones and zeros an init
    leaves (norm scales, ``D``, the convolution's bias) would hide a factor."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + 0.1 * jax.random.normal(key, leaf.shape) for leaf, key in zip(leaves, keys)
    ])


def scan_inputs(seed=0, b=2, t=24, h=8, p=16, g=2, n=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (
        jax.random.normal(keys[0], (b, t, h, p)),
        jnp.exp(jax.random.uniform(keys[1], (b, t, h), minval=-5.0, maxval=-1.0)),
        -jax.random.uniform(keys[2], (h,), minval=1.0, maxval=16.0),
        jax.random.normal(keys[3], (b, t, g, n)),
        jax.random.normal(keys[4], (b, t, g, n)),
        jax.random.normal(keys[5], (h,)),
    )


def lm_loss(logits, y):
    return cross_entropy_loss(logits.reshape(-1, logits.shape[-1]), y.reshape(-1))


# -- the scan ----------------------------------------------------------------


@pytest.mark.parametrize("chunk", [4, 16, 24, 256], ids=lambda c: "chunk%d" % c)
def test_scan_equals_the_recurrence_at_every_chunk(chunk):
    """chunk 16 does not divide T = 24 (the padded tail must leave the state
    alone), 24 is T and 256 is past it: one answer, the recurrence's."""
    args = scan_inputs()
    want_y, want_state = reference.recurrence(*args)
    got_y, got_state = ssd_scan(*args, chunk=chunk, return_final_state=True)
    np.testing.assert_allclose(got_y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_state, want_state, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("wrt", range(6), ids=["x", "dt", "A", "B", "C", "D"])
def test_scan_gradients_equal_the_recurrences(wrt):
    args = scan_inputs(seed=1)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    got = jax.grad(lambda *a: jnp.sum(ssd_scan(*a, chunk=8) * w), wrt)(*args)
    want = jax.grad(lambda *a: jnp.sum(reference.recurrence(*a)[0] * w), wrt)(*args)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("cut", [8, 13])
def test_a_sequence_cut_in_two_with_the_state_carried_equals_the_whole(cut):
    x, dt, a, b, c, d = scan_inputs(seed=2)
    whole, final = ssd_scan(x, dt, a, b, c, d, chunk=8, return_final_state=True)
    head, state = ssd_scan(
        x[:, :cut], dt[:, :cut], a, b[:, :cut], c[:, :cut], d, chunk=8,
        return_final_state=True,
    )
    tail, last = ssd_scan(
        x[:, cut:], dt[:, cut:], a, b[:, cut:], c[:, cut:], d, chunk=8,
        initial_state=state, return_final_state=True,
    )
    np.testing.assert_allclose(
        jnp.concatenate([head, tail], axis=1), whole, rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(last, final, rtol=2e-5, atol=2e-5)


def test_scan_in_bfloat16_keeps_its_decays_and_state_in_float32():
    """The result comes back in x's dtype, the state in float32, and the
    bfloat16 scan stays within operand rounding of the float32 recurrence
    over a sequence long enough (steps of 0.1 x 16 a token, 64 tokens a
    chunk) for a bfloat16 running sum to be off by whole units."""
    x, dt, a, b, c, d = scan_inputs(seed=3, b=1, t=256)
    dt = jnp.full_like(dt, 0.1)
    bf = lambda v: v.astype(jnp.bfloat16)  # noqa: E731
    y, state = ssd_scan(bf(x), dt, a, bf(b), bf(c), d, chunk=64, return_final_state=True)
    assert y.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    f32 = lambda v: bf(v).astype(jnp.float32)  # noqa: E731
    want, _ = reference.recurrence(f32(x), dt, a, f32(b), f32(c), d)
    err = jnp.max(jnp.abs(y.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want))
    assert float(err) < 0.02


# -- the mixer ---------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def mixer_and_params():
    mixer = Mamba2Mixer(SPEC, jnp.float32, 1e-5)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 48))
    return mixer, shaken(jax.jit(mixer.init)(jax.random.PRNGKey(1), x)["params"]), x


def test_mixer_equals_the_sequential_reference():
    mixer, params, x = mixer_and_params()
    assert set(params) == {"in_proj", "out_proj", "conv_kernel", "conv_bias",
                           "A_log", "dt_bias", "D", "norm"}
    assert params["in_proj"]["kernel"].shape == (48, 128 + (128 + 2 * 16) + 8)
    (got, _), (want, _) = mixer_both_ways()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


MIXER_LEAVES = ["A_log", "dt_bias", "D", "conv_bias", "conv_kernel", "norm",
                "in_proj/kernel", "out_proj/kernel", "input"]


@functools.lru_cache(maxsize=None)
def mixer_both_ways():
    """``(value, gradients)`` of the mixer and of the sequential reference
    under one cotangent, once for the cases that each look at one leaf."""
    mixer, params, x = mixer_and_params()
    w = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    return [
        value_and_gradients(fn, params, x, weight=w) for fn in (
            lambda p, x: mixer.apply({"params": p}, x),
            lambda p, x: reference.mamba_mixer(TOY, p, x),
        )
    ]


@pytest.mark.parametrize("leaf", MIXER_LEAVES)
def test_mixer_gradient_equals_the_references(leaf):
    (_, got), (_, want) = mixer_both_ways()

    def pick(grads):
        if leaf == "input":
            return grads[1]
        out = grads[0]
        for part in leaf.split("/"):
            out = out[part]
        return out

    assert float(jnp.max(jnp.abs(pick(want)))) > 1e-3     # the leaf is in use
    np.testing.assert_allclose(pick(got), pick(want), rtol=5e-4, atol=2e-5)


def test_mixer_initialises_as_mamba2_does():
    mixer = Mamba2Mixer(MambaSpec(num_heads=512, head_dim=2, d_state=4), jnp.float32)
    p = jax.jit(mixer.init)(jax.random.PRNGKey(0), jnp.zeros((1, 4, 16)))["params"]
    a, dt = jnp.exp(p["A_log"]), jax.nn.softplus(p["dt_bias"])
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    assert 1e-3 * 0.999 <= float(dt.min()) and float(dt.max()) <= 1e-1 * 1.001
    assert float(jnp.median(dt)) == pytest.approx(1e-2, rel=0.25)  # log-uniform
    np.testing.assert_array_equal(p["D"], 1.0)
    assert float(jnp.abs(p["conv_kernel"]).max()) <= 0.5


# -- the whole model ---------------------------------------------------------


@functools.lru_cache(maxsize=None)
def lm_and_reference(remat):
    """``(loss, logits, gradients)`` of the toy LM and of the plain reference
    at one batch, once for the three cases that each look at one of them."""
    lm = toy_lm(remat=remat)
    x, y = toy_batch()
    params = shaken(jax.jit(lm.init)(jax.random.PRNGKey(3), x)["params"])
    assert "lm_head" not in params                       # tied: no second matrix
    assert set(params["layer_0"]) == {"ln1", "mamba", "ln2", "mlp"}
    assert set(params["layer_1"]) == {"ln1", "attn", "ln2", "mlp"}
    assert params["layer_1"]["attn"]["q"]["kernel"].shape == (48, 4, 16)

    def program(p):
        logits = lm.apply({"params": p}, x)
        return lm_loss(logits, y)[0], logits

    def plain(p):
        logits = reference.forward(TOY, p, x)
        return reference.loss(logits, y), logits

    return [loss_logits_gradients(fn, params) for fn in (program, plain)]


@pytest.mark.parametrize("what", ["logits", "loss", "gradients"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_hybrid_lm_equals_the_plain_reference(remat, what):
    (loss, logits, got), (want_loss, want_logits, want) = lm_and_reference(remat)
    if what == "logits":
        np.testing.assert_allclose(logits, want_logits, rtol=2e-4, atol=2e-5)
        return
    if what == "loss":
        assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
        return
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            a, b, rtol=1e-3, atol=1e-6, err_msg=jax.tree_util.keystr(path)
        )


def test_the_tied_embeddings_gradient_is_the_sum_of_both_uses():
    lm = toy_lm()
    x, y = toy_batch()
    params = shaken(jax.jit(lm.init)(jax.random.PRNGKey(3), x)["params"])
    tied = jax.jit(jax.grad(lambda p: lm_loss(lm.apply({"params": p}, x), y)[0]))(params)

    def untied(lookup, head):
        """The reference's forward pass with the two uses of E as two
        arguments (``reference.forward`` itself ties them)."""
        config = TOY
        eps, res = config["rms_norm_eps"], config["residual_multiplier"]
        h = config["embedding_multiplier"] * lookup[x]
        for i, kind in enumerate(config["layer_types"]):
            lp = params["layer_%d" % i]
            n = reference._rms_norm(h, lp["ln1"]["scale"], eps)
            mixer = reference.mamba_mixer if kind == "mamba" else reference.attention_mixer
            h = h + res * mixer(config, lp["mamba" if kind == "mamba" else "attn"], n)
            n = reference._rms_norm(h, lp["ln2"]["scale"], eps)
            ff = jax.nn.silu(n @ lp["mlp"]["gate"]["kernel"]) * (n @ lp["mlp"]["up"]["kernel"])
            h = h + res * (ff @ lp["mlp"]["down"]["kernel"])
        h = reference._rms_norm(h, params["ln_f"]["scale"], eps)
        return reference.loss((h @ head.T) / config["logits_scaling"], y)

    e = params["embed"]["embedding"]
    from_lookup, from_head = jax.jit(jax.grad(untied, (0, 1)))(e, e)
    assert float(jnp.abs(from_lookup).max()) > 0 and float(jnp.abs(from_head).max()) > 0
    np.testing.assert_allclose(
        tied["embed"]["embedding"], from_lookup + from_head, rtol=1e-3, atol=1e-6
    )


MULTIPLIERS = [
    ("embedding_multiplier", 3.0), ("residual_multiplier", 0.7),
    ("logits_scaling", 2.5), ("attention_multiplier", 0.3),
]


@pytest.mark.parametrize("key,value", MULTIPLIERS, ids=[k for k, _ in MULTIPLIERS])
def test_each_multiplier_reaches_the_logits_as_the_reference_says(key, value):
    field = "attn_scale" if key == "attention_multiplier" else key
    lm = toy_lm(toy_arch(**{field: value}))
    x, _ = toy_batch()
    params = shaken(jax.jit(toy_lm().init)(jax.random.PRNGKey(3), x)["params"])
    got = jax.jit(lm.apply)({"params": params}, x)
    want = jax.jit(lambda p: reference.forward(dict(TOY, **{key: value}), p, x))(params)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    base = jax.jit(toy_lm().apply)({"params": params}, x)
    assert float(jnp.max(jnp.abs(got - base))) > 1e-3    # and it is not ignored


def test_attention_without_rope_sees_no_positions_and_with_rope_does():
    x, _ = toy_batch()
    params = jax.jit(toy_lm().init)(jax.random.PRNGKey(3), x)["params"]
    shifted = jnp.broadcast_to(jnp.arange(x.shape[1])[None] + 100, x.shape)
    plain, roped = jax.jit(toy_lm().apply), jax.jit(toy_lm(toy_arch(rope=True)).apply)
    np.testing.assert_array_equal(
        plain({"params": params}, x), plain({"params": params}, x, shifted)
    )
    assert float(jnp.max(jnp.abs(
        roped({"params": params}, x) - plain({"params": params}, x)
    ))) > 1e-4


def test_a_pattern_of_the_wrong_length_or_kind_is_refused():
    x, _ = toy_batch()
    short = TransformerLM(vocab_size=64, d_model=48, num_heads=4, num_layers=2,
                          d_ff=40, arch=toy_arch())
    with pytest.raises(ValueError, match="3 layer_types for num_layers 2"):
        short.init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="unknown layer type 'hyena'"):
        toy_lm(toy_arch(layer_types=("mamba", "hyena", "attention"))).init(
            jax.random.PRNGKey(0), x
        )
    with pytest.raises(ValueError, match="ArchSpec"):
        split_lm_params(toy_lm(), {}, 1)


def test_the_hybrid_trains_through_the_step_and_is_never_split_at_batch_one():
    lm = toy_lm(dtype=jnp.bfloat16, remat=True)
    x, y = toy_batch(b=1)
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.adamw(1e-2))
    step = make_train_step(lm_loss, numerics=True, donate=False)
    first = None
    for _ in range(8):
        state, metrics = step(state, (x, y))
        first = first if first is not None else float(metrics["loss"])
    assert "half_sq" not in metrics["_numerics"]
    assert float(metrics["loss"]) < first and np.isfinite(float(metrics["loss"]))


@functools.lru_cache(maxsize=None)
def compiled_steps_scopes():
    lm = toy_lm(dtype=jnp.bfloat16, remat=True)
    x, y = toy_batch(b=1)
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.adamw(1e-3))
    compiled = make_train_step(lm_loss, numerics=False).lower(state, (x, y)).compile()
    return set(obs_profile.scopes_of_hlo(compiled.as_text(), SSM_SCOPES).values())


@pytest.mark.parametrize("scope", SSM_SCOPES)
def test_the_compiled_step_names_the_mixers_scopes(scope):
    assert scope in compiled_steps_scopes()


# -- the other two LMs are what they were -------------------------------------

# sha256 of the lowered step of an OLMoE-shaped toy LM (jax 0.9.0, CPU): a
# ``TransformerLM`` field at its default leaves the expert LM's program what it
# was (``tests/test_olmoe.py`` keeps the dense LM's). Taken on PR 28's commit
# until PR 52, which changed the expert layer's program on purpose (its
# ``top_k`` has a gradient rule of its own, and ``save_flash`` keeps the route
# by name), and then PR 65 (that rule sends the gradient home by comparison,
# ``models/moe.py:_sent_home``, where it scattered), and then PR 68 (the loss
# head's rows are ``ops/cross_entropy.py``'s one ``custom_vjp``, where optax's
# ``log_softmax`` and an ``argmax`` stood); these are PR 68's
OLMOE_STEP = {
    True: "1f46d746567c096fa0e4bce8f00771ac52e234749bea8d8916316e0fcb2e046f",
    False: "f50465c72746811f70cf1c808a4c800e18d38d778a7c698723bef42ebb095a86",
}
# the same step behind the attention projections' fence (PR 39): one
# ``optimization_barrier`` a projection and half-batch; with the fence off
# the text is still the one above
OLMOE_STEP_FENCED = {
    True: "5396a8cf40d39a541f4ddaa8b2d8433b530529eaad810a82be20b838c0f07646",
    False: "886a3ab9dac0b1dfa51749c83a4f442da65808e571b4f3ff15f530d32ae464ef",
}


@pytest.mark.parametrize("fenced", [True, False], ids=["fenced", "unfenced"])
@pytest.mark.parametrize("numerics", [True, False], ids=["numerics", "bare"])
def test_the_expert_lm_lowers_to_the_step_it_was(unfence, numerics, fenced):
    if not fenced:
        unfence()
    lm = TransformerLM(
        vocab_size=64, d_model=32, num_heads=4, num_kv_heads=4, num_layers=2,
        d_ff=24, dtype=jnp.bfloat16, remat=True, norm_eps=1e-5, qk_norm=True,
        moe=MoESpec(num_experts=8, top_k=2, d_ff=24, aux_weight=0.005, z_weight=0.0005),
    )
    tokens = np.zeros((4, 16), np.int32)
    state = jax.eval_shape(
        lambda: create_state(lm, jax.random.PRNGKey(0), tokens, optax.adamw(3e-4))
    )
    text = make_train_step(lm_loss, numerics=numerics).lower(
        state, (tokens, tokens)
    ).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        OLMOE_STEP_FENCED if fenced else OLMOE_STEP
    )[numerics]


def test_an_arch_spec_at_its_defaults_is_the_dense_model():
    x, _ = toy_batch()
    dense = TransformerLM(vocab_size=64, d_model=48, num_heads=4, num_kv_heads=2,
                          num_layers=2, d_ff=40, dtype=jnp.float32)
    params = jax.jit(dense.init)(jax.random.PRNGKey(0), x)["params"]
    np.testing.assert_array_equal(
        dense.apply({"params": params}, x),
        dense.clone(arch=ArchSpec()).apply({"params": params}, x),
    )
