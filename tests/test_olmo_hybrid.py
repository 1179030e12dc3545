"""The linear-attention hybrid's path end to end on the CPU at toy widths: the
chunked gated delta rule against the step-by-step recurrence (value, final
state, gradients), the mixer and a hybrid ``TransformerLM`` (three kinds of
norm placement, a QK norm over the whole width, no positions) against the
benchmark's plain reference, the convolution kernels' no-bias call, and the
scopes, gauges and instant the model leaves for the tracing."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import loss_logits_gradients, value_and_gradients

from benchmark.reference import gdn_lm as reference
from edl_tpu.models import ArchSpec, GatedDeltaMixer, GatedDeltaSpec, TransformerLM
from edl_tpu.models import transformer as transformer_module
from edl_tpu.models.gated_delta import GDN_SCOPES
from edl_tpu.models.transformer import Block
from edl_tpu.obs import profile as obs_profile
from edl_tpu.obs import trace as obs_trace
from edl_tpu.ops import causal_conv_silu, gated_delta_rule
from edl_tpu.ops import gated_delta as rule_module
from edl_tpu.ops.causal_conv import _plain as conv_plain
from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

# a toy hybrid: 3 heads of 8 (keys) and 16 (values) beside MHA 4 x 12 in a
# model of width 48, chunks of 8 steps, so a sequence of 24 is three chunks
TOY = {
    "hidden_size": 48, "num_attention_heads": 4, "num_key_value_heads": 4,
    "layer_types": ["linear_attention", "full_attention", "linear_attention"],
    "num_hidden_layers": 3, "linear_num_key_heads": 3, "linear_num_value_heads": 3,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rms_norm_eps": 1e-6, "vocab_size": 64, "intermediate_size": 40,
}
SPEC = GatedDeltaSpec(num_heads=3, key_dim=8, value_dim=16, d_conv=4, chunk=8)


def toy_arch(**overrides):
    fields = dict(
        layer_types=("linear_attention", "attention", "linear_attention"),
        gated_delta=SPEC, rope=False, post_norms="only",
    )
    return ArchSpec(**dict(fields, **overrides))


def toy_lm(arch=None, dtype=jnp.float32, remat=False):
    arch = arch or toy_arch()
    return TransformerLM(
        vocab_size=64, d_model=48, num_heads=4, num_kv_heads=4,
        num_layers=len(arch.layer_types), d_ff=40, dtype=dtype, remat=remat,
        norm_eps=1e-6, qk_norm=True, arch=arch,
    )


def toy_batch(seed=0, b=2, t=24):
    tokens = np.random.default_rng(seed).integers(0, 64, (b, t + 1)).astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]


def shaken(params, seed=5):
    """Every leaf moved off its initial value: the ones an init leaves in the
    norm scales would hide a factor."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + 0.1 * jax.random.normal(key, leaf.shape) for leaf, key in zip(leaves, keys)
    ])


def rule_inputs(seed=0, b=2, t=24, h=3, d_k=8, d_v=16, beta=(1.0, 2.0),
                log_decay=(-5.0, -0.001)):
    """Unit keys, queries scaled as the layer scales them, ``beta`` drawn in
    (1, 2) (the half of its range that ``neg_eigval`` adds) and decays from
    ``exp(-5)`` (near 0) to ``exp(-0.001)`` (near 1)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda m: m / jnp.linalg.norm(m, axis=-1, keepdims=True)  # noqa: E731
    low, high = np.log(-log_decay[1]), np.log(-log_decay[0])
    return (
        unit(jax.random.normal(keys[0], (b, t, h, d_k))) * d_k ** -0.5,
        unit(jax.random.normal(keys[1], (b, t, h, d_k))),
        jax.random.normal(keys[2], (b, t, h, d_v)),
        -jnp.exp(jax.random.uniform(keys[3], (b, t, h), minval=low, maxval=high)),
        jax.random.uniform(keys[4], (b, t, h), minval=beta[0], maxval=beta[1]),
    )


def lm_loss(logits, y):
    return cross_entropy_loss(logits.reshape(-1, logits.shape[-1]), y.reshape(-1))


# -- the rule ------------------------------------------------------------------

CASES = {
    "chunk16": dict(chunk=16, t=64), "chunk64": dict(chunk=64, t=128),
    "chunk16_ragged": dict(chunk=16, t=40), "chunk64_ragged": dict(chunk=64, t=100),
    "chunk_past_t": dict(chunk=64, t=24), "near_zero_decay": dict(chunk=16, t=48,
                                                                  log_decay=(-20.0, -5.0)),
    "near_one_decay": dict(chunk=16, t=48, log_decay=(-1e-3, -1e-5)),
}


@pytest.mark.parametrize("case", CASES, ids=list(CASES))
def test_rule_equals_the_recurrence(case):
    """Chunks that divide T, that do not (the padded tail must leave the state
    alone) and one past it; d_k 8 against d_v 16; beta in (1, 2); decays near 0
    and near 1: one answer, the recurrence's."""
    opts = dict(CASES[case])
    chunk = opts.pop("chunk")
    args = rule_inputs(**opts)
    want_o, want_state = reference.recurrence(*args)
    got_o, got_state = gated_delta_rule(*args, chunk=chunk, return_final_state=True)
    assert got_o.shape == want_o.shape and got_state.shape == (2, 3, 8, 16)
    np.testing.assert_allclose(got_o, want_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_state, want_state, rtol=2e-5, atol=2e-5)


@functools.lru_cache(maxsize=None)
def rule_gradients(chunk):
    """The five gradients of the rule at a chunk (``None``: the recurrence's),
    once for the cases that each look at one."""
    args = rule_inputs(seed=1, t=80)                # 80: neither chunk divides it
    w = jax.random.normal(jax.random.PRNGKey(9), (2, 80, 3, 16))
    if chunk is None:
        fn = lambda *a: jnp.sum(reference.recurrence(*a)[0] * w)  # noqa: E731
    else:
        fn = lambda *a: jnp.sum(gated_delta_rule(*a, chunk=chunk) * w)  # noqa: E731
    return jax.jit(jax.grad(fn, range(5)))(*args)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("wrt", range(5), ids=["q", "k", "v", "g", "beta"])
def test_rule_gradients_equal_the_recurrences(wrt, chunk):
    np.testing.assert_allclose(
        rule_gradients(chunk)[wrt], rule_gradients(None)[wrt], rtol=2e-4, atol=2e-4
    )


@functools.lru_cache(maxsize=None)
def carry_gradients(dtype, t):
    """Gradients of the rule with respect to ``q, k, v, g, beta`` and the
    initial state, with cotangents on ``o`` and on the final state: through
    the carry's own backward, and through jax's transpose of the same scan
    (``carried_states`` without its ``custom_vjp``: the parent's rule)."""
    args = rule_inputs(seed=1, t=t)
    args = tuple(a.astype(dtype) for a in args[:3]) + args[3:]
    args += (jax.random.normal(jax.random.PRNGKey(4), (2, 3, 8, 16)),)
    w = jax.random.normal(jax.random.PRNGKey(9), (2, t, 3, 16))
    w_state = jax.random.normal(jax.random.PRNGKey(10), (2, 3, 8, 16))

    def loss(q, k, v, g, beta, initial):
        o, state = gated_delta_rule(
            q, k, v, g, beta, chunk=16, initial_state=initial, return_final_state=True
        )
        return jnp.sum(o.astype(jnp.float32) * w) + jnp.sum(state * w_state)

    own = jax.jit(jax.grad(loss, range(6)))(*args)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(rule_module, "carried_states", rule_module.carried_states.fun)
        plain = jax.jit(jax.grad(loss, range(6)))(*args)
    return own, plain


@pytest.mark.parametrize("t", [64, 80], ids=["whole_chunks", "ragged"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("wrt", range(6), ids=["q", "k", "v", "g", "beta", "initial_state"])
def test_the_carrys_own_backward_equals_jaxs_transpose_of_the_scan(wrt, dtype, t):
    """One reverse scan of ``jax.vjp`` of the same step at the saved float32
    states: the same operations at the same operands, so float32 rounding (the
    order a compiled sum is taken in) is all that may differ, under bfloat16
    operands as under float32 ones."""
    own, plain = carry_gradients(dtype, t)
    got, want = (np.asarray(a[wrt], np.float32) for a in (own, plain))
    assert own[wrt].dtype == plain[wrt].dtype and np.abs(want).max() > 0
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_the_final_states_gradient_reaches_the_inputs():
    args = rule_inputs(seed=2, t=40)
    w = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 8, 16))
    final = lambda fn: lambda *a: jnp.sum(fn(*a)[1] * w)  # noqa: E731
    got = jax.grad(final(
        lambda *a: gated_delta_rule(*a, chunk=16, return_final_state=True)
    ), (0, 1, 2, 3, 4))(*args)
    want = jax.grad(final(reference.recurrence), (0, 1, 2, 3, 4))(*args)
    assert float(jnp.abs(got[0]).max()) == 0.0      # the state never reads q
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("cut", [16, 29])
def test_a_sequence_cut_in_two_with_the_state_carried_equals_the_whole(cut):
    args = rule_inputs(seed=3, t=64)
    whole, final = gated_delta_rule(*args, chunk=16, return_final_state=True)
    head, state = gated_delta_rule(
        *(a[:, :cut] for a in args), chunk=16, return_final_state=True
    )
    tail, last = gated_delta_rule(
        *(a[:, cut:] for a in args), chunk=16, initial_state=state,
        return_final_state=True,
    )
    np.testing.assert_allclose(jnp.concatenate([head, tail], 1), whole, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(last, final, rtol=2e-5, atol=2e-5)


def test_the_result_is_the_same_for_two_chunk_sizes():
    args = rule_inputs(seed=4, t=128)
    a, state_a = gated_delta_rule(*args, chunk=16, return_final_state=True)
    b, state_b = gated_delta_rule(*args, chunk=64, return_final_state=True)
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(state_a, state_b, rtol=2e-5, atol=2e-5)


def test_rule_in_bfloat16_keeps_its_decays_inverse_and_state_in_float32():
    args = rule_inputs(seed=5, t=256, d_k=32, d_v=64)
    low = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    got, state = jax.jit(
        lambda *a: gated_delta_rule(*a, chunk=64, return_final_state=True)
    )(*low)
    assert got.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    want, want_state = reference.recurrence(
        *(a.astype(jnp.float32) for a in low)
    )
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 0.02 * scale
    assert float(jnp.max(jnp.abs(state - want_state))) < 0.02 * float(
        jnp.max(jnp.abs(want_state))
    )


@pytest.mark.parametrize("size", [1, 2, 16, 64])
def test_the_doubling_inverts_a_unit_lower_triangular_system(size):
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(size), (3, size, size)), -1)
    got = rule_module.unit_lower_inverse(a)
    np.testing.assert_allclose(
        got, np.linalg.inv(np.eye(size) + np.asarray(a, np.float64)),
        rtol=1e-4, atol=1e-4,
    )


def test_keys_that_repeat_inside_a_chunk_cost_no_precision():
    """One key a whole chunk long at beta near 2: the system's entries are
    near 2 everywhere under the diagonal, and a solve by powers of it would
    cancel numbers of 1e18."""
    q, k, v, g, beta = rule_inputs(seed=6, t=64, log_decay=(-1e-3, -1e-5),
                                   beta=(1.9, 2.0))
    k = jnp.broadcast_to(k[:, :1], k.shape)
    want_o, want_state = reference.recurrence(q, k, v, g, beta)
    got_o, got_state = gated_delta_rule(q, k, v, g, beta, chunk=64,
                                        return_final_state=True)
    np.testing.assert_allclose(got_o, want_o, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got_state, want_state, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("fault", ["chunk", "k", "g", "v"])
def test_the_rule_refuses_what_it_cannot_compute(fault):
    q, k, v, g, beta = rule_inputs(t=16)
    with pytest.raises(ValueError, match="gated_delta_rule"):
        if fault == "chunk":
            gated_delta_rule(q, k, v, g, beta, chunk=48)
        elif fault == "k":
            gated_delta_rule(q, k[..., :4], v, g, beta)
        elif fault == "g":
            gated_delta_rule(q, k, v, g[:, :8], beta)
        else:
            gated_delta_rule(q, k, v[:, :8], g, beta)


def test_each_traced_shape_leaves_one_gdn_chunks_instant():
    obs_trace.get_tracer().reset_notes()
    tracer = obs_trace.get_tracer()
    before = len([e for e in tracer.to_events() if e["name"] == "gdn_chunks"])
    args = rule_inputs(t=40)
    for _ in range(2):                              # the second finds it noted
        gated_delta_rule(*args, chunk=16)
    noted = [e for e in tracer.to_events() if e["name"] == "gdn_chunks"][before:]
    assert len(noted) == 1
    # batch 2, float32 operands: the states three chunks inherit and the final
    # one, three chunks' T, then V_new and o over the 48 padded steps
    saved = 2 * (4 * (3 + 1) * 3 * 8 * 16 + 4 * 3 * 3 * 16 * 16 + 2 * 4 * 48 * 3 * 16)
    assert noted[0]["args"] == {
        "chunk": 16, "chunks": 3, "heads": 3, "d_k": 8, "d_v": 16,
        "state_bytes": 4 * 3 * 8 * 16, "solve": "block_doubling",
        "carry": "saved", "saved_bytes": saved, "path": "plain", "why": "backend",
    }
    # the cell's call: 128 states of [15, 96, 192] and 128 T of [15, 64, 64]
    # float32, V_new and o bfloat16
    assert rule_module.saved_bytes(64, 128, 15, 96, 192, 2) == (
        129 * 15 * 96 * 192 * 4 + 128 * 15 * 64 * 64 * 4 + 2 * 8192 * 15 * 192 * 2
    )


# -- the scalar rule's carry as the walk's kernels, in the Pallas interpreter --------

# heads, d_k = d_v / 2 ... and the steps: what the chunk-local stage and the
# carry each take, and how the carry's operands lie
WALKS = {
    # the cell's class: both by the kernels, a tile a head as gdn_operands wrote them
    "the_cells_class": (3, 96, 128, True, ("kernel", None, "kernel", None, "tiles")),
    # three chunks are no whole lane tiles of steps: the chunk-local stage is
    # plain, and rows of whole lane tiles a head are still the walk's
    "three_chunks_of_lane_tiles": (2, 128, 192, True, ("plain", "steps", "kernel", None, "rows")),
    # ... and rows of heads of 96 are not
    "three_chunks_of_96": (3, 96, 192, True, ("plain", "steps", "plain", "width", "rows")),
    "a_length_the_chunk_pads": (2, 128, 150, True, ("plain", "steps", "kernel", None, "rows")),
    "no_tpu_and_no_interpreter": (3, 96, 128, False, ("plain", "backend", "plain", "backend", "rows")),
}


def walk_inputs(case):
    h, d, t = WALKS[case][:3]
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    unit = lambda m: m / jnp.linalg.norm(m, axis=-1, keepdims=True)  # noqa: E731
    bf16 = jnp.bfloat16
    q = (unit(jax.random.normal(keys[0], (1, t, h, d))) * d ** -0.5).astype(bf16)
    k = unit(jax.random.normal(keys[1], (1, t, h, d))).astype(bf16)
    v = jax.random.normal(keys[2], (1, t, h, 2 * d)).astype(bf16)
    g = -jax.nn.softplus(jax.random.normal(keys[3], (1, t, h)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (1, t, h)))
    return (q, k, v, g, beta), 0.1 * jax.random.normal(keys[5], (1, h, d, 2 * d))


@pytest.mark.parametrize("case", list(WALKS))
def test_the_scalar_rules_carry_says_which_form_it_took(case):
    h, d, t, interpret, (local, local_why, carry, carry_why, operands) = WALKS[case]
    args, _ = walk_inputs(case)
    tracer = obs_trace.get_tracer()
    tracer.reset_notes()
    seen = lambda name: [e["args"] for e in tracer.to_events() if e["name"] == name]  # noqa: E731
    before = {name: len(seen(name)) for name in ("gdn_chunks", "delta_carry")}
    jax.eval_shape(lambda *a: gated_delta_rule(*a, chunk=64, interpret=interpret), *args)
    (chunks,), (walk,) = (seen(name)[before[name]:] for name in ("gdn_chunks", "delta_carry"))
    assert (chunks["path"], chunks.get("why")) == (local, local_why) and chunks["carry"] == "saved"
    assert (walk["path"], walk.get("why")) == (carry, carry_why)
    assert walk["decay"] == "head" and walk["operands"] == operands
    assert (walk["heads_a_step"], walk["state_bytes"]) == (h, 4 * h * d * 2 * d)
    assert walk["chunks"] == -(-t // 64)


@functools.lru_cache(maxsize=None)
def walk_both_ways(case):
    args, state = walk_inputs(case)

    def program(interpret):
        def both(state, *args):
            values, pull = jax.vjp(lambda state, *a: gated_delta_rule(
                *a, chunk=64, initial_state=state, return_final_state=True, interpret=interpret
            ), state, *args)
            keys = jax.random.split(jax.random.PRNGKey(12), 2)
            return values, pull(tuple(
                jax.random.normal(key, a.shape).astype(a.dtype) for key, a in zip(keys, values)
            ))

        return jax.jit(both)

    return [program(interpret)(state, *args) for interpret in (True, False)]


@pytest.mark.parametrize("case", ["three_chunks_of_lane_tiles", "a_length_the_chunk_pads"])
@pytest.mark.parametrize(
    "what", ["o", "state", "initial_state", "q", "k", "v", "g", "beta"]
)
def test_the_walk_after_a_plain_chunk_local_stage_is_the_plain_rule(what, case):
    """A decay a head over rows whose heads lie side by side: the scalar rule
    where only the carry is the kernels' (the cell's own case, both stages by
    the kernels, is ``tests/test_gdn_kernels.py``'s)."""
    (got, got_grads), (want, want_grads) = walk_both_ways(case)
    names = ["initial_state", "q", "k", "v", "g", "beta"]
    if what in ("o", "state"):
        a, b = got[what == "state"], want[what == "state"]
    else:
        a, b = got_grads[names.index(what)], want_grads[names.index(what)]
    assert a.shape == b.shape and a.dtype == b.dtype
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert np.isfinite(a).all() and np.abs(a - b).max() <= 2e-2 * np.abs(b).max()


# -- the convolution's no-bias call ----------------------------------------------


@pytest.mark.parametrize("what", ["value", "dx", "dkernel"])
def test_causal_conv_silu_without_a_bias_at_offset_zero_in_the_kernels(what):
    """The mixer's call: no bias, the convolution's channels the LEADING ones
    of a wider array (offset 0), so the kernels' blocks are the gcd of the
    channels and nothing."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (1, 256, 96 + 32), jnp.float32)
    kernel = jax.random.uniform(keys[1], (4, 96), jnp.float32, -0.5, 0.5)
    w = jax.random.normal(keys[2], (1, 256, 96))
    ran = lambda fn: lambda x, k: jnp.sum(fn(x, k) * w)  # noqa: E731
    kernels = lambda x, k: causal_conv_silu(x, k, None, offset=0, interpret=True)  # noqa: E731
    plain = lambda x, k: conv_plain(x, k, None, 0)  # noqa: E731
    if what == "value":
        np.testing.assert_allclose(kernels(x, kernel), plain(x, kernel), rtol=1e-5, atol=1e-5)
        return
    wrt = 0 if what == "dx" else 1
    got = jax.grad(ran(kernels), wrt)(x, kernel)
    want = jax.grad(ran(plain), wrt)(x, kernel)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    if what == "dx":
        assert float(jnp.abs(got[..., 96:]).max()) == 0.0   # beside the convolution


# -- the mixer -------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def toy_mixer():
    mixer = GatedDeltaMixer(SPEC, jnp.float32, 1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 48))
    return mixer, shaken(jax.jit(mixer.init)(jax.random.PRNGKey(1), x)["params"]), x


@functools.lru_cache(maxsize=None)
def mixer_both_ways():
    """``(value, gradients)`` of the mixer and of the sequential reference
    under one cotangent, once for the cases that each look at one leaf."""
    mixer, params, x = toy_mixer()
    w = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    return [
        value_and_gradients(fn, params, weight=w, argnums=0)
        for fn in (
            lambda p: mixer.apply({"params": p}, x),
            lambda p: reference.linear_attention_mixer(TOY, p, x),
        )
    ]


def test_mixer_equals_the_sequential_reference():
    mixer, params, x = toy_mixer()
    assert params["in_proj"]["kernel"].shape == (48, 24 + 24 + 48 + 48 + 3 + 3)
    assert params["conv_kernel"].shape == (4, 96) and "conv_bias" not in params
    assert params["norm"].shape == (16,) and params["out_proj"]["kernel"].shape == (48, 48)
    (got, _), (want, _) = mixer_both_ways()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


MIXER_LEAVES = ["in_proj", "conv_kernel", "A_log", "dt_bias", "norm", "out_proj"]


@pytest.mark.parametrize("leaf", MIXER_LEAVES)
def test_mixer_gradient_equals_the_references(leaf):
    (_, got), (_, want) = mixer_both_ways()
    for a, b in zip(jax.tree.leaves(got[leaf]), jax.tree.leaves(want[leaf])):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)


def test_two_chips_shares_of_the_heads_add_up_to_the_whole_layer():
    """The cell holds 15 of a layer's 30 heads: the share tied to the model.
    A head's q, k, v, gate, beta, decay and state are its own, so the layer's
    output is the sum over heads of what each adds through its rows of W_o:
    two chips' shares of the heads (their columns of the in projection and of
    the convolution, their A_log and dt_bias, their rows of W_o, the one norm
    scale whole) add up to the uncut reference's layer."""
    spec = GatedDeltaSpec(num_heads=4, key_dim=8, value_dim=16, chunk=8)
    mixer = GatedDeltaMixer(spec, jnp.float32, 1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 48))
    params = shaken(jax.jit(mixer.init)(jax.random.PRNGKey(1), x)["params"])
    whole = dict(TOY, linear_num_key_heads=4, linear_num_value_heads=4)
    want = jax.jit(lambda p: reference.linear_attention_mixer(whole, p, x))(params)

    def share(first, count):
        heads = np.arange(first, first + count)
        cols = lambda start, width: (  # noqa: E731 — the heads' columns of a segment
            start + (heads[:, None] * width + np.arange(width)[None]).ravel()
        )
        q, k, v = cols(0, 8), cols(32, 8), cols(64, 16)
        gate, b, a = cols(128, 16), 192 + heads, 196 + heads
        conv = np.concatenate([q, k, v])
        return {
            "in_proj": {"kernel": params["in_proj"]["kernel"][
                :, np.concatenate([q, k, v, gate, b, a])
            ]},
            "conv_kernel": params["conv_kernel"][:, conv],
            "A_log": params["A_log"][heads], "dt_bias": params["dt_bias"][heads],
            "norm": params["norm"],
            "out_proj": {"kernel": params["out_proj"]["kernel"][cols(0, 16)]},
        }

    half = dict(TOY, linear_num_key_heads=2, linear_num_value_heads=2)
    halved = jax.jit(lambda p: reference.linear_attention_mixer(half, p, x))
    parts = [halved(share(first, 2)) for first in (0, 2)]
    np.testing.assert_allclose(parts[0] + parts[1], want, rtol=2e-5, atol=2e-5)
    assert float(jnp.max(jnp.abs(parts[0] - want))) > 0.1     # and neither is the whole
    # the program's mixer on a share is the reference's on it
    held = GatedDeltaMixer(GatedDeltaSpec(2, 8, 16, chunk=8), jnp.float32, 1e-6)
    np.testing.assert_allclose(
        jax.jit(held.apply)({"params": share(0, 2)}, x), parts[0], rtol=2e-4, atol=2e-5
    )


def test_mixer_initialises_as_its_source_does():
    mixer = GatedDeltaMixer(GatedDeltaSpec(64, 8, 16), jnp.float32)
    params = jax.jit(mixer.init)(jax.random.PRNGKey(0), jnp.zeros((1, 8, 32)))["params"]
    rate = np.exp(np.asarray(params["A_log"]))
    assert 0 < rate.min() < 2 and 14 < rate.max() <= 16          # U(0, 16)
    step = np.log1p(np.exp(np.asarray(params["dt_bias"])))       # softplus
    assert 1e-3 <= step.min() < 3e-3 and 3e-2 < step.max() <= 1e-1
    conv = np.asarray(params["conv_kernel"])
    assert -0.5 <= conv.min() < -0.45 and 0.45 < conv.max() <= 0.5
    np.testing.assert_array_equal(params["norm"], np.ones(16))


def test_without_neg_eigval_beta_stays_under_one():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 48)) * 4.0
    for neg, top in ((True, 2.0), (False, 1.0)):
        spec = GatedDeltaSpec(3, 8, 16, chunk=8, neg_eigval=neg)
        mixer = GatedDeltaMixer(spec, jnp.float32)
        params = shaken(jax.jit(mixer.init)(jax.random.PRNGKey(1), x)["params"])
        _, sown = jax.jit(
            lambda p, x, mixer=mixer: mixer.apply({"params": p}, x, mutable=["intermediates"])
        )(params, x)
        beta = sown["intermediates"]["rule_inputs"][0][4]
        assert top / 2 < float(beta.max()) < top and float(beta.min()) > 0


# -- the model -------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def lm_and_reference(remat):
    """``(loss, logits, gradients)`` of the toy LM and of the plain reference
    at one batch, once for the three cases that each look at one of them."""
    lm = toy_lm(remat=remat)
    x, y = toy_batch()
    params = shaken(jax.jit(lm.init)(jax.random.PRNGKey(3), x)["params"])
    assert set(params["layer_0"]) == {"gdn", "mlp", "ln1_post", "ln2_post"}
    assert set(params["layer_1"]) == {"attn", "mlp", "ln1_post", "ln2_post"}
    assert params["layer_1"]["attn"]["q_norm"]["scale"].shape == (48,)

    def program(p):
        logits = lm.apply({"params": p}, x)
        return lm_loss(logits, y)[0], logits

    def plain(p):
        logits = reference.forward(TOY, p, x)
        return reference.loss(logits, y), logits

    return [loss_logits_gradients(fn, params) for fn in (program, plain)]


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("what", ["logits", "loss", "gradients"])
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
            a, b, rtol=1e-3, atol=1e-5, err_msg=jax.tree_util.keystr(path)
        )


def lowered_gradient(lm):
    x, y = toy_batch()
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), x)["params"])
    loss = lambda p: lm_loss(lm.apply({"params": p}, x), y)[0]  # noqa: E731
    return jax.jit(jax.grad(loss)).lower(params).as_text()


@pytest.mark.parametrize("policy, loops, solves", [
    ("save_flash", 2, 1), ("save_flash_qkv", 2, 1), ("full", 3, 2),
])
def test_a_linear_layers_carry_loops_once_forward_and_once_in_reverse(policy, loops, solves):
    """Two remat blocks around the rule: with the rule's names in the block's
    policy the gradient holds a forward and a reverse loop a layer and one
    solve (chunks of 8: three rounds of two products at the highest
    precision, and two more in the inverse's backward); with nothing saved the
    block's recomputation runs the forward loop and the solve again."""
    arch = toy_arch(layer_types=("linear_attention",) * 2)
    text = lowered_gradient(toy_lm(arch, remat=True).clone(remat_policy=policy))
    assert len(re.findall(r"stablehlo\.while", text)) == loops * 2
    exact = len(re.findall(r"precision = \[HIGHEST, HIGHEST\]", text))
    assert exact == (6 * solves + 2) * 2


def test_the_carrys_names_in_the_policy_leave_a_dense_lm_as_it_was(monkeypatch):
    dense = toy_lm(toy_arch(layer_types=("attention",) * 2, gated_delta=None), remat=True)
    dense = dense.clone(remat_policy="save_flash")
    with_names = lowered_gradient(dense)
    monkeypatch.setattr(
        transformer_module, "_remat_policy",
        lambda name: jax.checkpoint_policies.save_only_these_names("flash_out", "flash_lse"),
    )
    assert lowered_gradient(dense) == with_names


PLACEMENTS = {
    False: ({"ln1", "ln2"}, lambda n1, n2, p1, p2, mix, ff, x: (
        lambda h: h + ff(n2(h)))(x + mix(n1(x)))),
    True: ({"ln1", "ln2", "ln1_post", "ln2_post"}, lambda n1, n2, p1, p2, mix, ff, x: (
        lambda h: h + p2(ff(n2(h))))(x + p1(mix(n1(x))))),
    "only": ({"ln1_post", "ln2_post"}, lambda n1, n2, p1, p2, mix, ff, x: (
        lambda h: h + p2(ff(h)))(x + p1(mix(x)))),
}


@pytest.mark.parametrize("placement", PLACEMENTS, ids=["before", "before_and_after", "after"])
def test_a_blocks_norms_sit_where_the_one_field_says(placement):
    names, equation = PLACEMENTS[placement]
    arch = toy_arch(post_norms=placement)
    block = Block(4, 40, jnp.float32, arch=arch, mixer="linear_attention")
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 48)) * 3.0
    positions = jnp.arange(24)[None]
    params = shaken(jax.jit(block.init)(jax.random.PRNGKey(1), x, positions)["params"])
    assert {k for k in params if k.startswith("ln")} == names
    norm = lambda name: lambda v: reference._rms_norm(  # noqa: E731
        v, params[name]["scale"], 1e-6
    ) if name in params else None
    mlp = params["mlp"]
    want = equation(
        norm("ln1"), norm("ln2"), norm("ln1_post"), norm("ln2_post"),
        lambda v: reference.linear_attention_mixer(TOY, params["gdn"], v),
        lambda v: (jax.nn.silu(v @ mlp["gate"]["kernel"]) * (v @ mlp["up"]["kernel"]))
        @ mlp["down"]["kernel"],
        x,
    )
    np.testing.assert_allclose(
        block.apply({"params": params}, x, positions), want, rtol=2e-4, atol=2e-4
    )


def test_an_unknown_placement_or_layer_type_is_refused_by_name():
    x, _ = toy_batch()
    with pytest.raises(ValueError, match="unknown post_norms 'after'"):
        toy_lm(toy_arch(post_norms="after")).init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="one of attention, sliding_attention, mamba, "
                                         "linear_attention"):
        toy_lm(toy_arch(layer_types=("linear_attention", "delta", "attention"))).init(
            jax.random.PRNGKey(0), x
        )


@pytest.mark.parametrize("tokens", [1, 5], ids=["step", "prefill"])
def test_a_decode_call_on_a_linear_attention_block_raises(tokens):
    lm = toy_lm().clone(decode=True, max_decode_len=16)
    with pytest.raises(NotImplementedError, match="gated-delta-rule block has no decode"):
        lm.init(jax.random.PRNGKey(0), np.zeros((1, tokens), np.int32))


def test_the_hybrid_trains_through_the_step_and_exports_its_three_gauges():
    lm = toy_lm(dtype=jnp.bfloat16, remat=True)
    x, y = toy_batch(b=1)
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.adamw(1e-2))
    assert set(state.sown) == {"gdn_decay_mean", "gdn_beta_mean", "gdn_state_absmax"}
    step = make_train_step(lm_loss, numerics=True, donate=False)
    first = None
    for _ in range(8):
        state, metrics = step(state, (x, y))
        first = first if first is not None else float(metrics["loss"])
    assert float(metrics["loss"]) < first and np.isfinite(float(metrics["loss"]))
    assert 0.0 < float(metrics["gdn_decay_mean"]) < 1.0
    assert 0.0 < float(metrics["gdn_beta_mean"]) < 2.0
    assert 0.0 < float(metrics["gdn_state_absmax"]) < 1e3
    assert set(metrics["_numerics"]["sown"]) == set(state.sown)
    from edl_tpu.obs import metrics as obs_metrics
    from edl_tpu.obs import numerics as obs_numerics

    obs_numerics.publish_sown({k: np.asarray(metrics[k]) for k in state.sown})
    text = obs_metrics.default_registry().render()
    for name in state.sown:
        assert "edl_train_%s " % name in text


@functools.lru_cache(maxsize=None)
def compiled_steps_scopes():
    lm = toy_lm(dtype=jnp.bfloat16, remat=True)
    x, y = toy_batch(b=1)
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.adamw(1e-3))
    compiled = make_train_step(lm_loss, numerics=False).lower(state, (x, y)).compile()
    return set(obs_profile.scopes_of_hlo(compiled.as_text(), GDN_SCOPES).values())


@pytest.mark.parametrize("scope", GDN_SCOPES)
def test_the_compiled_step_names_the_mixers_scopes(scope):
    assert scope in compiled_steps_scopes()
