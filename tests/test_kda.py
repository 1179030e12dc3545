"""Ling-3.0-flash's three mechanisms at toy sizes on the CPU (the whole model is
in ``tests/test_kda_model.py``): the chunked Kimi
delta rule (a decay for every key channel) against the step-by-step recurrence,
the grid-pipelined flash kernels at two widths (latent attention trains as keys
of 192 and values of 128) against dense float32 attention, the grouped choice of
experts against a plain top-k of groups; then the mixers, the shares of an expert
layer and the whole model against ``benchmark/reference/kda_lm.py``, which
imports nothing from ``edl_tpu.models``.
"""

import functools
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import value_and_gradients

from benchmark.families import kda_lm as family
from benchmark.reference import kda_lm as reference
from edl_tpu.models import (
    KimiDeltaMixer,
    LatentAttention,
    MoESpec,
)
from edl_tpu.models.moe import DroplessMoE
from edl_tpu.obs import trace as obs_trace
from edl_tpu.ops import gated_delta_rule, kda_rule

A = importlib.import_module("edl_tpu.ops.attention")
G = importlib.import_module("edl_tpu.ops.gated_delta")
T = importlib.import_module("edl_tpu.models.transformer")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs", "ling_3_0_flash_vl.json")) as f:
    TOY = json.load(f)
D = TOY["hidden_size"]


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    scale = max(np.max(np.abs(want)), 1e-12)
    assert np.max(np.abs(got - want)) / scale <= tol


def shaken(params, seed=7):
    """Every vector (a norm's scale, a decay's bias) off its start, so that a
    misplaced one shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 400))
    return jax.tree.map(
        lambda a: a * (1 + 0.2 * jax.random.normal(next(keys), a.shape)) if a.ndim <= 2 and a.size < 4096 else a,
        params,
    )


# -- the rule --------------------------------------------------------------------


def rule_inputs(seed=0, b=2, t=96, h=3, d_k=8, d_v=16, low=-5.0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda m: m / jnp.linalg.norm(m, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (b, t, h, d_k))) * d_k ** -0.5
    k = unit(jax.random.normal(keys[1], (b, t, h, d_k)))
    v = jax.random.normal(keys[2], (b, t, h, d_v))
    g = low * jax.nn.sigmoid(2.0 * jax.random.normal(keys[3], (b, t, h, d_k)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, h)))
    return q, k, v, g, beta


def at_the_bound(args):
    """Steps 16..63 of every channel at the safe gate's bound: forty-eight
    steps running of e^-5 each."""
    q, k, v, g, beta = args
    steps = (jnp.arange(g.shape[1]) >= 16) & (jnp.arange(g.shape[1]) < 64)
    return q, k, v, jnp.where(steps[None, :, None, None], -5.0 + 1e-4, g), beta


def repeated_keys(args):
    """One key for every step of a head: the system's entries near beta."""
    q, k, v, g, beta = args
    return q, jnp.broadcast_to(k[:, :1], k.shape), v, g, beta


CASES = {"drawn": lambda a: a, "at_the_bound": at_the_bound, "repeated_keys": repeated_keys}
chunked = lambda *a: kda_rule(*a, chunk=32, return_final_state=True)  # noqa: E731


def _value_and_five_gradients(rule):
    """One jitted program for every case (they differ in values alone): ``(o,
    state)`` and the gradients of a weight on both for the five operands."""

    def weighed(*a):  # the output and the state both
        o, state = rule(*a)
        return jnp.sum(jnp.sin(o)) + jnp.sum(state ** 2), (o, state)

    return jax.jit(jax.value_and_grad(weighed, argnums=range(5), has_aux=True))


BOTH_RULES = (_value_and_five_gradients(chunked), _value_and_five_gradients(reference.recurrence))


@functools.lru_cache(maxsize=None)
def chunked_and_recurrence(case):
    args = CASES[case](rule_inputs())
    with jax.default_matmul_precision("highest"):
        return [(values, grads) for (_, values), grads in (run(*args) for run in BOTH_RULES)]


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("what", ["value", "q", "k", "v", "g", "beta"])
def test_the_chunked_rule_equals_the_step_by_step_recurrence(case, what):
    ((o, state), got), ((want_o, want_state), want) = chunked_and_recurrence(case)
    if what == "value":
        assert o.shape == (2, 96, 3, 16) and state.shape == (2, 3, 8, 16)
        _close(o, want_o, tol=1e-4)
        _close(state, want_state, tol=1e-4)
        return
    leaf = ("q", "k", "v", "g", "beta").index(what)
    # at the bound the terms that survive are e^-5 and less of the rest's
    _close(got[leaf], want[leaf], tol=2e-3 if case == "at_the_bound" else 2e-4)


@pytest.mark.parametrize("chunk", [16, 64, 32, 8])
def test_the_result_does_not_depend_on_the_chunk(chunk):
    args = rule_inputs(seed=1, t=70)  # a length no chunk divides: padded steps leave the state
    with jax.default_matmul_precision("highest"):
        o, state = jax.jit(lambda *a: kda_rule(*a, chunk=chunk, return_final_state=True))(*args)
        want_o, want_state = BOTH_RULES[1](*args)[0][1]
    _close(o, want_o, tol=1e-4)
    _close(state, want_state, tol=1e-4)


def test_with_every_channels_decay_equal_it_is_the_scalar_rule():
    q, k, v, g, beta = rule_inputs(seed=2)
    one = g[..., :1]
    with jax.default_matmul_precision("highest"):
        got = kda_rule(q, k, v, jnp.broadcast_to(one, g.shape), beta, chunk=32)
        want = gated_delta_rule(q, k, v, one[..., 0], beta, chunk=32)
    _close(got, want, tol=1e-5)


def test_the_rule_carries_an_initial_state_and_rounds_its_operands_once():
    q, k, v, g, beta = rule_inputs(seed=3)
    with jax.default_matmul_precision("highest"):
        _, half = chunked(*(a[:, :64] for a in (q, k, v, g, beta)))
        rest = kda_rule(*(a[:, 64:] for a in (q, k, v, g, beta)), chunk=32, initial_state=half)
        whole = kda_rule(q, k, v, g, beta, chunk=32)
    _close(rest, whole[:, 64:], tol=1e-5)
    bf16 = lambda a: a.astype(jnp.bfloat16)  # noqa: E731
    # jitted: the CPU's op-by-op dot takes no bfloat16 operands
    low = jax.jit(lambda *a: kda_rule(*a, chunk=32))(bf16(q), bf16(k), bf16(v), g, beta)
    assert low.dtype == jnp.bfloat16
    _close(low.astype(jnp.float32), whole, tol=0.03)


@pytest.mark.parametrize("bad", ["g_shape", "beta_shape", "chunk", "v_shape"])
def test_the_rule_refuses_shapes_it_cannot_take(bad):
    q, k, v, g, beta = rule_inputs(t=32)
    kwargs = {}
    if bad == "g_shape":
        g = g[..., 0]
    elif bad == "beta_shape":
        beta = beta[..., None]
    elif bad == "chunk":
        kwargs["chunk"] = 24
    else:
        v = v[:, :-1]
    with pytest.raises(ValueError, match="kda_rule"):
        kda_rule(q, k, v, g, beta, **kwargs)


def test_the_scalar_rules_carry_is_what_it_was():
    """``_carry`` tells a decay a head from a decay a channel by its rank: the
    scalar rule's callers see the product they saw."""
    state = jnp.ones((1, 2, 4, 3))
    inputs = (jnp.zeros((1, 2, 5, 4)), jnp.zeros((1, 2, 5, 3)), jnp.zeros((1, 5, 2, 4)))
    a_head, _ = G._carry(state, (*inputs, jnp.full((1, 2), 0.5)))
    a_channel, _ = G._carry(state, (*inputs, jnp.full((1, 2, 4), 0.5).at[..., 0].set(0.25)))
    assert float(a_head[0, 0, 0, 0]) == 0.5 and float(a_channel[0, 0, 0, 0]) == 0.25
    assert float(a_channel[0, 0, 1, 0]) == 0.5


def test_each_traced_shape_leaves_one_kda_chunks_instant():
    obs_trace.get_tracer().reset_notes()
    tracer = obs_trace.get_tracer()
    before = len([e for e in tracer.to_events() if e["name"] == "kda_chunks"])
    args = rule_inputs(t=64)
    for _ in range(2):
        jax.jit(lambda *a: kda_rule(*a, chunk=32)).lower(*args)
    found = [e["args"] for e in tracer.to_events() if e["name"] == "kda_chunks"][before:]
    assert len(found) == 1
    assert (found[0]["chunk"], found[0]["pairs"], found[0]["heads"]) == (32, "halving", 3)
    assert "sub_block" not in found[0]
    assert (found[0]["d_k"], found[0]["d_v"]) == (8, 16)
    assert found[0]["state_bytes"] == 4 * 3 * 8 * 16
    assert found[0]["saved_bytes"] == G.saved_bytes(32, 2, 3, 8, 16, 4, 2)
    assert found[0]["path"] == "plain" and found[0]["solve"] == G.SOLVE


# -- the chunk-local stage's kernels, in the Pallas interpreter --------------------

OPERANDS = ("w", "u", "k_out", "whole", "q_in", "scores", "inverse")
LEAVES = ("q", "k", "v", "g", "beta")


def near_zero(args):
    """Channels that hardly forget: every factor of every level near one."""
    q, k, v, g, beta = args
    return q, k, v, 1e-3 * g, beta


def padded(args):
    """The last 40 steps as ``kda_rule`` pads a ragged length: zero rows,
    ``g = 0`` and ``beta = 0``, which leave the state as it is."""
    q, k, v, g, beta = args
    live = (jnp.arange(g.shape[1]) < g.shape[1] - 40)
    cut = lambda a: a * live.reshape((1, -1) + (1,) * (a.ndim - 2))  # noqa: E731
    return tuple(cut(a) for a in (q, k, v, g, beta))


KERNEL_CASES = {"drawn": lambda a: a, "at_the_bound": at_the_bound, "near_zero": near_zero,
                "padded": padded}


def kernel_inputs(case, dtype):
    """One sequence of two chunks, two heads of 128 / 128: the kernels' case."""
    q, k, v, g, beta = KERNEL_CASES[case](rule_inputs(seed=4, b=1, t=128, h=2, d_k=128, d_v=128))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def by_kernels(q, k, v, g, beta):
    """The six operands the rule reads by the kernels, in ``_local_plain``'s
    shapes."""
    b, t, h, d = q.shape
    flat = lambda a: a.reshape(b, t, -1)  # noqa: E731
    w, u, k_out, whole, q_in, scores = G._local_kernels(
        flat(q), flat(k), flat(v), flat(g), beta, True
    )
    return (w, u, k_out.reshape(t // 64, b, 64, h, d), whole,
            q_in.reshape(b, t // 64, 64, h, d), scores)


def inverse_by_kernels(q, k, v, g, beta):
    b, t = q.shape[:2]
    pairs = G._inverse_call(k.reshape(b, t, -1), g.reshape(b, t, -1), beta, True)
    # [b n h/2 c (2 s)]: a pair of heads side by side along the lanes
    apart = jnp.moveaxis(pairs.reshape(b, t // 64, -1, 64, 2, 64), 4, 3)
    return apart.reshape(b, t // 64, -1, 64, 64)


def weights(like, seed=20):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(like))
    return tuple(jax.random.normal(key, a.shape).astype(a.dtype) for key, a in zip(keys, like))


def _stage_program(stage, inverse):
    """One jitted program for every case of a dtype (the cases differ in
    values alone): the stage's seven operands and its five gradients."""

    @jax.jit
    def run(*args):
        values, pull = jax.vjp(lambda *x: stage(*x)[:6], *args)
        return values + (inverse(*args),), pull(weights(values))

    return run


_plain_stage = lambda *a: G._local_plain(*a, 64)  # noqa: E731
STAGES = (
    _stage_program(by_kernels, inverse_by_kernels),
    _stage_program(_plain_stage, lambda *a: _plain_stage(*a)[6]),
)


@functools.lru_cache(maxsize=None)
def stage_both_ways(case, dtype):
    """``(values, gradients)`` of the stage by the kernels and by the plain
    form on the same inputs: the gradients under one random cotangent of the
    six operands the rule reads (``T`` is the backward's own residual)."""
    args = kernel_inputs(case, jnp.dtype(dtype))
    with jax.default_matmul_precision("highest"):
        return [run(*args) for run in STAGES]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(KERNEL_CASES))
@pytest.mark.parametrize("what", OPERANDS + LEAVES)
def test_the_kernels_are_the_plain_chunk_local_stage(what, case, dtype):
    """Float32 operands (which only the tests hand the kernels) hold the
    arithmetic to the plain form's; bfloat16 the points of rounding: an operand
    rounded at another point reads a bfloat16 step, 4e-3, or more off."""
    (got, got_grads), (want, want_grads) = stage_both_ways(case, dtype)
    if what in OPERANDS:
        a, b = got[OPERANDS.index(what)], want[OPERANDS.index(what)]
    else:
        a, b = got_grads[LEAVES.index(what)], want_grads[LEAVES.index(what)]
    assert a.shape == b.shape and a.dtype == b.dtype
    # at the bound the terms that survive are e^-5 and less of the rest's
    exact = 2e-3 if case == "at_the_bound" else 1e-4
    _close(a, b, tol=exact if dtype == "float32" else 1.5e-2)


bf16_args = lambda args: tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]  # noqa: E731


def _rule_program(interpret):
    """One jitted program for every case (they differ in values alone)."""

    @jax.jit
    def run(state, *args):
        values, pull = jax.vjp(lambda *a: kda_rule(
            *a, chunk=64, initial_state=state, return_final_state=True, interpret=interpret
        ), *args)
        return values, pull(weights(values, seed=21))

    return run


RULES = (_rule_program(True), _rule_program(False))


@functools.lru_cache(maxsize=None)
def rule_both_ways(case):
    """``kda_rule`` on bfloat16 operands from an initial state, ``(o, final
    state)`` and the five gradients under a random cotangent of both, by the
    kernels (in the interpreter) and by the plain form."""
    args = bf16_args(KERNEL_CASES[case](rule_inputs(seed=5, b=1, t=192, h=2, d_k=128, d_v=128)))
    state = 0.1 * jax.random.normal(jax.random.PRNGKey(6), (1, 2, 128, 128))
    return [run(state, *args) for run in RULES]


@pytest.mark.parametrize("case", ["drawn", "at_the_bound", "padded"])
@pytest.mark.parametrize("what", ["o", "state"] + list(LEAVES))
def test_the_rule_by_the_kernels_is_the_rule_by_the_plain_form(what, case):
    (got, got_grads), (want, want_grads) = rule_both_ways(case)
    if what in ("o", "state"):
        a, b = got[what == "state"], want[what == "state"]
    else:
        a, b = got_grads[LEAVES.index(what)], want_grads[LEAVES.index(what)]
    assert a.shape == b.shape and a.dtype == b.dtype
    _close(a.astype(jnp.float32), b.astype(jnp.float32), tol=2e-2)


_rule_from_zeros = jax.jit(
    lambda *a: kda_rule(*a, chunk=64, return_final_state=True, interpret=True)
)


@pytest.mark.parametrize("case", ["drawn", "at_the_bound"])
def test_the_rule_by_the_kernels_equals_the_step_by_step_recurrence(case):
    """Within the limit the plain form's bfloat16 call is held to above."""
    args = bf16_args(KERNEL_CASES[case](rule_inputs(seed=3, b=1, t=128, h=2, d_k=128, d_v=128)))
    o, state = _rule_from_zeros(*args)
    with jax.default_matmul_precision("highest"):
        want_o, want_state = reference.recurrence(*(a.astype(jnp.float32) for a in args))
    assert o.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    _close(o.astype(jnp.float32), want_o, tol=0.03)
    _close(state, want_state, tol=0.03)


@pytest.mark.parametrize("why,path", [
    ("the_kernels_case", "kernel"), ("no_tpu_and_no_interpreter", "plain"),
    ("float32_operands", "plain"), ("a_ragged_length", "plain"), ("a_chunk_of_32", "plain"),
    ("a_width_of_64", "plain"), ("three_heads", "plain"),
])
def test_which_form_runs_is_decided_from_the_operands_and_says_so(why, path):
    """``kda_chunks`` carries ``path``; the CPU's default, float32 operands (the
    benchmark check's exact call), a length the chunk does not divide, another
    chunk, a width under a lane tile and an odd count of heads take the plain form."""
    t, d, h, chunk, interpret, narrow = 128, 128, 2, 64, True, bf16_args
    if why == "no_tpu_and_no_interpreter":
        interpret = False
    elif why == "float32_operands":
        narrow = lambda args: args  # noqa: E731
    elif why == "a_ragged_length":
        t = 100
    elif why == "a_chunk_of_32":
        chunk = 32
    elif why == "a_width_of_64":
        d = 64
    elif why == "three_heads":
        h = 3
    args = narrow(rule_inputs(seed=8, b=1, t=t, h=h, d_k=d, d_v=d))
    obs_trace.get_tracer().reset_notes()
    tracer = obs_trace.get_tracer()
    before = len([e for e in tracer.to_events() if e["name"] == "kda_chunks"])
    lowered = jax.jit(lambda *a: kda_rule(*a, chunk=chunk, interpret=interpret)).lower(*args)
    found = [e["args"] for e in tracer.to_events() if e["name"] == "kda_chunks"][before:]
    assert [e["path"] for e in found] == [path]
    assert ("kda_operands" in lowered.as_text(debug_info=True)) == (path == "kernel")


# -- the carry and the output stage as one walk, in the Pallas interpreter -----------

CARRIED = ("o", "state")
CARRY_LEAVES = ("initial_state", "w", "u", "k_out", "whole", "q_in", "scores")
# heads, d_k, d_v, a decay a key channel, a tile a head, an initial state, the steps kept
CARRY_CASES = {
    "a_decay_a_channel": (2, 128, 128, True, False, True, 128),
    "a_decay_a_head": (2, 128, 128, False, False, False, 128),
    "five_heads_and_a_padded_length": (5, 128, 128, True, False, True, 3 * 64 - 24),
    "three_tiles_of_96_and_192": (3, 96, 192, False, True, True, 128),
    "five_tiles_a_channel_and_a_padded_length": (5, 96, 192, True, True, False, 3 * 64 - 40),
    # more than the walk takes by number: two rounds of 8 and two after them
    "eighteen_heads": (18, 128, 128, True, False, False, 128),
}


def carry_operands(case):
    """``(initial_state, w, u, k_out, whole, q_in, scores)`` as
    ``_carried_outputs`` takes them, of the sizes a chunk-local stage leaves."""
    h, d_k, d_v, channel, tiles, initial, t = CARRY_CASES[case]
    nc, b, size, bf16 = -(-t // 64), 1, 64, jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 7)
    rows = lambda key, shape, d: (jax.random.normal(key, shape) * d ** -0.5).astype(bf16)  # noqa: E731
    w = rows(keys[0], (nc, b, h, size, d_k), d_k)
    u = jax.random.normal(keys[1], (nc, b, h, size, d_v))
    k_out = rows(keys[2], (nc, b, size, h, d_k), d_k)
    whole = jax.random.uniform(keys[3], (nc, b, h, d_k) if channel else (nc, b, h), minval=0.3)
    q_in = rows(keys[4], (b, nc, size, h, d_k), d_k)
    scores = jnp.tril(0.2 * jax.random.normal(keys[5], (b, nc, h, size, size))).astype(bf16)
    state = jax.random.normal(keys[6], (b, h, d_k, d_v)) if initial else None
    if tiles:
        k_out, q_in = jnp.swapaxes(k_out, 2, 3), jnp.swapaxes(q_in, 2, 3)
    else:
        k_out, q_in = k_out.reshape(nc, b, size, -1), q_in.reshape(b, nc * size, -1)
    return state, w, u, k_out, whole, q_in, scores


@functools.lru_cache(maxsize=None)
def carry_both_ways(case):
    """``(o, final state)`` and the gradients of the operands (the initial
    state's where there is one) under a random cotangent of both, by the
    walk's two kernels and by the plain form (``carried_states`` and the two
    products), which the interpreter's flag alone tells apart. (One sequence:
    at a batch of two the CPU's own dot refuses the plain form's bfloat16
    operands beside a float32 result.)"""
    state, *operands = carry_operands(case)
    t = CARRY_CASES[case][-1]

    def program(interpret):
        def run(*args):
            leaves = args if state is not None else (None, *args)
            return G._carried_outputs(*leaves, t, interpret)

        def both(*args):
            values, pull = jax.vjp(run, *args)
            return values, pull(weights(values, seed=22))

        return jax.jit(both)

    args = operands if state is None else (state, *operands)
    return [program(interpret)(*args) for interpret in (True, False)]


@pytest.mark.parametrize("case,what", [
    (case, what) for case, fields in CARRY_CASES.items() for what in CARRIED + CARRY_LEAVES
    if fields[5] or what != "initial_state"
])
def test_the_walks_kernels_are_the_plain_carry_and_output_stage(case, what):
    """Values and every gradient: a decay a head and a decay a key channel,
    rows of heads side by side and a tile a head (96 / 192 wide), with and
    without an initial state, a length the chunk pads, odd counts of heads and
    more heads than a step takes by number. The state, ``u`` and every accumulation are
    float32 in both; the kernels round each product's operand once to bfloat16
    where the plain backward on a CPU keeps ``dS`` float32, a bfloat16 step."""
    (got, got_grads), (want, want_grads) = carry_both_ways(case)
    if what in CARRIED:
        a, b = got[CARRIED.index(what)], want[CARRIED.index(what)]
    else:
        at = CARRY_LEAVES.index(what) - (not CARRY_CASES[case][5])
        a, b = got_grads[at], want_grads[at]
    assert a.shape == b.shape and a.dtype == b.dtype
    _close(a.astype(jnp.float32), b.astype(jnp.float32), tol=1.5e-2)


@pytest.mark.parametrize("rule", ["kda_rule", "gated_delta_rule"])
def test_a_recomputation_that_saves_the_names_traces_no_second_walk(rule):
    """Under ``save_only_these_names(*REMAT_NAMES)``, as the mixers wrap the
    rule: the states the chunks inherit, ``V_new`` and the final state are
    saved by name and ``o`` is the rule's own output, so the gradient holds
    ``delta_carry`` once and ``delta_carry_back`` once."""
    h, d = (2, 128) if rule == "kda_rule" else (3, 32)
    q, k, v, g, beta = bf16_args(rule_inputs(seed=9, b=1, t=128, h=h, d_k=d, d_v=d))
    if rule == "gated_delta_rule":
        g = jnp.mean(g, axis=-1)
    saved = jax.checkpoint(
        functools.partial(getattr(G, rule), chunk=64, return_final_state=True, interpret=True),
        policy=jax.checkpoint_policies.save_only_these_names(*G.REMAT_NAMES),
    )

    def loss(*args):
        o, state = saved(*args)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(state)

    text = jax.jit(jax.grad(loss, range(5))).lower(q, k, v, g, beta).as_text(debug_info=True)
    # the interpreter leaves no custom call, but each call its jit by name
    calls = lambda name: len(re.findall(r"call @%s\b" % name, text))  # noqa: E731
    assert calls("_carry_call") == 1 and calls("_carry_back_call") == 1


@pytest.mark.parametrize("why,path", [
    ("the_kernels_case", "kernel"), ("three_heads", "kernel"), ("no_tpu_and_no_interpreter", "plain"),
    ("float32_operands", "plain"), ("a_chunk_of_32", "plain"), ("a_width_of_64", "plain"),
])
def test_the_carrys_form_is_decided_from_its_operands_and_says_so(why, path):
    """``delta_carry`` carries ``path`` and, where plain, ``why``; an odd count
    of heads, which the chunk-local kernels refuse, is the walk's case."""
    d, h, chunk, interpret, narrow = 128, 2, 64, True, bf16_args
    if why == "no_tpu_and_no_interpreter":
        interpret = False
    elif why == "float32_operands":
        narrow = lambda args: args  # noqa: E731
    elif why == "a_chunk_of_32":
        chunk = 32
    elif why == "a_width_of_64":
        d = 64
    elif why == "three_heads":
        h = 3
    args = narrow(rule_inputs(seed=8, b=1, t=128, h=h, d_k=d, d_v=d))
    tracer = obs_trace.get_tracer()
    tracer.reset_notes()
    before = len([e for e in tracer.to_events() if e["name"] == "delta_carry"])
    lowered = jax.jit(lambda *a: kda_rule(*a, chunk=chunk, interpret=interpret)).lower(*args)
    (found,) = [e["args"] for e in tracer.to_events() if e["name"] == "delta_carry"][before:]
    want = {"the_kernels_case": None, "three_heads": None, "no_tpu_and_no_interpreter": "backend",
            "float32_operands": "dtype", "a_chunk_of_32": "chunk", "a_width_of_64": "width"}[why]
    assert found["path"] == path and found.get("why") == want
    assert found["heads_a_step"] == h and found["state_bytes"] == 4 * h * d * d
    assert found["decay"] == "channel" and found["operands"] == "rows"
    assert ("_carry_call" in lowered.as_text(debug_info=True)) == (path == "kernel")


@pytest.mark.parametrize("heads,tiles,d,why", [
    (16, False, 128, None), (15, True, 96, None), (15, False, 96, "width"), (3, True, 24, "width"),
    (128, False, 128, "state"),
])
def test_the_walk_refuses_widths_that_do_not_tile_and_states_vmem_cannot_hold(heads, tiles, d, why):
    """Rows whose heads lie side by side want whole lane tiles a head, a tile a
    head a packed bfloat16 tile's 16 rows; 128 heads of 128 x 128 are 8 MB of
    float32 state, ten times over for the blocks that hold it."""
    sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype)  # noqa: E731
    w, u = sds((2, 1, heads, 64, d)), sds((2, 1, heads, 64, 2 * d), jnp.float32)
    k_out = sds((2, 1, heads, 64, d)) if tiles else sds((2, 1, 64, heads * d))
    q_in = sds((1, 2, heads, 64, d)) if tiles else sds((1, 128, heads * d))
    assert G._carry_refuses(w, u, k_out, q_in, sds((1, 2, heads, 64, 64)), True) == why


# -- the flash kernels at two widths ---------------------------------------------


def two_width_inputs(b=1, h=2, h_kv=2, t=256, d=48, d_v=32, seed=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (
        jax.random.normal(keys[0], (b, h, t, d)), jax.random.normal(keys[1], (b, h_kv, t, d)),
        jax.random.normal(keys[2], (b, h_kv, t, d_v)), jax.random.normal(keys[3], (b, h, t, d_v)),
    )


@functools.lru_cache(maxsize=None)
def two_widths_both_ways(fused, h_kv):
    """``(out, dq, dk, dv)`` by the kernels and by the dense form, once for
    the four cases that each look at one of them."""
    from unittest import mock

    q, k, v, w = two_width_inputs(h_kv=h_kv)
    scale = q.shape[-1] ** -0.5
    kernels = lambda q, k, v: A._auto(  # noqa: E731
        q, k, v, True, scale, (64, 128), (128, 64), None
    )
    dense = lambda q, k, v: A.attention_reference(q, k, v, causal=True, scale=scale)  # noqa: E731
    # not fused: a head whose dq no VMEM holds, so dq and dk/dv apart
    capacity = A._VMEM_V5E if fused else 0
    with mock.patch.object(A, "_vmem_capacity", lambda: capacity), mock.patch.object(
        A, "_flash2_bwd_kernel", wraps=A._flash2_bwd_kernel
    ) as fused_kernel:
        found = [
            value_and_gradients(fn, q, k, v, weight=w, argnums=(0, 1, 2))
            for fn in (kernels, dense)
        ]
    assert fused_kernel.called == fused
    return [(out, *grads) for out, grads in found], (q, k, v)


@pytest.mark.parametrize("h_kv", [2, 1], ids=["mha", "gqa"])
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "dq_and_dkv"])
@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
def test_flash2_at_two_widths_equals_dense_attention(what, fused, h_kv):
    (got, want), operands = two_widths_both_ways(fused, h_kv)
    index = ("out", "dq", "dk", "dv").index(what)
    assert got[0].shape == (1, 2, 256, 32)
    if index:
        assert got[index].shape == operands[index - 1].shape
    _close(got[index], want[index], tol=1e-5)


def test_a_two_width_call_through_the_public_name():
    """`flash_attention` at its own blocks (one a side here) takes values
    narrower than the keys."""
    q, k, v, _ = two_width_inputs()
    got = A.flash_attention(q, k, v, causal=True)
    assert got.shape == (1, 2, 256, 32)
    _close(got, A.attention_reference(q, k, v, causal=True), tol=1e-5)


# -- the grouped choice ------------------------------------------------------------

E, K, F, GROUPS, KEPT = 64, 8, 24, 8, 4
LAYER = dict(
    TOY, num_experts=E, num_experts_per_tok=K, moe_intermediate_size=F,
    moe_shared_expert_intermediate_size=F, n_group=GROUPS, topk_group=KEPT,
    share={"router_experts": E, "experts_first": 0},
)


def _layer(held, **overrides):
    fields = dict(
        num_experts=E, top_k=K, d_ff=F, norm_topk_prob=True, aux_weight=0.0,
        z_weight=0.0, score_func="sigmoid", route_scale=2.5, bias_rate=1e-3,
        shared_d_ff=F, held=held, n_group=GROUPS, topk_group=KEPT, dtype=jnp.float32,
    )
    return DroplessMoE(**dict(fields, **overrides))


@pytest.fixture(scope="module")
def whole_layer():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, D), jnp.float32)
    variables = jax.jit(_layer(None).init)(jax.random.PRNGKey(2), x)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (E,), jnp.float32)
    return x, variables["params"], {"router_bias": bias - jnp.mean(bias)}


def test_the_grouped_choice_is_a_plain_top_k_of_groups(whole_layer):
    """Written out with numpy: a group's score is the sum of its two best ``s +
    b``; the best 4 of 8 groups are kept; the 8 best ``s + b`` inside them are
    the token's experts; the gauge counts the groups they touch."""
    x, params, stats = whole_layer
    _, sown = _layer(None).apply(
        {"params": params, "batch_stats": stats}, x, mutable=["intermediates", "metrics"]
    )
    top_idx = np.asarray(sown["intermediates"]["top_idx"][0])
    logits = np.asarray(sown["intermediates"]["router_logits"][0], np.float64)
    choice = 1 / (1 + np.exp(-logits)) + np.asarray(stats["router_bias"], np.float64)
    for n in range(choice.shape[0]):
        by_group = choice[n].reshape(GROUPS, E // GROUPS)
        score = np.sort(by_group, axis=-1)[:, -2:].sum(axis=-1)
        kept = np.argsort(-score)[:KEPT]
        inside = np.full(E, -np.inf)
        for group in kept:
            members = slice(group * (E // GROUPS), (group + 1) * (E // GROUPS))
            inside[members] = choice[n, members]
        assert sorted(top_idx[n]) == sorted(np.argsort(-inside)[:K])
        assert set(top_idx[n] // (E // GROUPS)) <= set(kept)
    touched = np.mean([len(set(row // (E // GROUPS))) for row in top_idx])
    assert float(sown["metrics"]["moe_groups_live"][0]) == pytest.approx(touched)
    assert touched <= KEPT
    # and the reference's own choice is the same one
    _, experts, margin, _ = reference.route(LAYER, jnp.asarray(logits, jnp.float32), stats["router_bias"])
    settled = np.asarray(margin) > 1e-5
    assert (np.sort(np.asarray(experts), -1) == np.sort(top_idx, -1))[settled].all()


@pytest.mark.parametrize("bias_rate", [1e-3, 0.0], ids=["bias", "no_bias"])
def test_with_one_group_it_is_the_ungrouped_layer_bit_for_bit(whole_layer, bias_rate):
    x, params, stats = whole_layer
    variables = {"params": params, **({"batch_stats": stats} if bias_rate else {})}
    grouped = _layer(None, n_group=1, topk_group=1, bias_rate=bias_rate)
    plain = DroplessMoE(
        num_experts=E, top_k=K, d_ff=F, norm_topk_prob=True, aux_weight=0.0, z_weight=0.0,
        score_func="sigmoid", route_scale=2.5, bias_rate=bias_rate, shared_d_ff=F,
        dtype=jnp.float32,
    )
    a, sown = grouped.apply(variables, x, mutable=["metrics"])
    b = plain.apply(variables, x, mutable=["metrics"])[0]
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert "moe_groups_live" not in sown["metrics"]
    lower = lambda layer: jax.jit(  # noqa: E731
        lambda v, x: layer.apply(v, x, mutable=["metrics"])[0]
    ).lower(variables, x).as_text()
    assert lower(grouped) == lower(plain)
    assert MoESpec(8, 2, 16).n_group == 1 and MoESpec(8, 2, 16).topk_group == 1


@pytest.mark.parametrize("fields", [
    dict(n_group=5), dict(n_group=8, topk_group=9), dict(n_group=32, topk_group=2),
    dict(n_group=8, topk_group=1, top_k=16),
])
def test_groups_that_cannot_hold_the_choice_are_refused(whole_layer, fields):
    x, params, stats = whole_layer
    with pytest.raises(ValueError, match="groups"):
        _layer(None, **fields).apply({"params": params, "batch_stats": stats}, x)


@pytest.fixture(scope="module")
def expert_layer_both_ways(whole_layer):
    x, params, stats = whole_layer

    def program(p, x):
        return _layer(None).apply({"params": p, "batch_stats": stats}, x)

    def plain(p, x):
        return reference.mixture(LAYER, p, stats["router_bias"], x.reshape(-1, D))[0].reshape(x.shape)

    weight = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    with jax.default_matmul_precision("highest"):
        return [value_and_gradients(f, params, x, weight=weight) for f in (program, plain)]


@pytest.mark.parametrize("what", ["value", "gradients"])
def test_the_expert_layer_equals_a_dense_loop(expert_layer_both_ways, what):
    (value, got), (want_value, want) = expert_layer_both_ways
    if what == "value":
        _close(value, want_value, tol=1e-5)
        return
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        _close(a, b, tol=1e-4)


@pytest.mark.parametrize(
    "sizes", [(8,) * 8, (16,) * 4, (24, 40), (64,)], ids=lambda s: "x".join(map(str, s)),
)
def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer(whole_layer, sizes):
    """Every chip routes over all E experts in their groups and computes what
    its own give; the shared expert is on every chip alike, so it counts once:
    the sum of the shares' ROUTED parts plus the shared expert's output is the
    uncut layer of the reference; and the reference given a share agrees chip
    by chip."""
    x, params, stats = whole_layer
    tokens = x.reshape(-1, D)
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.mixture(LAYER, params, stats["router_bias"], tokens)
        shared = reference.swiglu(params["shared"], tokens)
        total, first = shared, 0
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
                here, stats["router_bias"], tokens,
            )
            _close(part.reshape(-1, D), want, tol=1e-5)
            total, first = total + (part.reshape(-1, D) - shared), first + count
    _close(total, uncut, tol=1e-5)


# -- the mixers --------------------------------------------------------------------

SPEC = family.kda_spec(TOY)
MLA = family.latent_spec(TOY)


@pytest.fixture(scope="module")
def kda_mixer():
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, D), jnp.float32)
    layer = KimiDeltaMixer(SPEC, jnp.float32, TOY["rms_norm_eps"])
    return layer, shaken(jax.jit(layer.init)(jax.random.PRNGKey(6), x)["params"]), x


def test_the_kda_mixer_holds_the_published_parameters(kda_mixer):
    _, params, _ = kda_mixer
    h, d = TOY["num_attention_heads"], TOY["head_dim"]
    assert {k: v["kernel"].shape for k, v in params.items() if k.endswith("_proj")} == {
        "q_proj": (D, h * d), "k_proj": (D, h * d), "v_proj": (D, h * d),
        "f_proj": (D, h * d), "g_proj": (D, h * d), "b_proj": (D, h), "o_proj": (h * d, D),
    }
    assert params["q_conv"].shape == params["k_conv"].shape == params["v_conv"].shape == (4, h * d)
    assert params["A_log"].shape == (h,) and params["dt_bias"].shape == (h, d)
    assert params["norm"].shape == (d,)
    counted = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    toy = dict(TOY, hidden_size=D)
    assert counted == family.kda_mixer_params(toy) + 3 * 4 * h * d + h + h * d + d


@pytest.fixture(scope="module")
def kda_mixer_both_ways(kda_mixer):
    layer, params, x = kda_mixer
    program = lambda p, x: layer.apply({"params": p}, x)  # noqa: E731
    plain = lambda p, x: reference.kda_mixer(TOY, p, x)  # noqa: E731
    weight = jax.random.normal(jax.random.PRNGKey(8), x.shape)
    with jax.default_matmul_precision("highest"):
        return [value_and_gradients(f, params, x, weight=weight) for f in (program, plain)]


@pytest.mark.parametrize("what", ["value", "gradients", "inputs"])
def test_the_kda_mixer_equals_the_reference(kda_mixer, kda_mixer_both_ways, what):
    layer, params, x = kda_mixer
    (value, got), (want_value, want) = kda_mixer_both_ways
    with jax.default_matmul_precision("highest"):
        if what == "value":
            _close(value, want_value, tol=1e-4)
            return
        if what == "inputs":
            _, sown = jax.jit(lambda p, x: layer.apply(
                {"params": p}, x, mutable=["intermediates", "metrics"]
            ))(params, x)
            want = reference.rule_inputs(TOY, params, x)
            for a, b in zip(sown["intermediates"]["rule_inputs"][0], want):
                _close(a, b, tol=1e-5)
            g = sown["intermediates"]["rule_inputs"][0][3]
            assert g.shape == (2, 64, 4, 16) and -5.0 < float(jnp.min(g)) and float(jnp.max(g)) <= 0.0
            assert float(sown["metrics"]["kda_decay_mean"][0]) == pytest.approx(
                float(jnp.mean(jnp.exp(want[3]))), rel=1e-5
            )
            assert float(sown["metrics"]["kda_state_absmax"][0]) > 0
            return
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        assert float(jnp.linalg.norm(b)) > 0, jax.tree_util.keystr(path)
        _close(a, b, tol=1e-3)


def test_a_future_step_never_reaches_an_earlier_output(kda_mixer):
    layer, params, x = kda_mixer
    later = x.at[:, 40:].add(1.0)
    apply = jax.jit(lambda x: layer.apply({"params": params}, x))
    a, b = apply(x), apply(later)
    assert np.array_equal(np.asarray(a[:, :40]), np.asarray(b[:, :40]))
    assert float(jnp.max(jnp.abs(a[:, 40:] - b[:, 40:]))) > 1e-3


@pytest.fixture(scope="module")
def mla_layer():
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 48, D), jnp.float32)
    layer = LatentAttention(4, MLA, jnp.float32, TOY["rms_norm_eps"], float(TOY["rope_theta"]))
    positions = jnp.broadcast_to(jnp.arange(48)[None], (2, 48))
    return layer, shaken(jax.jit(layer.init)(jax.random.PRNGKey(10), x, positions)["params"]), x, positions


def test_the_latent_layer_holds_the_published_parameters(mla_layer):
    _, params, _, _ = mla_layer
    assert {k: v["kernel"].shape for k, v in params.items() if "kernel" in v} == {
        "q": (D, 4, 24), "kv_a": (D, 32 + 8), "kv_b": (32, 4, 16 + 16), "g": (D, 4),
        "o": (4, 16, D),
    }
    assert params["kv_norm"]["scale"].shape == (32,)
    counted = sum(int(np.prod(v["kernel"].shape)) for v in params.values() if "kernel" in v)
    assert counted == family.mla_mixer_params(TOY)


@pytest.fixture(scope="module")
def mla_layer_both_ways(mla_layer):
    layer, params, x, positions = mla_layer
    program = lambda p, x: layer.apply({"params": p}, x, positions)  # noqa: E731
    plain = lambda p, x: reference.mla_mixer(TOY, p, x)  # noqa: E731
    weight = jax.random.normal(jax.random.PRNGKey(11), x.shape)
    with jax.default_matmul_precision("highest"):
        return [value_and_gradients(f, params, x, weight=weight) for f in (program, plain)]


@pytest.mark.parametrize("what", ["value", "gradients"])
def test_the_latent_layer_equals_the_reference(mla_layer_both_ways, what):
    (value, got), (want_value, want) = mla_layer_both_ways
    if what == "value":
        _close(value, want_value, tol=1e-5)
        return
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        assert float(jnp.linalg.norm(b)) > 0, jax.tree_util.keystr(path)
        _close(a, b, tol=1e-4)


def test_the_latent_layers_rotation_is_at_the_specs_base_and_shared_by_the_heads(mla_layer):
    layer, params, x, positions = mla_layer
    other = layer.clone(rope_theta=10000.0)
    apply = jax.jit(lambda layer, positions: layer.apply({"params": params}, x, positions),
                    static_argnums=0)
    a, b = apply(layer, positions), apply(other, positions)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-4
    shifted = apply(layer, positions + 7)  # relative positions only
    _close(shifted, a, tol=1e-4)


def test_each_traced_shape_leaves_one_mla_shape_instant(mla_layer):
    layer, params, x, positions = mla_layer
    obs_trace.get_tracer().reset_notes()
    tracer = obs_trace.get_tracer()
    before = len([e for e in tracer.to_events() if e["name"] == "mla_shape"])
    for _ in range(2):
        jax.jit(lambda p, x: layer.apply({"params": p}, x, positions)).lower(params, x)
    found = [e["args"] for e in tracer.to_events() if e["name"] == "mla_shape"][before:]
    assert len(found) == 1
    assert (found[0]["tq"], found[0]["heads"], found[0]["d_qk"], found[0]["d_v"]) == (48, 4, 24, 16)
    assert found[0]["latent"] == 32 and found[0]["rope_dim"] == 8
    assert found[0]["fwd_blocks"] == list(A._flash2_blocks("fwd", 48, 48, None))
