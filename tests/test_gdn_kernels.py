"""The scalar delta rule's chunk-local stage as Pallas kernels, run on the CPU
through the Pallas interpreter against the plain form that stands beside them
(``ops/gated_delta.py:_scalar_kernels`` / ``_scalar_plain``): the stage's own
operands and gradients at float32, where nothing is rounded and the arithmetic
itself is held; the whole rule at bfloat16, where the points of rounding are,
at the cell's class of shape (an odd count of heads of 96 / 192), at an even
count of heads of whole lane tiles and at one head; which form a call takes,
and why; and decays so fast that a factor underflows. The kernels hold the
steps along the lanes, two chunks a grid step: every sequence here is at least
two grid steps, so a chunk's place in its pair and a pair's in the sequence
both show."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.obs import trace as obs_trace
from edl_tpu.ops import gated_delta_rule

G = importlib.import_module("edl_tpu.ops.gated_delta")

OPERANDS = ("w", "u", "k_out", "whole", "q_in", "scores", "inverse")
LEAVES = ("q", "k", "v", "g", "beta")
# heads, d_k, d_v: the cell's class (15 heads of 96 / 192: an odd count, heads
# that start on no lane tile), whole lane tiles in pairs, one odd head
SHAPES = {"fifteen_of_96_192": (15, 96, 192), "two_of_128_128": (2, 128, 128),
          "one_of_96_192": (1, 96, 192)}


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    assert np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-12) <= tol


def rule_inputs(seed, h, d_k, d_v, b=1, t=256, fast=1.0):
    """As the mixer hands them over: unit keys, scaled unit queries, a
    softplus gate's log-decay (``fast`` times it), beta in (0, 2)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda m: m / jnp.linalg.norm(m, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (b, t, h, d_k))) * d_k ** -0.5
    k = unit(jax.random.normal(keys[1], (b, t, h, d_k)))
    v = jax.random.normal(keys[2], (b, t, h, d_v))
    g = -fast * jax.nn.softplus(jax.random.normal(keys[3], (b, t, h)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, h)))
    return q, k, v, g, beta


def weights(like, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(like))
    return tuple(jax.random.normal(key, a.shape).astype(a.dtype) for key, a in zip(keys, like))


bf16_args = lambda args: tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]  # noqa: E731


# -- the stage itself, float32 operands straight to the kernels --------------------


def _lanes(a):
    """The steps along the lanes, a head's channels one under another."""
    return jnp.swapaxes(a.reshape(*a.shape[:2], -1), 1, 2)


def by_kernels(q, k, v, g, beta):
    """The six operands the rule reads by the kernels as the rule calls them,
    in ``_scalar_plain``'s shapes."""
    b, t, h = beta.shape
    w, u, k_out, whole, q_in, scores = G._scalar_kernels(
        *map(_lanes, (q, k, v, g, beta)), True
    )
    return (w, u, jnp.swapaxes(k_out, 2, 3), whole.reshape(t // 64, b, h),
            jnp.swapaxes(q_in, 2, 3), scores)


def inverse_by_kernels(q, k, v, g, beta):
    b, t, h = beta.shape
    pairs = G._scalar_inverse_call(_lanes(k), _lanes(g), _lanes(beta), True)
    # [b n/2 h c (2 s)]: a head's two chunks side by side along the lanes
    apart = jnp.moveaxis(pairs.reshape(b, t // 128, h, 64, 2, 64), 4, 2)
    return apart.reshape(b, t // 64, h, 64, 64)


def _stage_program(stage, inverse):
    @jax.jit
    def run(*args):
        values, pull = jax.vjp(lambda *x: stage(*x)[:6], *args)
        return values + (inverse(*args),), pull(weights(values, seed=20))

    return run


_plain_stage = lambda *a: G._scalar_plain(*a, 64)  # noqa: E731
STAGES = (
    _stage_program(by_kernels, inverse_by_kernels),
    _stage_program(_plain_stage, lambda *a: _plain_stage(*a)[6]),
)


@functools.lru_cache(maxsize=None)
def stage_both_ways():
    """Three heads of 96 / 192 (a pair and an odd last one), float32."""
    args = rule_inputs(4, 3, 96, 192)
    with jax.default_matmul_precision("highest"):
        return [run(*args) for run in STAGES]


@pytest.mark.parametrize("what", OPERANDS + LEAVES)
def test_the_kernels_are_the_plain_chunk_local_stage(what):
    """Float32 operands (which only a test hands the kernels) hold the
    arithmetic to the plain form's: every operand in its reader's layout, every
    chunk's ``T``, and the five gradients under one random cotangent of the six
    operands the rule reads."""
    (got, got_grads), (want, want_grads) = stage_both_ways()
    if what in OPERANDS:
        a, b = got[OPERANDS.index(what)], want[OPERANDS.index(what)]
    else:
        a, b = got_grads[LEAVES.index(what)], want_grads[LEAVES.index(what)]
    assert a.shape == b.shape and a.dtype == b.dtype
    _close(a, b, tol=1e-4)


# -- the rule by both forms, bfloat16 as the step runs it ---------------------------


def _rule_program(interpret):
    @functools.partial(jax.jit, static_argnums=0)
    def run(from_a_state, state, *args):
        values, pull = jax.vjp(lambda *a: gated_delta_rule(
            *a, chunk=64, initial_state=state if from_a_state else None,
            return_final_state=True, interpret=interpret,
        ), *args)
        return values, pull(weights(values, seed=21))

    return run


RULES = (_rule_program(True), _rule_program(False))


@functools.lru_cache(maxsize=None)
def rule_both_ways(shape, fast=1.0):
    """``(o, final state)`` and the five gradients under a random cotangent of
    both, by the kernels (in the interpreter) and by the plain form, from an
    initial state."""
    h, d_k, d_v = SHAPES[shape]
    args = bf16_args(rule_inputs(5, h, d_k, d_v, fast=fast))
    state = 0.1 * jax.random.normal(jax.random.PRNGKey(6), (1, h, d_k, d_v))
    return [run(True, state, *args) for run in RULES]


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("what", ["o", "state"] + list(LEAVES))
def test_the_rule_by_the_kernels_is_the_rule_by_the_plain_form(what, shape):
    (got, got_grads), (want, want_grads) = rule_both_ways(shape)
    if what in ("o", "state"):
        a, b = got[what == "state"], want[what == "state"]
    else:
        a, b = got_grads[LEAVES.index(what)], want_grads[LEAVES.index(what)]
    assert a.shape == b.shape and a.dtype == b.dtype
    _close(a.astype(jnp.float32), b.astype(jnp.float32), tol=2e-2)


@pytest.mark.parametrize("what", ["o", "state"] + list(LEAVES))
def test_a_factor_that_underflows_reads_zero_and_not_nan(what):
    """Log-decays of -40 to -200 a step: over a few steps ``exp(gamma_i -
    gamma_j)``, ``exp(gamma)`` and ``exp(gamma_C)`` all underflow float32.
    Every ``exp`` is of a difference that is never positive, so they read 0,
    forward and backward, as the plain form's and the recurrence's do."""
    (got, got_grads), (want, want_grads) = rule_both_ways("one_of_96_192", fast=120.0)
    if what in ("o", "state"):
        a, b = got[what == "state"], want[what == "state"]
    else:
        a, b = got_grads[LEAVES.index(what)], want_grads[LEAVES.index(what)]
    assert np.isfinite(np.asarray(a, np.float32)).all()
    _close(a.astype(jnp.float32), b.astype(jnp.float32), tol=2e-2)


def test_the_rule_by_the_kernels_equals_the_step_by_step_recurrence():
    """Within the limit the family's check holds the bfloat16 call to."""
    from benchmark.reference import gdn_lm as reference

    args = bf16_args(rule_inputs(3, 3, 96, 192))
    o, state = RULES[0](False, None, *args)[0]
    with jax.default_matmul_precision("highest"):
        want_o, want_state = reference.recurrence(*(a.astype(jnp.float32) for a in args))
    assert o.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    _close(o.astype(jnp.float32), want_o, tol=0.02)
    _close(state, want_state, tol=0.02)


# -- which form a call takes ----------------------------------------------------


@pytest.mark.parametrize("case,why", [
    ("the_cells_class", None), ("one_head_of_16", None), ("backend", "backend"),
    ("dtype", "dtype"), ("chunk", "chunk"), ("steps", "steps"), ("width", "width"),
])
def test_which_form_runs_is_decided_from_the_operands_and_says_so(case, why):
    """``gdn_chunks`` carries ``path`` and, on ``plain``, ``why``: the first of
    a TPU backend or the interpreter, bfloat16 operands (the benchmark check's
    exact call is float32), the kernels' chunk, a length of whole lane tiles
    (two chunks: three are refused) and widths in multiples of a packed
    bfloat16 tile's 16 rows that does not hold. Neither an odd count of heads
    nor a width under a lane tile is refused: a head lies along the sublanes."""
    h, d, t, chunk, interpret, narrow = 3, 96, 128, 64, True, bf16_args
    if case == "one_head_of_16":
        h, d = 1, 16
    elif case == "backend":
        interpret = False
    elif case == "dtype":
        narrow = lambda args: args  # noqa: E731
    elif case == "chunk":
        chunk = 32
    elif case == "steps":
        t = 192
    elif case == "width":
        d = 24
    args = narrow(rule_inputs(8, h, d, 2 * d, t=t))
    tracer = obs_trace.get_tracer()
    tracer.reset_notes()
    before = len([e for e in tracer.to_events() if e["name"] == "gdn_chunks"])
    lowered = jax.jit(
        lambda *a: gated_delta_rule(*a, chunk=chunk, interpret=interpret)
    ).lower(*args)
    found = [e["args"] for e in tracer.to_events() if e["name"] == "gdn_chunks"][before:]
    assert [(e["path"], e.get("why")) for e in found] == [("plain" if why else "kernel", why)]
    assert (found[0]["heads"], found[0]["d_k"], found[0]["d_v"]) == (h, d, 2 * d)
    text = lowered.as_text(debug_info=True)
    assert all((name in text) == (why is None) for name in ("gdn_inverse", "gdn_operands"))
