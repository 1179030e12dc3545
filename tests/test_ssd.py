"""``ops/ssd.py``'s scan as two Pallas kernels that carry the state themselves,
in the interpreter on the CPU: value, final state and the gradients (the six
inputs' and the initial state's) of the kernel form against the plain form
(``_local_plain`` and ``_carry_out``'s ``lax.scan``) and against the float32
sequential recurrence, at the two cells' head shape (64 wide over a state of
128), their chunks and groups (256 in one group, 128 in four) and the two
crossed; the state between chunks is float32 (a rounded one is caught at the
benchmark's own limit); what the contract refuses takes the plain form, whose
carry is the loop, and says why; a wrong program is caught at the benchmark's
own limit. Mosaic's tiling is not checked here: ``tests/test_tpu_compile.py``
compiles the kernels for a described v5e.
"""

import functools
import importlib
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import ssm_lm
from benchmark.families.ssm_lm import SCAN_REL_TOL, STATE_RMS_TOL, STATE_STEPS
from benchmark.reference import ssm_lm as reference
from edl_tpu.obs import trace as obs_trace
from edl_tpu.ops import ssd_scan

S = importlib.import_module("edl_tpu.ops.ssd")
G = importlib.import_module("edl_tpu.ops.gated_delta")

SHAPES = [(256, 1), (128, 4)]          # (chunk, groups): Granite's cell, Nemotron's
IDS = ["chunk256_1group", "chunk128_4groups"]
NAMES = ("x", "dt", "A", "B", "C", "D")
WIDTH, STATE = 64, 128


def scan_inputs(chunk, groups, seed=0, heads=None, width=WIDTH, state=STATE, steps=None,
                dtype=jnp.bfloat16):
    """A ``T`` of four chunks and eight heads a group, a round of the kernels'
    loops; step sizes and decay rates spread as a Mamba-2 layer's are
    (``exp(dt A)`` from 0.999 to under 0.1 a step)."""
    t, heads = steps or 4 * chunk, heads or 8 * groups
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(keys[0], (1, t, heads, width)).astype(dtype)
    dt = jnp.exp(jax.random.uniform(keys[1], (1, t, heads), minval=-6.0, maxval=-1.5))
    a = -jax.random.uniform(keys[2], (heads,), minval=1.0, maxval=16.0)
    b = (jax.random.normal(keys[3], (1, t, groups, state)) * state ** -0.5).astype(dtype)
    c = jax.random.normal(keys[4], (1, t, groups, state)).astype(dtype)
    d = 1.0 + 0.1 * jax.random.normal(keys[5], (heads,))
    w = jax.random.normal(keys[6], (1, t, heads, width)).astype(dtype)
    state0 = jax.random.normal(keys[7], (1, heads, width, state))
    return (x, dt, a, b, c, d), w, state0


@functools.lru_cache(maxsize=None)
def value_and_grads(chunk, groups, form, with_state, chunks=4):
    """``(y, final state or None, the gradients)`` of ``form``: the kernels in
    the interpreter, the plain form, or the float32 recurrence. The gradients
    are the six inputs' and, ``with_state``, the initial state's."""
    args, w, state0 = scan_inputs(chunk, groups, steps=chunks * chunk)
    f32 = lambda v: v.astype(jnp.float32)  # noqa: E731

    def fn(*a):
        a, state0 = (a[:6], a[6]) if with_state else (a, None)
        if form == "recurrence":
            y, final = reference.recurrence(*(f32(v) for v in a), state=state0)
        else:
            y, final = ssd_scan(
                *a, chunk=chunk, initial_state=state0, return_final_state=True,
                interpret=form == "kernel",
            )
        return (y, final) if with_state else (y, jnp.zeros_like(final))

    (y, final), vjp = jax.vjp(fn, *args, *([state0] if with_state else []))
    grads = vjp((w.astype(y.dtype), jnp.ones_like(final) if with_state else jnp.zeros_like(final)))
    return y, (final if with_state else None), grads


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


@pytest.mark.parametrize("with_state", [False, True], ids=["from_zeros", "state_in_and_out"])
@pytest.mark.parametrize("chunk,groups", SHAPES, ids=IDS)
def test_the_kernels_value_is_the_plain_forms_and_the_recurrences(chunk, groups, with_state):
    """Against the plain form within a rounding of the bfloat16 result, against
    the float32 recurrence within the benchmark's own limit; the final state,
    float32 on both sides, within half of that."""
    got_y, got_state, _ = value_and_grads(chunk, groups, "kernel", with_state)
    plain_y, plain_state, _ = value_and_grads(chunk, groups, "plain", with_state)
    want_y, want_state, _ = value_and_grads(chunk, groups, "recurrence", with_state)
    assert got_y.dtype == jnp.bfloat16
    assert rel(got_y, plain_y) <= 0.004
    assert rel(got_y, want_y) <= SCAN_REL_TOL / 2
    if with_state:
        assert got_state.dtype == jnp.float32
        assert rel(got_state, plain_state) <= 0.004     # a rounding of its bfloat16 operand
        assert rel(got_state, want_state) <= 0.01


@pytest.mark.parametrize("with_state", [False, True], ids=["from_zeros", "state_in_and_out"])
@pytest.mark.parametrize("wrt", range(6), ids=NAMES)
@pytest.mark.parametrize("chunk,groups", SHAPES, ids=IDS)
def test_the_kernels_gradients_are_the_plain_forms_and_the_recurrences(
        chunk, groups, wrt, with_state):
    """Each of the six gradients, through ``y`` and (``state_in_and_out``)
    through the final state too: the backward kernel against jax's own of the
    plain form, and both against the float32 recurrence's."""
    got = value_and_grads(chunk, groups, "kernel", with_state)[2][wrt]
    plain = value_and_grads(chunk, groups, "plain", with_state)[2][wrt]
    want = value_and_grads(chunk, groups, "recurrence", with_state)[2][wrt]
    assert got.shape == plain.shape and got.dtype == plain.dtype
    assert rel(got, plain) <= 0.01
    assert rel(got, want) <= SCAN_REL_TOL
    assert rel(plain, want) <= SCAN_REL_TOL


@pytest.mark.parametrize("chunk,groups", SHAPES, ids=IDS)
def test_the_initial_states_gradient_is_the_plain_forms_and_the_recurrences(chunk, groups):
    """Through every chunk's inherited term and through the final state: the
    carry's own reverse scan (``_carry_out_bwd``'s ``d_state``) against jax's
    backward of the plain form's, and both against the float32 recurrence's."""
    got = value_and_grads(chunk, groups, "kernel", True)[2][6]
    plain = value_and_grads(chunk, groups, "plain", True)[2][6]
    want = value_and_grads(chunk, groups, "recurrence", True)[2][6]
    assert got.shape == want.shape == (1, 8 * groups, WIDTH, STATE) and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(want))) > 0.1              # the state reaches the outputs
    assert rel(got, plain) <= 0.01
    assert rel(got, want) <= SCAN_REL_TOL
    assert rel(plain, want) <= SCAN_REL_TOL


CROSSED = [(128, 1), (256, 4)]          # (chunk, groups): each cell's chunk in the other's groups
WHAT = ("y", "final", *NAMES, "initial")


@pytest.mark.parametrize("chunk,groups,with_state,what", [
    pytest.param(chunk, groups, with_state, what, id="chunk%d_%dgroups-%s-%s" % (
        chunk, groups, "state_in_and_out" if with_state else "from_zeros", what))
    for chunk, groups in CROSSED for with_state in (False, True) for what in WHAT
    if with_state or what not in ("final", "initial")   # no state goes in or comes out
])
def test_the_fused_pair_is_the_plain_stage_and_its_loop_over_three_chunks(
        chunk, groups, with_state, what):
    """The two kernels with the carry inside against ``_local_plain`` and
    ``_carry_out`` (through ``ssd_scan``, which chooses between them), three
    chunks: ``y``, the final state, each of the six gradients and the initial
    state's, which only a scan that was handed a state has."""
    got_y, got_state, got = value_and_grads(chunk, groups, "kernel", with_state, 3)
    want_y, want_state, want = value_and_grads(chunk, groups, "plain", with_state, 3)
    if what == "y":
        assert got_y.dtype == jnp.bfloat16 and rel(got_y, want_y) <= 0.004
    elif what == "final":
        assert got_state.dtype == jnp.float32 and rel(got_state, want_state) <= 0.004
    else:
        a, b = got[WHAT.index(what) - 2], want[WHAT.index(what) - 2]
        assert a.shape == b.shape and a.dtype == b.dtype
        assert rel(a, b) <= 0.01


def _rounding_its_state(kernel, name):
    """``kernel`` with the walk's state (the scratch its signature calls
    ``name``) rounded to bfloat16 after every chunk: the wrong program, a
    state kept from chunk to chunk in the operands' dtype."""
    at = list(inspect.signature(kernel).parameters).index(name)

    @functools.wraps(kernel)
    def rounded(*refs):
        kernel(*refs)
        refs[at][...] = refs[at][...].astype(jnp.bfloat16).astype(jnp.float32)

    return rounded


@pytest.mark.parametrize("state", ["float32", "bfloat16"])
def test_the_state_between_chunks_is_float32(state, monkeypatch):
    """The state after the last step in the benchmark's long-memory regime
    (``STATE_STEPS``: a head's memory spans every chunk), sixteen chunks,
    against the float32 recurrence at the benchmark's own limit: the kernels'
    float32 scratch is well inside it, and the same kernel with the scratch
    rounded to bfloat16 a chunk is outside, as the benchmark's wrong program
    (``benchmark/tests/test_ssm_lm.py``) is on the plain path."""
    config = dict(mamba_n_heads=8, mamba_d_head=16, mamba_n_groups=1, mamba_d_state=128)
    args, _ = ssm_lm.scan_inputs(config, 7, 2048, STATE_STEPS)
    args = [args[k] for k in ssm_lm.SCAN_ARGS]
    _, want = reference.recurrence(*(v.astype(jnp.float32) for v in args))
    if state == "bfloat16":
        monkeypatch.setattr(
            S, "ssd_forward_kernel", _rounding_its_state(S.ssd_forward_kernel, "state_ref")
        )
    S._forward_call.clear_cache()
    try:
        _, got = ssd_scan(*args, chunk=128, return_final_state=True, interpret=True)
    finally:
        S._forward_call.clear_cache()
    assert got.dtype == jnp.float32
    err = ssm_lm._rms_rel(got, want)
    if state == "float32":
        assert err <= STATE_RMS_TOL / 2
    else:
        assert err > STATE_RMS_TOL


def test_a_scan_without_a_skip_has_the_kernels_too():
    args, _, _ = scan_inputs(128, 2, seed=3, width=16)
    got = ssd_scan(*args[:5], chunk=128, interpret=True)
    want = ssd_scan(*args[:5], chunk=128)
    assert rel(got, want) <= 0.004


@pytest.mark.parametrize("why,path", [
    ("the_kernels_case", "kernel"), ("no_tpu_and_no_interpreter", "backend"),
    ("float32_operands", "dtype"), ("a_chunk_of_64", "chunk"), ("a_ragged_length", "steps"),
    ("three_heads_a_group", "heads"), ("four_heads_a_group", "heads"),
    ("a_state_of_64", "width"),
    ("a_head_of_8", "width"), ("more_than_vmem_holds", "vmem"),
])
def test_which_form_runs_is_decided_from_the_operands_and_says_so(why, path, monkeypatch):
    """One ``ssm_chunks`` instant a traced shape: ``path`` and, on ``plain``,
    the first condition of the contract that did not hold."""
    kw = dict(chunk=128, groups=2, heads=16, width=16, state=128, steps=256)
    interpret = True
    if why == "no_tpu_and_no_interpreter":
        interpret = False
    elif why == "float32_operands":
        kw["dtype"] = jnp.float32
    elif why == "a_chunk_of_64":
        kw["chunk"] = 64
    elif why == "a_ragged_length":
        kw["steps"] = 200
    elif why == "three_heads_a_group":
        kw["heads"] = 6
    elif why == "four_heads_a_group":
        kw["heads"] = 8
    elif why == "a_state_of_64":
        kw["state"] = 64
    elif why == "a_head_of_8":
        kw["width"] = 8
    elif why == "more_than_vmem_holds":
        monkeypatch.setattr(S, "_VMEM_MOST", 1 << 16)
    chunk = kw["chunk"]
    args, _, _ = scan_inputs(**kw)
    tracer = obs_trace.get_tracer()
    tracer.reset_notes()
    before = len([e for e in tracer.to_events() if e["name"] == "ssm_chunks"])
    for _ in range(2):
        lowered = jax.jit(
            lambda *a: ssd_scan(*a, chunk=chunk, interpret=interpret)
        ).lower(*args)
    found = [e["args"] for e in tracer.to_events() if e["name"] == "ssm_chunks"][before:]
    assert len(found) == 1
    note = found[0]
    steps = -(-kw["steps"] // chunk) * chunk
    assert (note["chunk"], note["chunks"], note["heads"], note["groups"]) == (
        chunk, steps // chunk, kw["heads"], 2)
    assert (note["d_head"], note["d_state"]) == (kw["width"], kw["state"])
    assert note["state_bytes"] == 4 * kw["heads"] * kw["width"] * kw["state"]
    if path == "kernel":
        assert (note["path"], note["carry"]) == ("kernel", "kernel") and "why" not in note
    else:
        assert (note["path"], note["why"], note["carry"]) == ("plain", path, "loop")
    assert ("ssd_forward" in lowered.as_text(debug_info=True)) == (path == "kernel")


def test_the_backward_keeps_nothing_but_the_inputs():
    """What the kernels' ``custom_vjp`` saves for its backward are its first
    four operands and the state every chunk inherited (the initial one its
    first): no ``[L, L]`` tile, no ``Y_diag``, no second copy of ``xBC``."""
    (x, dt, a, b, c, d), _, state0 = scan_inputs(128, 2, width=16)
    xbc = jnp.concatenate([v.reshape(1, v.shape[1], -1) for v in (x, b, c)], axis=-1)
    local = (xbc.swapaxes(1, 2), dt.swapaxes(1, 2), a.reshape(-1, 1), d.reshape(-1, 1))
    (y, final), saved = S._scan_kernels_fwd(*local, state0, 128, 16, 128, True)
    assert len(saved) == 5 and all(s is v for s, v in zip(saved, local))
    entering = saved[4]
    assert entering.shape == (4, *state0.shape) and entering.dtype == jnp.float32
    assert (y.shape, y.dtype) == ((1, 16 * 16, 512), jnp.bfloat16)
    np.testing.assert_array_equal(entering[0], state0)
    assert (final.shape, final.dtype) == (state0.shape, jnp.float32)


def test_heads_that_the_groups_do_not_divide_are_refused():
    (x, dt, a, b, c, d), _, _ = scan_inputs(128, 3, heads=16, width=16)
    with pytest.raises(ValueError, match="16 heads in 3 groups"):
        ssd_scan(x, dt, a, b, c, d, chunk=128)


def test_a_running_sum_kept_in_bfloat16_is_caught_at_the_benchmarks_limit(monkeypatch):
    """The wrong program: the kernels' running sum of ``dt A`` rounded to
    bfloat16 a step, as a careless port would keep it. The log-decay of a fast
    head passes 30 inside a chunk of 256, where bfloat16 steps by 0.25; against
    the float32 recurrence that is past ``SCAN_REL_TOL``, which the right
    program stays well under."""
    args, _, _ = scan_inputs(256, 1, seed=5)
    want, _ = reference.recurrence(*(v.astype(jnp.float32) for v in args))
    right = ssd_scan(*args, chunk=256, interpret=True)

    def in_bfloat16(a, reverse=False, axis=0):
        rounded = G._running_sum(a.astype(jnp.bfloat16).astype(jnp.float32), reverse, axis)
        return rounded.astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(S, "_running_sum", in_bfloat16)
    S._forward_call.clear_cache()
    try:
        wrong = ssd_scan(*args, chunk=256, interpret=True)
    finally:
        S._forward_call.clear_cache()
    assert rel(right, want) <= SCAN_REL_TOL / 2
    assert rel(wrong, want) > SCAN_REL_TOL
