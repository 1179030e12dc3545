"""``ops/cross_entropy.py:rows_cross_entropy`` against the form it replaced,
``optax.softmax_cross_entropy(logits, one_hot)`` + ``jnp.argmax``: the value,
the argmax and the gradient a row, at the shapes and scales the cells see and
on the rows where the two spellings could part (ties, a label at either end, a
label that is no column, a NaN); then its three callers against what they
returned when each spelled the arithmetic itself."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from edl_tpu.models.transformer import _scored_cross_entropy
from edl_tpu.obs import trace as obs_trace
from edl_tpu.ops.cross_entropy import MAX_VOCAB, rows_cross_entropy
from edl_tpu.train import cross_entropy_loss, make_block_diffusion_loss

SHAPES = {
    "64x1017": (64, 1017),
    "128x25024": (128, 25024),     # Trinity's slice: 195.5 lane tiles
    "2x16x391": (2, 16, 391),      # a leading batch axis
}


def rows(shape, scale, seed=0):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(scale * rng.standard_normal(shape), jnp.float32)
    labels = jnp.asarray(rng.integers(0, shape[-1], shape[:-1]), jnp.int32)
    weights = jnp.asarray(rng.random(shape[:-1]), jnp.float32)
    return logits, labels, weights


def optax_rows(logits, labels):
    ce = optax.softmax_cross_entropy(logits, jax.nn.one_hot(labels, logits.shape[-1]))
    return ce, jnp.argmax(logits, -1)


def read(fn, logits, labels, weights):
    """``{"value", "best", "grad"}`` of ``fn``, the gradient under a weight a
    row so that no row's cotangent is the same number."""
    def weighted(logits):
        ce, best = fn(logits, labels)
        return jnp.sum(weights * ce), (ce, best)

    (_, (ce, best)), grad = jax.value_and_grad(weighted, has_aux=True)(logits)
    return {"value": ce, "best": best, "grad": grad}


def ours(logits, labels):
    return rows_cross_entropy(logits, labels, site="test")


def agree(what, got, want, scale=1.0):
    assert got.shape == want.shape and got.dtype == want.dtype
    if what == "best":
        np.testing.assert_array_equal(got, want)
    elif what == "value":
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * scale)
    else:
        # weights below 1 on a softmax below 1: float32 rounding of exp alone
        np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("what", ["value", "best", "grad"])
@pytest.mark.parametrize("scale", [1.0, 8.0, 50.0])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_rows_equal_optax_and_argmax(shape, scale, what):
    case = rows(SHAPES[shape], scale)
    agree(what, read(ours, *case)[what], read(optax_rows, *case)[what], scale)


def special_rows(name):
    """``(logits [4, 9], labels [4])`` with the named oddity in row 1."""
    logits, labels, weights = rows((4, 9), 3.0, seed=1)
    if name == "all_equal":
        logits = logits.at[1].set(2.5)
    elif name == "two_maxima":
        logits = logits.at[1, 6].set(40.0).at[1, 2].set(40.0)
    elif name == "label_first":
        labels = labels.at[1].set(0)
    elif name == "label_last":
        labels = labels.at[1].set(8)
    elif name == "label_below":
        labels = labels.at[1].set(-1)
    elif name == "label_above":
        labels = labels.at[1].set(9)
    return logits, labels, weights


@pytest.mark.parametrize("what", ["value", "best", "grad"])
@pytest.mark.parametrize("name", [
    "all_equal", "two_maxima", "label_first", "label_last", "label_below", "label_above",
])
def test_odd_rows_equal_optax_and_argmax(name, what):
    case = special_rows(name)
    got = read(ours, *case)
    agree(what, got[what], read(optax_rows, *case)[what])
    if name == "all_equal":
        assert int(got["best"][1]) == 0
    if name == "two_maxima":
        assert int(got["best"][1]) == 2     # the first index wins
    if name in ("label_below", "label_above"):
        # an all-zero one-hot: the row scores nothing and moves nothing
        assert float(got["value"][1]) == 0.0 and not np.any(got["grad"][1])


def test_a_row_with_a_nan_has_no_best_column():
    logits, labels, _ = rows((3, 7), 1.0)
    ce, best = ours(logits.at[1, 4].set(jnp.nan), labels)
    assert int(best[1]) == 7 and np.isnan(ce[1])
    assert np.isfinite(ce[0]) and np.isfinite(ce[2])


def test_the_rows_agree_under_jit_in_one_program():
    case = rows((32, 515), 8.0)
    got = jax.jit(lambda *a: read(ours, *a))(*case)
    want = read(optax_rows, *case)
    for what in want:
        agree(what, got[what], want[what], 8.0)


def test_best_and_labels_take_no_gradient():
    logits, labels, weights = rows((8, 33), 1.0)
    out, pull = jax.vjp(lambda x: ours(x, labels), logits)
    zero = np.zeros(out[1].shape, jax.dtypes.float0)
    (grad,) = pull((weights, zero))
    np.testing.assert_allclose(grad.sum(-1), 0.0, atol=1e-6)   # softmax - one_hot


@pytest.mark.parametrize("vocab", [MAX_VOCAB, MAX_VOCAB + 128])
def test_a_vocabulary_float32_cannot_count_is_refused(vocab):
    logits = jax.ShapeDtypeStruct((2, vocab), jnp.float32)
    with pytest.raises(ValueError, match="float32"):
        rows_cross_entropy(logits, jax.ShapeDtypeStruct((2,), jnp.int32), site="test")


def test_labels_of_another_shape_are_refused():
    logits, labels, _ = rows((4, 9), 1.0)
    with pytest.raises(ValueError, match="labels"):
        ours(logits, labels[:, None])


def test_each_shape_and_site_notes_itself_once():
    tracer = obs_trace.get_tracer()
    tracer.reset_notes()
    logits, labels, _ = rows((2, 16, 391), 1.0)
    ours(logits, labels)
    ours(logits, labels)
    cross_entropy_loss(logits.reshape(32, 391), labels.reshape(32))
    noted = [args for name, args in tracer.notes() if name == "ce_rows"]
    tracer.reset_notes()
    assert noted == [
        {"rows": 32, "vocab": 391, "site": "test"},
        {"rows": 32, "vocab": 391, "site": "cross_entropy_loss"},
    ]


# -- the three callers, against what each returned when it spelled the rows itself


def old_cross_entropy_loss(logits, labels):
    ce, best = optax_rows(logits, labels)
    return ce.mean(), {"accuracy": (best == labels).mean()}


def old_block_diffusion_loss(logits, y):
    labels, weights = y
    ce, best = optax_rows(logits, labels)
    weights = weights.astype(jnp.float32)
    scored = weights > 0
    count = jnp.maximum(jnp.sum(scored), 1)
    return jnp.sum(weights * ce) / ce.size, {
        "accuracy": jnp.sum(scored & (best == labels)) / count,
        "bd_masked_share": jnp.mean(scored),
        "bd_masked_ce": jnp.sum(jnp.where(scored, ce, 0.0)) / count,
    }


def old_scored_cross_entropy(logits, y):
    labels, scored = y
    one_hot = jax.nn.one_hot(labels, logits.shape[-1])
    ce = -jnp.sum(one_hot * jax.nn.log_softmax(logits, axis=-1), axis=-1)
    return jnp.sum(ce * scored) / jnp.maximum(jnp.sum(scored), 1), {}


def scored_cross_entropy(logits, y):
    return _scored_cross_entropy(logits, *y), {}


def caller_case(caller):
    """``(new, old, logits, target)``: two loss heads and what they read."""
    if caller == "cross_entropy_loss":
        logits, labels, _ = rows((96, 1017), 8.0, seed=2)
        return cross_entropy_loss, old_cross_entropy_loss, logits, labels
    logits, labels, weights = rows((2, 24, 391), 8.0, seed=3)
    if caller == "block_diffusion_loss":
        # 1 / t where a position was masked, 0 where it was not
        weights = jnp.where(weights < 0.4, 0.0, 1.0 / weights)
        return make_block_diffusion_loss(), old_block_diffusion_loss, logits, (labels, weights)
    scored = jnp.broadcast_to(jnp.arange(24) < 22, labels.shape)
    return scored_cross_entropy, old_scored_cross_entropy, logits, (labels, scored)


CALLERS = {
    "cross_entropy_loss": ("loss", "grad", "accuracy"),
    "block_diffusion_loss": ("loss", "grad", "accuracy", "bd_masked_share", "bd_masked_ce"),
    "scored_cross_entropy": ("loss", "grad"),
}


@pytest.mark.parametrize("caller,what", [
    (caller, what) for caller, outputs in CALLERS.items() for what in outputs
])
def test_a_caller_returns_what_it_returned(caller, what):
    new, old, logits, target = caller_case(caller)

    def outputs(fn):
        (loss, metrics), grad = jax.value_and_grad(fn, has_aux=True)(logits, target)
        return {"loss": loss, "grad": grad, **metrics}

    got, want = outputs(new), outputs(old)
    assert set(got) == set(want) == set(CALLERS[caller])
    if what == "grad":
        np.testing.assert_allclose(got[what], want[what], rtol=1e-6, atol=1e-7)
    else:
        np.testing.assert_allclose(got[what], want[what], rtol=1e-6)
