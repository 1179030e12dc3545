"""``ops/causal_conv.py``: the Pallas kernel pair in interpret mode against the
plain float32 form (``causal_conv`` then ``silu``) and jax's own backward of
it: values, the four gradients, what a row may depend on, and the shapes the
kernels leave to the plain form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.ops import causal_conv as cc

F32, BF16 = jnp.float32, jnp.bfloat16
DTYPES = [pytest.param(F32, id="float32"), pytest.param(BF16, id="bfloat16")]
# the kernels run with time along the lanes and the channels along the
# sublanes, so a length is whole lane tiles (128) and a width whole bfloat16
# sublane tiles (16); a tile is at most 8192 steps, so 8192 + 128 = 5 tiles of
# 1664: both halos cross
LENGTHS = [128, 384, 8192 + 128]


def operands(dtype, b, t, c, taps=4, offset=0, beside=0, seed=0):
    """``x`` is ``offset + c + beside`` wide: the convolution's channels lie
    at ``offset``, as ``xBC`` lies inside the in projection's output."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (b, t, offset + c + beside)).astype(dtype)
    kernel = jax.random.uniform(keys[1], (taps, c), F32, -0.5, 0.5)
    bias = 0.1 * jax.random.normal(keys[2], (c,))
    dy = jax.random.normal(keys[3], (b, t, c)).astype(dtype)
    return x, kernel, bias, dy


def kernels(x, kernel, bias, offset=0):
    assert cc._blocks(x, kernel, offset) is not None
    return cc.causal_conv_silu(x, kernel, bias, offset=offset, interpret=True)


def close(got, want, dtype):
    """To 1e-5 of the largest value in float32, to bfloat16's rounding (one
    part in 2^8) otherwise."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    tol = 1e-5 if dtype == F32 else 2.0 ** -7
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * np.abs(want).max())


@pytest.mark.parametrize("with_bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("b", [1, 2], ids=lambda b: "b%d" % b)
@pytest.mark.parametrize("c", [16, 48], ids=lambda c: "c%d" % c)
@pytest.mark.parametrize("t", LENGTHS, ids=lambda t: "t%d" % t)
@pytest.mark.parametrize("dtype", DTYPES)
def test_value_equals_the_plain_forms(dtype, t, c, b, with_bias):
    # the batch of two reads its channels in place out of a wider array
    offset = 16 * (b - 1)
    x, kernel, bias, _ = operands(dtype, b, t, c, offset=offset, beside=offset)
    bias = bias if with_bias else None
    got = kernels(x, kernel, bias, offset)
    assert got.dtype == dtype and got.shape == (b, t, c)
    close(got, cc._plain(x, kernel, bias, offset), dtype)


@pytest.mark.parametrize("wrt", ["x", "kernel", "bias", "x_used_twice"])
@pytest.mark.parametrize("t", LENGTHS, ids=lambda t: "t%d" % t)
@pytest.mark.parametrize("dtype", DTYPES)
def test_gradients_equal_jaxs_of_the_plain_form(dtype, t, wrt):
    offset = 32
    x, kernel, bias, dy = operands(dtype, 2, t, 48, offset=offset, beside=16)

    def loss(op):
        def f(x, kernel, bias):
            out = (op(x, kernel, bias, offset).astype(F32) * dy.astype(F32)).sum()
            if wrt == "x_used_twice":  # as z and dt leave the same projection
                out = out + (x.astype(F32) ** 2).sum()
            return out
        return f

    arg = {"x": 0, "kernel": 1, "bias": 2, "x_used_twice": 0}[wrt]
    got = jax.grad(loss(kernels), arg)(x, kernel, bias)
    want = jax.grad(loss(cc._plain), arg)(x, kernel, bias)
    assert got.dtype == want.dtype and got.shape == want.shape
    close(got, want, dtype if arg == 0 else F32)


@pytest.mark.parametrize("block_t", [128, 256], ids=lambda n: "tile%d" % n)
@pytest.mark.parametrize("dtype", DTYPES)
def test_tiles_as_short_as_a_halo_give_the_same(monkeypatch, dtype, block_t):
    monkeypatch.setattr(cc, "_BLOCK_T", block_t)
    x, kernel, bias, dy = operands(dtype, 1, 768, 32, taps=7)
    assert cc._blocks(x, kernel, 0) == (32, block_t)

    def both(op):
        y, pull = jax.vjp(lambda *a: op(*a), x, kernel, bias)
        return (y, *pull(dy))

    for got, want in zip(both(kernels), both(lambda *a: cc._plain(*a, 0))):
        close(got, want, dtype if got.ndim == 3 else F32)


@pytest.mark.parametrize("dtype", DTYPES)
def test_a_row_of_the_value_sees_no_later_row(dtype):
    t0 = 1664 + 5  # inside the second tile
    x, kernel, bias, _ = operands(dtype, 1, 8320, 16)
    other = x.at[:, t0:].set(operands(dtype, 1, 8320, 16, seed=1)[0][:, t0:])
    a, b = kernels(x, kernel, bias), kernels(other, kernel, bias)
    np.testing.assert_array_equal(np.asarray(a[:, :t0], np.float32),
                                  np.asarray(b[:, :t0], np.float32))
    assert not np.array_equal(np.asarray(a[:, t0], np.float32),
                              np.asarray(b[:, t0], np.float32))


@pytest.mark.parametrize("dtype", DTYPES)
def test_a_row_of_dx_sees_no_earlier_row_of_the_cotangent(dtype):
    t0 = 3328 - 2  # the last steps of the second tile: their dx reads the third
    x, kernel, bias, dy = operands(dtype, 1, 8320, 16)
    other = dy.at[:, :t0].set(operands(dtype, 1, 8320, 16, seed=1)[3][:, :t0])
    pull = jax.vjp(lambda x: kernels(x, kernel, bias), x)[1]
    (a,), (b,) = pull(dy), pull(other)
    np.testing.assert_array_equal(np.asarray(a[:, t0:], np.float32),
                                  np.asarray(b[:, t0:], np.float32))
    assert not np.array_equal(np.asarray(a[:, t0 - 1], np.float32),
                              np.asarray(b[:, t0 - 1], np.float32))


@pytest.mark.parametrize("t,c,taps,offset", [
    pytest.param(192, 32, 4, 0, id="steps_off_the_lanes"),
    pytest.param(128, 24, 4, 0, id="channels_off_the_sublanes"),
    pytest.param(128, 32, 8, 0, id="eight_taps"),
    pytest.param(128, 32, 4, 8, id="offset_off_the_sublanes"),
])
def test_a_shape_the_kernels_refuse_takes_the_plain_form(t, c, taps, offset):
    x, kernel, bias, _ = operands(BF16, 2, t, c, taps=taps, offset=offset)
    assert cc._blocks(x, kernel, offset) is None
    op = lambda x: cc.causal_conv_silu(x, kernel, bias, offset=offset, interpret=True)
    assert "pallas_call" not in str(jax.make_jaxpr(op)(x))
    np.testing.assert_array_equal(
        np.asarray(op(x), np.float32),
        np.asarray(cc._plain(x, kernel, bias, offset), np.float32),
    )


def test_off_the_tpu_the_plain_form_runs_and_channels_must_exist():
    x, kernel, bias, _ = operands(BF16, 1, 128, 16)
    assert cc._blocks(x, kernel, 0) is not None
    assert "pallas_call" not in str(
        jax.make_jaxpr(lambda x: cc.causal_conv_silu(x, kernel, bias))(x)
    )
    with pytest.raises(ValueError, match="no 16 channels at 8"):
        cc.causal_conv_silu(x, kernel, bias, offset=8)
