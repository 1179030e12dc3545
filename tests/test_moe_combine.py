"""A held share's combine on its buffer (``models/moe.py:_sum_unsorted`` handed
``m < N k`` rows: a sum by token over the rows the held experts wrote,
``ops/grouped_matmul.py:rows_summed_by_segment``) against the form it took
until PR 60, kept here as the reference: a gather of ``rows[inverse]`` for all
``N k`` pairs, the pairs at and past ``live`` masked away, summed over ``k``
neighbours. The plain form and the Megablox form (in the interpreter), float32
and bfloat16 rows, forward and as ``_rows_sorted``'s gradient."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import edl_tpu.models.moe as moe

gm = importlib.import_module("edl_tpu.ops.grouped_matmul")  # the package exports the function

N, K, D, HELD = 384, 4, 128, 3  # three tiles of 128 tokens


def parents(rows, inverse, k, live):
    """``_sum_unsorted`` with ``live`` as PR 59 had it."""
    n = inverse.shape[0] // k
    back = jnp.where(
        (inverse < live)[:, None], rows[jnp.minimum(inverse, rows.shape[0] - 1)], 0
    )
    return jnp.sum(back.reshape(n, k, rows.shape[-1]), axis=1, dtype=jnp.float32)


def _held(case):
    """``[N, K]``: which (token, choice) pairs fall on a held expert."""
    rng = np.random.default_rng(7)
    held = rng.random((N, K)) < 0.25
    held[0], held[1], held[2] = False, [False, True, False, False], True
    if case == "live_0":
        held[:] = False
    if case == "a_tile_of_tokens_without_a_row":
        held[128:256] = False
    return held


def route(case):
    """``(first_rows [m], inverse [N K], live, m)`` as the layer makes them: the
    held pairs sorted to the front by expert, the others behind them."""
    held = _held(case)
    rng = np.random.default_rng(8)
    flat = np.where(held, rng.integers(0, HELD, (N, K)), HELD).reshape(N * K)
    order = np.argsort(flat, kind="stable")
    inverse = np.argsort(order, kind="stable")
    live = int(held.sum())
    m = {
        "live_m": live,                 # the buffer is full
        "m_no_multiple_of_the_row_tile": live + 13,
        "live_0": 256,
    }.get(case, -(-live // 128) * 128 + 128)
    assert live <= m < N * K and (case != "m_no_multiple_of_the_row_tile" or m % 128)
    return jnp.asarray(order[:m], jnp.int32), jnp.asarray(inverse, jnp.int32), jnp.int32(live), m


CASES = (
    "tokens_with_none_one_and_all_k_live", "live_0", "live_m",
    "a_tile_of_tokens_without_a_row", "m_no_multiple_of_the_row_tile",
)
FORMS = {
    "plain": gm.rows_summed_by_segment,
    "megablox_interpreted": functools.partial(
        gm.rows_summed_by_segment, implementation="pallas", interpret=True
    ),
}


def rows_of(m, live, dtype, garbage):
    rows = jax.random.normal(jax.random.PRNGKey(m), (m, D), jnp.float32).astype(dtype)
    # Megablox writes no row at or past ``live``: whatever the memory held
    return jnp.where((jnp.arange(m) >= live)[:, None], garbage, rows)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("case", CASES)
def test_the_buffers_sum_by_token_is_the_gather_of_every_pair(monkeypatch, case, form, dtype):
    """The same value from the buffer's ``m`` rows alone, NaN in the rows at and
    past ``live``: only the order of a token's at most ``k`` float32 terms may
    differ, so float32 rows agree to 1e-6 and bfloat16 rows to float32 rounding."""
    monkeypatch.setattr(moe, "rows_summed_by_segment", FORMS[form])
    first_rows, inverse, live, m = route(case)
    rows = rows_of(m, live, dtype, jnp.nan)
    want = parents(rows_of(m, live, dtype, 0.0), inverse, K, live)
    got = moe._sum_unsorted(rows, first_rows, inverse, K, live)
    assert got.shape == (N, D) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)
    if case == "tokens_with_none_one_and_all_k_live":
        assert not np.asarray(got[0]).any()  # no live row: zeros
        np.testing.assert_array_equal(
            np.asarray(got[1]), np.asarray(rows[inverse[K + 1]], np.float32)
        )
    if case in ("live_0", "a_tile_of_tokens_without_a_row"):
        assert not np.asarray(got[128:256]).any()


@pytest.mark.parametrize("segments", ["in_any_order", "none_named", "not_whole_tiles"])
@pytest.mark.parametrize("form", list(FORMS))
def test_rows_summed_by_segment_is_a_segment_sum(form, segments):
    """The op's own contract: segments in any order, a row that names no
    segment (negative, or ``num_segments`` and more) is nobody's whatever it
    holds, a count of segments that is no whole number of tiles."""
    count = 200 if segments == "not_whole_tiles" else 256
    rng = np.random.default_rng(3)
    ids = rng.integers(-2, count + 3, 300)
    if segments == "none_named":
        ids[:] = count
    nobodys = (ids < 0) | (ids >= count)
    rows = rng.standard_normal((300, D)).astype(np.float32)
    want = np.zeros((count, D), np.float32)
    np.add.at(want, ids[~nobodys], rows[~nobodys])
    rows[nobodys] = np.nan
    got = FORMS[form](jnp.asarray(rows, jnp.bfloat16), jnp.asarray(ids), count)
    want_bf16 = np.zeros((count, D), np.float32)
    np.add.at(want_bf16, ids[~nobodys], np.asarray(jnp.asarray(rows, jnp.bfloat16), np.float32)[~nobodys])
    assert got.shape == (count, D) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want_bf16, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-2, atol=2e-2)
    # asked for in the rows' dtype: the float32 sum, rounded once
    rounded = FORMS[form](jnp.asarray(rows, jnp.bfloat16), jnp.asarray(ids), count, jnp.bfloat16)
    assert rounded.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(rounded, np.float32), np.asarray(got.astype(jnp.bfloat16), np.float32)
    )


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("function", ["_rows_combined", "_rows_sorted"])
def test_the_gradients_through_a_buffer_are_jaxs_own_of_the_reference(monkeypatch, function, form):
    """``_rows_combined``'s gradient is ``_rows_sorted``'s forward and the other
    way round; on a buffer each is what jax derives from the reference (the
    masked gather of every pair, and its transpose), over the live rows:
    a row past ``live`` is nobody's, and what its cotangent holds is zeroed
    by the layer's own select."""
    monkeypatch.setattr(moe, "rows_summed_by_segment", FORMS[form])
    first_rows, inverse, live, m = route("tokens_with_none_one_and_all_k_live")
    alive = (jnp.arange(m) < live)[:, None]
    if function == "_rows_combined":
        rows = rows_of(m, live, jnp.float32, 0.0)
        cot = jax.random.normal(jax.random.PRNGKey(1), (N, D), jnp.float32)
        got = jax.grad(lambda r: jnp.sum(
            moe._rows_combined(r, first_rows, inverse, K, live) * cot))(rows)
        want = jax.grad(lambda r: jnp.sum(parents(r, inverse, K, live) * cot))(rows)
        got = jnp.where(alive, got, 0)
    else:
        tokens = jax.random.normal(jax.random.PRNGKey(2), (N, D), jnp.float32)
        cot = rows_of(m, live, jnp.float32, jnp.nan)  # Megablox's d lhs past ``live``
        (got,) = jax.vjp(
            lambda t: moe._rows_sorted(t, first_rows, inverse, K, live), tokens
        )[1](cot)
        (want,) = jax.vjp(
            lambda t: jnp.where(alive, t[first_rows // K], 0), tokens
        )[1](jnp.nan_to_num(cot))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("live", [None, "given"], ids=["every_expert_held", "the_large_branch"])
def test_all_the_pairs_rows_are_gathered_as_they_were(monkeypatch, live):
    """``live is None`` (every expert held) and ``m == N k`` (the large branch of
    the layer's ``cond``) never reach the sum by segment: the gather of every
    pair, the form before PR 60, to the bit."""
    def never(*a, **kw):
        raise AssertionError("the buffer's form, with every pair's row at hand")

    monkeypatch.setattr(moe, "rows_summed_by_segment", never)
    first_rows, inverse, some, _ = route("tokens_with_none_one_and_all_k_live")
    order = jnp.argsort(inverse)
    rows = jax.random.normal(jax.random.PRNGKey(5), (N * K, D), jnp.bfloat16)
    if live is None:
        want = jnp.sum(rows[inverse].reshape(N, K, D), axis=1, dtype=jnp.float32)
        got = moe._sum_unsorted(rows, order, inverse, K)
    else:
        want = parents(rows, inverse, K, some)
        got = moe._sum_unsorted(rows, order, inverse, K, some)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_layer_says_whether_a_steps_rows_fit_its_buffer():
    """``"metrics"/moe_buffer_taken``: 1.0 where the step's live rows fit the
    buffer (the sum by segment ran), 0.0 where a bias sends every token to the
    held experts and the layer takes its whole ``N k`` branch; a layer that
    holds every expert has no buffer and sows nothing."""
    def sown(held, bias):
        layer = moe.DroplessMoE(
            num_experts=8, top_k=2, d_ff=16, score_func="sigmoid", bias_rate=1e-3,
            aux_weight=0.0, z_weight=0.0, held=held, dtype=jnp.float32,
        )
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 16), jnp.float32)
        variables = jax.jit(layer.init)(jax.random.PRNGKey(1), x)
        stats = {"router_bias": jnp.where(jnp.arange(8) < 2, bias, 0.0)}
        _, out = layer.apply(
            {"params": variables["params"], "batch_stats": stats}, x, mutable=["metrics"]
        )
        return out["metrics"]

    assert float(sown((0, 2), 0.0)["moe_buffer_taken"][0]) == 1.0
    crowded = sown((0, 2), 10.0)
    assert float(crowded["moe_buffer_taken"][0]) == 0.0
    assert float(crowded["moe_rows_dropped"][0]) == 0.0
    assert "moe_buffer_taken" not in sown(None, 0.0)
