"""Attention over a learned selection of keys, end to end on the CPU at toy
widths: the index scores, the selection (short rows, ties, exactly ``min(k, t +
1)`` a row), attention under the selection and the indexer's loss with their
gradients — each the plain ``jax.numpy`` form and the Pallas kernels in
interpret mode — against the benchmark's plain reference; the two statements
about which loss reaches which parameter; ``topk >= T`` as the ``"attention"``
layer; a ``TransformerLM`` of the layer against ``benchmark/reference/
sparse_lm.py`` through ``make_train_step``; the expert layer's shares under
softmax scores with renormalised weights; three equal position streams as
``rope()``; and what the older configurations' defaults still lower to."""

import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.families import sparse_lm as family
from benchmark.reference import sparse_lm as reference
from edl_tpu.models import ArchSpec, SparseAttentionSpec, TransformerLM
from edl_tpu.models import transformer as transformer_module
from edl_tpu.models.moe import DroplessMoE
from edl_tpu.models.transformer import LAYER_TYPES, rope
from edl_tpu.obs import trace as obs_trace
from edl_tpu.train import create_state, make_train_step

S = importlib.import_module("edl_tpu.ops.sparse_attention")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rehearsal(name):
    with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs", name + ".json")) as f:
        return json.load(f)


TOY = rehearsal("keye_vl_2_0_30b_a3b")


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    scale = max(np.max(np.abs(want)), 1e-12)
    assert np.max(np.abs(got - want)) / scale <= tol, np.max(np.abs(got - want)) / scale


# -- the operator alone -------------------------------------------------------

T, H, HKV, DH, J, DI, TOPK = 256, 4, 2, 32, 4, 16, 48
BLOCKS = ((64, 128), (128, 64))     # (fwd block_q, block_k), (bwd block_q, block_k)


@pytest.fixture(scope="module")
def small_tiles():
    """The kernels' module constants at sizes that give T = 256 several tiles
    each way (the real ones are one tile there)."""
    was = S._INDEX_BLOCKS, S._SELECT_ROWS, S._SELECT_CHUNK, S._TARGET_ROWS
    S._INDEX_BLOCKS, S._SELECT_ROWS, S._SELECT_CHUNK, S._TARGET_ROWS = (64, 64), 32, 128, 32
    yield
    S._INDEX_BLOCKS, S._SELECT_ROWS, S._SELECT_CHUNK, S._TARGET_ROWS = was


def operands(seed=0, tied=False):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = [(1, H, T, DH), (1, HKV, T, DH), (1, HKV, T, DH), (1, J, T, DI),
              (1, T, DI), (1, T, J)]
    q, k, v, iq, ik, iw = (jax.random.normal(key, s) for key, s in zip(keys, shapes))
    iw = 0.1 * iw
    if tied:  # coarse values: many equal scores a row, and at the threshold
        iq, ik, iw = jnp.round(iq * 2) / 2, jnp.round(ik * 2) / 2, jnp.round(iw * 8) / 8
    return q, k, v, iq, ik, iw


@pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied"])
def test_index_scores_kernel_against_the_reference_einsum(small_tiles, tied):
    _, _, _, iq, ik, iw = operands(1, tied)
    got = S._index_scores_kernels(iq[0], ik[0], iw[0], 64, 64, True)
    want = reference.index_scores(jnp.swapaxes(iq[0], 0, 1), ik[0], iw[0])
    causal = np.tril(np.ones((T, T), bool))
    _close(np.where(causal, got, 0), np.where(causal, want, 0), tol=1e-6)
    _close(S.index_scores_reference(iq[0], ik[0], iw[0]), want, tol=1e-6)


@pytest.mark.parametrize("topk", [1, 48, 200, T, 4 * T])
@pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied"])
def test_selection_keeps_exactly_min_k_t_plus_1_keys_a_row(small_tiles, tied, topk):
    """Rows shorter than k keep their whole prefix; a tie at the threshold goes
    to the lower key index; the bisection, ``lax.top_k`` in the program's plain
    form and the reference's blocked ``lax.top_k`` mark the same pairs."""
    _, _, _, iq, ik, iw = operands(2, tied)
    scores = S.index_scores_reference(iq[0], ik[0], iw[0])
    tau, last, _ = S._select_kernels(scores, topk, 32, 128, True)
    got = np.asarray(S.selection_mask(scores, tau, last)) != 0
    want, kth = reference.select(scores, topk)
    np.testing.assert_array_equal(got, np.asarray(want))
    np.testing.assert_array_equal(np.asarray(S.select_reference(scores, topk)), np.asarray(want))
    np.testing.assert_array_equal(got.sum(axis=1), np.minimum(topk, np.arange(T) + 1))
    assert not np.triu(got, 1).any()
    if tied and 1 < topk < T:
        assert int((np.asarray(last) < T).sum()) > 0   # the tie phase ran, and agreed
    row = T - 1  # by hand: the k-th largest of the last row's prefix
    assert float(kth[row]) == np.sort(np.asarray(scores[row]))[::-1][min(topk, T) - 1]


def _selected_lse(scores, mask):
    """The plain form of the select kernel's third output: the log-sum-exp of
    a row's scores over the keys ``mask`` keeps."""
    return jax.scipy.special.logsumexp(jnp.where(mask != 0, scores, -jnp.inf), axis=-1)


@pytest.mark.parametrize("topk", [1, 48, 200, T, 4 * T])
@pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied"])
def test_the_select_kernel_gives_the_log_sum_exp_of_the_keys_a_row_keeps(small_tiles, tied, topk):
    """Over exactly the selection (the causal test and the tie rule with it),
    scores of both signs, and two rows of all zeros, where every key ties at
    the threshold and the sum is the count of those kept."""
    _, _, _, iq, ik, iw = operands(2, tied)
    scores = S.index_scores_reference(iq[0], ik[0], iw[0])
    scores = scores.at[7].set(0.0).at[200].set(0.0)
    assert float(scores.min()) < 0 < float(scores.max())
    _, _, lse = S._select_kernels(scores, topk, 32, 128, True)
    assert lse.shape == (T,) and lse.dtype == jnp.float32
    _close(lse, _selected_lse(scores, S.select_reference(scores, topk)), tol=1e-6)
    for row in (7, 200):
        assert float(lse[row]) == pytest.approx(np.log(min(topk, row + 1)), rel=1e-6, abs=1e-6)


def test_a_tie_at_the_threshold_goes_to_the_lower_key_index(small_tiles):
    scores = jnp.zeros((T, T), jnp.float32).at[:, 5].set(1.0)   # one key above, all others tied
    tau, last, lse = S._select_kernels(scores, 4, 32, 128, True)
    got = np.asarray(S.selection_mask(scores, tau, last)) != 0
    assert got[100].nonzero()[0].tolist() == [0, 1, 2, 5]
    assert got[3].nonzero()[0].tolist() == [0, 1, 2, 3]
    assert got[4].nonzero()[0].tolist() == [0, 1, 2, 3]         # key 5 is not causal yet
    np.testing.assert_array_equal(got, np.asarray(S.select_reference(scores, 4)))
    # the kept tied keys alone count: three zeros and the one, not the row's 100 zeros
    assert float(lse[100]) == pytest.approx(np.log(3 + np.e), rel=1e-6)
    assert float(lse[4]) == pytest.approx(np.log(4), rel=1e-6)
    _close(lse, _selected_lse(scores, got), tol=1e-6)


def _objective(impl, args, kl_weight=3.0):
    def f(*a):
        if impl == "plain":
            o, kl, stats, _ = S.sparse_attention_reference(*a, TOPK)
        else:
            o, kl, stats, _ = S.sparse_attention(*a, TOPK, interpret=True, blocks=BLOCKS)
        return jnp.sum(o * jnp.cos(jnp.arange(DH))) + kl_weight * kl, (o, kl, stats)

    return jax.value_and_grad(f, argnums=tuple(range(6)), has_aux=True)(*args)


def _reference_objective(args, kl_weight=3.0):
    def f(q, k, v, iq, ik, iw):
        scores = reference.index_scores(jnp.swapaxes(iq[0], 0, 1), ik[0], iw[0])
        picked, _ = reference.select(jax.lax.stop_gradient(scores), TOPK)
        o, p = reference.selected_attention(
            jnp.swapaxes(q[0], 0, 1), jnp.swapaxes(k[0], 0, 1), jnp.swapaxes(v[0], 0, 1),
            picked,
        )
        kl = reference.index_kl(scores, picked, jax.lax.stop_gradient(p))
        o = jnp.swapaxes(o, 0, 1)[None]
        return jnp.sum(o * jnp.cos(jnp.arange(DH))) + kl_weight * kl, (o, kl)

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(f, argnums=tuple(range(6)), has_aux=True)(*args)


@pytest.fixture(scope="module")
def three_ways(small_tiles):
    args = operands(3)
    return _objective("plain", args), _objective("kernels", args), _reference_objective(args)


NAMES = ("q", "k", "v", "index_q", "index_k", "index_w")


@pytest.mark.parametrize("impl", ["plain", "kernels"])
@pytest.mark.parametrize("what", ["out", "index_kl", *("d_" + n for n in NAMES)])
def test_attention_over_the_selection_against_the_reference(three_ways, impl, what):
    """Forward, the indexer's loss and all six gradients of the program's
    plain form and of its kernels (interpret mode, several tiles each way)
    against ``benchmark/reference/sparse_lm.py``."""
    (_, (o, kl, _)), grads = three_ways[0 if impl == "plain" else 1]
    (_, (ref_o, ref_kl)), ref_grads = three_ways[2]
    if what == "out":
        _close(o, ref_o, tol=1e-5)
    elif what == "index_kl":
        _close(kl, ref_kl, tol=1e-5)
    else:
        i = NAMES.index(what[2:])
        _close(grads[i], ref_grads[i], tol=5e-5)


def test_the_kernels_report_the_selected_share_and_the_live_tiles(three_ways):
    (_, (_, _, plain)), _ = three_ways[0]
    (_, (_, _, stats)), _ = three_ways[1]
    pairs = sum(min(t + 1, TOPK) for t in range(T))
    assert float(stats["selected_share"]) == pytest.approx(pairs / (T * (T + 1) / 2))
    assert float(plain["selected_share"]) == pytest.approx(float(stats["selected_share"]))
    assert 0 < float(stats["tile_live"]) <= 1


def test_tile_live_counts_the_causal_tiles_that_hold_a_selected_pair():
    """A selection that keeps each row's newest 8 keys leaves only the tiles on
    the diagonal (and the one before a row block's first rows) live."""
    t = 256
    rows, cols = np.arange(t)[:, None], np.arange(t)[None, :]
    mask = jnp.asarray((cols <= rows) & (cols > rows - 8), jnp.int8)
    stats = S._stats(mask, 64, 64)
    assert float(stats["tile_live"]) == pytest.approx((4 + 3) / 10)
    assert float(S._stats(jnp.asarray(cols <= rows, jnp.int8), 64, 64)["tile_live"]) == 1.0


def test_the_masked_forward_survives_rows_whose_first_tiles_hold_no_key(small_tiles):
    """Only late keys selected: the first key tiles of every late row are
    empty, and the online softmax must come out as if they were never there."""
    q, k, v, *_ = operands(4)
    rows, cols = np.arange(T)[:, None], np.arange(T)[None, :]
    mask = jnp.asarray((cols <= rows) & (cols > rows - 3), jnp.int8)
    out, lse = S._sparse_forward(q[0], k[0], v[0], mask, DH ** -0.5, 64, 64, True)
    want, probs = S._masked_attention_reference(q[0], k[0], v[0], mask != 0, DH ** -0.5)
    _close(out, want, tol=1e-5)
    assert np.isfinite(np.asarray(lse)).all()


def test_shapes_the_kernels_cannot_tile_take_the_plain_form(monkeypatch):
    for name, real in (("_INDEX_BLOCKS", (512, 512)), ("_SELECT_ROWS", 128), ("_SELECT_CHUNK", 2048)):
        monkeypatch.setattr(S, name, real)   # whatever a fixture of this module left
    assert S._kernel_plan(16384, 128, 2) == {
        "fwd": (1024, 1024), "bwd": (1024, 1024), "index": (512, 512),
        "rows": 128, "chunk": 2048,
    }
    assert S._kernel_plan(100, 32, 4) is None            # no block divides it
    assert S._kernel_plan(16, 32, 4) is None             # under an int8 tile's 32 rows
    args = [a[:, :, :100] if a.ndim == 4 else a[:, :100] for a in operands(5)]
    out, kl, _, _ = S.sparse_attention(*args, 16, interpret=True)
    want, want_kl, _, _ = S.sparse_attention_reference(*args, 16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_a_traced_shape_leaves_one_dsa_shape_instant(small_tiles):
    obs_trace.get_tracer().reset_notes()
    ring = obs_trace.get_tracer()
    seen = lambda: [e["args"] for e in ring.to_events() if e["name"] == "dsa_shape"]  # noqa: E731
    before = len(seen())
    args = operands(6)
    for _ in range(2):  # the second trace of the shape notes nothing
        jax.eval_shape(
            lambda *a: S.sparse_attention(*a, TOPK, interpret=True, blocks=BLOCKS), *args
        )
    found = seen()[before:]
    assert len(found) == 1
    event = found[0]
    assert event["tq"] == T and event["topk"] == TOPK and event["select"] == "bisect"
    assert event["index_lse"] == "select"     # the indexer's row sums leave the select kernel
    assert event["index_heads"] == J and event["index_dim"] == DI
    assert event["score_bytes"] == T * T * 4 and event["mask_bytes"] == T * T
    # the target's schedule: the tile, every head in one grid step, two strips a tile
    assert event["target_blocks"] == [64, 64] and event["target_heads_step"] == H
    assert event["target_strip"] == [32, 64]
    # dL_I/dI is made by the loss's forward call and kept as its ten causal tiles of 64 x 64
    assert event["target_grad"] == "forward" and event["di_bytes"] == 10 * 64 * 64 * 4


def _unpacked(packed, block=64):
    """``dL_I/dI [T, T]`` from its causal tiles as the target kernel packs
    them (``S._packed_tile``), zero past the diagonal's tiles."""
    packed = np.asarray(packed)
    n = T // block
    assert packed.shape == (n * (n + 1) // 2 * block, block)
    whole = np.zeros((T, T), packed.dtype)
    for qi in range(n):
        for ki in range(qi + 1):
            at = int(S._packed_tile(qi, ki)) * block
            whole[qi * block:(qi + 1) * block, ki * block:(ki + 1) * block] = packed[at:at + block]
    return whole


def _target_case(h, hkv, seed):
    """Operands of the target kernel as the layer hands them over (the masked
    forward's own ``lse``), and the reference's ``L_I`` and ``dI``."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (h, T, DH))
    k, v = (jax.random.normal(key, (hkv, T, DH)) for key in keys[1:3])
    scores = jax.random.normal(keys[3], (T, T))
    picked = S.select_reference(scores, TOPK)
    mask = picked.astype(jnp.int8)
    scale = DH ** -0.5
    _, lse = S._sparse_forward(q, k, v, mask, scale, 64, 64, True)
    with jax.default_matmul_precision("highest"):
        target = jnp.mean(S._masked_attention_reference(q, k, v, picked, scale)[1], axis=0)
    loss = lambda s: S.index_kl_reference(s, picked, target)  # noqa: E731
    want_kl, want_d = jax.value_and_grad(loss)(scores)
    run = lambda grad_dtype=None: S._target_kernels(  # noqa: E731
        q, k, lse, mask, scores, _selected_lse(scores, mask), scale, 64, 64, True,
        grad_dtype,
    )
    return run, np.asarray(picked), want_kl, want_d * T


def test_the_targets_two_modes_give_the_same_rows_and_a_gradient_that_sums_to_nothing(small_tiles):
    """The value call and the ``dI`` call read the rows' KL bit for bit alike;
    ``dI`` is 0 off the selection and ``softmax - p`` adds up to 0 over a
    row's selection."""
    run, picked, _, _ = _target_case(H, HKV, 11)
    rows, none = run()
    rows_again, d = run(jnp.float32)
    assert none is None
    assert np.asarray(rows).tobytes() == np.asarray(rows_again).tobytes()
    d = _unpacked(d)                                    # dead tiles are not held at all
    assert not d[~picked].any()
    assert np.abs(d.sum(axis=1)).max() < 1e-5 and np.abs(d).max() > 1e-3


@pytest.mark.parametrize("mode", ["value", "grad"])
@pytest.mark.parametrize(
    "h, hkv", [(4, 4), (4, 2), (8, 1), (5, 1), (6, 3)],
    ids=["group1", "group2", "group8", "five_heads", "six_heads_in_pairs"],
)
def test_the_target_kernel_against_the_reference_by_group_and_mode(small_tiles, h, hkv, mode):
    """Every head in one grid step, two strips a tile: GQA groups of 1, 2 and 8
    heads a key head, and head counts that no power of two divides."""
    run, picked, want_kl, want_d = _target_case(h, hkv, 12 + h)
    rows, d = run(jnp.float32 if mode == "grad" else None)
    assert rows.shape == (T,)
    _close(jnp.mean(rows), want_kl, tol=1e-5)
    if mode == "grad":
        _close(np.where(picked, _unpacked(d), 0.0), want_d, tol=5e-5)


@pytest.mark.parametrize("n", [1, 4, 32])
def test_the_packed_index_map_gives_every_causal_tile_a_row_block_of_its_own(n):
    """``n (n + 1) / 2`` causal tiles fill as many row blocks with no gap, in
    the order the grids walk them, and a dead grid step (``ki > qi``) holds
    the row's last live tile: no block is fetched or written back for it."""
    at = lambda qi, ki: int(S._packed_tile(qi, ki))  # noqa: E731
    live = [at(qi, ki) for qi in range(n) for ki in range(qi + 1)]
    assert live == list(range(n * (n + 1) // 2))
    assert S._packed_rows(n * 64, 64) == len(live) * 64
    for qi in range(n):
        assert {at(qi, ki) for ki in range(qi + 1, n)} <= {at(qi, qi)}


def test_index_blocks_that_are_not_square_take_the_plain_form(small_tiles, monkeypatch):
    """The causal tiles of ``dL_I/dI`` are packed for square blocks: a plan
    whose two index blocks differ is a shape the kernels do not take."""
    assert S._kernel_plan(T, DH, 4, BLOCKS)["index"] == (64, 64)
    monkeypatch.setattr(S, "_INDEX_BLOCKS", (64, 128))
    assert S._kernel_plan(T, DH, 4, BLOCKS) is None


def test_a_layer_under_the_remat_policy_gives_the_value_and_gradients_it_gives_without(small_tiles):
    """``dL_I/dI`` kept by name and read in the backward is the array the
    forward wrote: the value and all six gradients under ``jax.checkpoint``
    with ``save_flash`` are those of the layer differentiated bare."""
    def layer(*a):
        o, kl, _, _ = S.sparse_attention(*a, TOPK, interpret=True, blocks=BLOCKS)
        return jnp.sum(o * jnp.cos(jnp.arange(DH))) + 3.0 * kl

    args = operands(10)
    argnums = tuple(range(6))
    want, want_grads = jax.jit(jax.value_and_grad(layer, argnums=argnums))(*args)
    kept = jax.checkpoint(layer, policy=transformer_module._remat_policy("save_flash"))
    got, grads = jax.jit(jax.value_and_grad(kept, argnums=argnums))(*args)
    _close(got, want, tol=1e-6)
    for g, w in zip(grads, want_grads):
        assert np.abs(np.asarray(w)).max() > 0
        _close(g, w, tol=1e-6)


def test_an_unpicked_key_whose_exponent_passes_float32_leaves_the_target_finite(small_tiles):
    """The heads' sum is masked once, at the close: an unpicked causal key
    whose ``q . k * scale - lse`` passes 89 makes that sum ``inf`` for its
    head, and the select must come before anything multiplies it."""
    q, k, v, iq, ik, iw = operands(8)
    picked = np.asarray(S.sparse_attention_reference(q, k, v, iq, ik, iw, TOPK)[3]["selection"][0]) != 0
    row = T - 3
    key = int(np.flatnonzero(~picked[row, :row])[0])
    along = jnp.zeros((DH,)).at[0].set(30.0)          # 30 * 30 * 32 ** -0.5 = 159
    q, k = q.at[0, 1, row].set(along), k.at[0, 0, key].set(along)
    _, lse = S._sparse_forward(q[0], k[0], v[0], jnp.asarray(picked, jnp.int8), DH ** -0.5, 64, 64, True)
    assert float(q[0, 1, row] @ k[0, 0, key]) * DH ** -0.5 - float(lse[1, row]) > 89
    args = (q, k, v, iq, ik, iw)
    (_, (_, kl, _)), grads = _objective("kernels", args)
    (_, (_, want_kl, _)), want = _objective("plain", args)
    _close(kl, want_kl, tol=1e-5)
    for got, ref in zip(grads[3:], want[3:]):          # dI reaches the indexer's three
        _close(got, ref, tol=5e-5)


def test_the_masked_kernels_keep_emitting_attn_tiles(small_tiles):
    obs_trace.get_tracer().reset_notes()
    ring = obs_trace.get_tracer()
    seen = lambda: [e["args"] for e in ring.to_events() if e["name"] == "attn_tiles"]  # noqa: E731
    before = len(seen())
    args = operands(6)
    jax.eval_shape(
        jax.grad(lambda *a: jnp.sum(
            S.sparse_attention(*a, TOPK, interpret=True, blocks=BLOCKS)[0].astype(jnp.float32)
        )), *args
    )
    found = {e["kernel"]: e for e in seen()[before:]}
    assert set(found) == {"sparse_fwd", "sparse_bwd"}
    assert found["sparse_fwd"]["tq"] == T and found["sparse_fwd"]["window"] is None
    assert (found["sparse_fwd"]["block_q"], found["sparse_fwd"]["block_k"]) == BLOCKS[0]
    assert found["sparse_bwd"]["acc_bytes"] == T * DH * 4
    assert 0 < found["sparse_fwd"]["dead"] < 0.5      # the tiles past the diagonal


# -- the layer in the model ---------------------------------------------------


def toy_model(kind="sparse_attention", topk=None, dtype=jnp.float32, layers=None):
    spec = family.sparse_spec(TOY)
    if topk is not None:
        spec = SparseAttentionSpec(spec.index_heads, spec.index_dim, topk, spec.loss_weight)
    layers = layers or TOY["num_hidden_layers"]
    return TransformerLM(
        dtype=dtype, vocab_size=TOY["vocab_size"], d_model=TOY["hidden_size"],
        num_heads=TOY["num_attention_heads"], num_kv_heads=TOY["num_key_value_heads"],
        num_layers=layers, d_ff=TOY["intermediate_size"], remat=True,
        norm_eps=TOY["rms_norm_eps"], qk_norm="head", moe=family.moe_spec(TOY),
        arch=ArchSpec(
            layer_types=(kind,) * layers, sparse_attention=spec,
            head_dim=TOY["head_dim"], rope_theta=float(TOY["rope_theta"]),
        ),
    )


def shaken(params, seed=7):
    """Every vector (a norm's scale or bias) off its start, so that a misplaced
    one shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 400))
    return jax.tree.map(
        lambda a: a + 0.2 * jax.random.normal(next(keys), a.shape) if a.ndim == 1 else a,
        params,
    )


@pytest.fixture(scope="module")
def toy():
    tokens = jax.random.randint(
        jax.random.PRNGKey(11), (2, TOY["train"]["seq_len"] + 1), 0, TOY["vocab_size"]
    )
    model = toy_model()
    params = shaken(jax.jit(model.init)(jax.random.PRNGKey(12), tokens[:, :-1])["params"])
    return model, params, tokens[:, :-1], tokens[:, 1:]


def _is_indexers(path):
    return "index_" in jax.tree_util.keystr(path)


def _language_loss(model, params, tokens, targets):
    logits = model.apply({"params": params}, tokens)
    return reference.cross_entropy(logits, targets)


def _indexer_loss(model, params, tokens):
    _, sown = model.apply({"params": params}, tokens, mutable=["losses"])
    return sum(
        jnp.sum(leaf) for path, leaf in jax.tree_util.tree_leaves_with_path(sown["losses"])
        if "dsa_index_kl" in jax.tree_util.keystr(path)
    )


def test_the_language_loss_gives_the_indexers_parameters_a_zero_gradient(toy):
    model, params, tokens, targets = toy
    grads = jax.jit(jax.grad(lambda p: _language_loss(model, p, tokens, targets)))(params)
    seen = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        if _is_indexers(path):
            seen += 1
            assert float(jnp.max(jnp.abs(leaf))) == 0.0, jax.tree_util.keystr(path)
        else:
            assert float(jnp.max(jnp.abs(leaf))) > 0.0, jax.tree_util.keystr(path)
    assert seen == 5 * TOY["num_hidden_layers"]   # q, k, w and the LayerNorm's two


def test_the_indexers_loss_gives_every_other_parameter_a_zero_gradient(toy):
    model, params, tokens, _ = toy
    grads = jax.jit(jax.grad(lambda p: _indexer_loss(model, p, tokens)))(params)
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        name = jax.tree_util.keystr(path)
        if _is_indexers(path):
            assert float(jnp.max(jnp.abs(leaf))) > 0.0, name
        else:
            assert float(jnp.max(jnp.abs(leaf))) == 0.0, name


def test_a_selection_of_every_causal_key_is_the_attention_layer(toy):
    _, params, tokens, _ = toy
    t = tokens.shape[1]
    dense_params = {
        name: dict(layer, attn={k: v for k, v in layer["attn"].items() if "index_" not in k})
        if name.startswith("layer_") else layer
        for name, layer in params.items()
    }
    want = jax.jit(toy_model("attention").apply)({"params": dense_params}, tokens)
    for topk in (t, 4 * t):
        got = jax.jit(toy_model(topk=topk).apply)({"params": params}, tokens)
        _close(got, want, tol=1e-6)
    short = jax.jit(toy_model(topk=t // 4).apply)({"params": params}, tokens)
    assert float(jnp.max(jnp.abs(short - want))) > 1e-3   # a real selection differs


def test_the_decode_path_refuses_a_selection(toy):
    assert "sparse_attention" in LAYER_TYPES
    model = toy_model()
    with pytest.raises(NotImplementedError, match="decode cache"):
        model.clone(decode=True, remat=False).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32)
        )


@pytest.fixture(scope="module")
def trained(toy):
    """One step of ``make_train_step`` on the toy, and the reference's
    objective and gradients at the same parameters under the program's own
    discrete decisions."""
    model, params, tokens, targets = toy
    state = create_state(
        model, jax.random.PRNGKey(12), np.zeros(tokens.shape, np.int32), optax.sgd(1.0)
    ).replace(params=params)
    job = family.build(TOY, tokens.shape[0], 0)
    new_state, metrics = make_train_step(job["loss"], donate=False)(state, (tokens, targets))
    logits, left = jax.jit(lambda p: model.apply(
        {"params": p}, tokens, mutable=["intermediates", "losses", "metrics"]
    ))(params)
    layers = range(TOY["num_hidden_layers"])
    chosen = jnp.stack([left["intermediates"]["layer_%d" % i]["moe"]["top_idx"][0] for i in layers])
    selections = jnp.stack([
        left["intermediates"]["layer_%d" % i]["attn"]["selection"][0] != 0 for i in layers
    ])
    with jax.default_matmul_precision("highest"):
        want_logits, losses, info = jax.jit(
            lambda p: reference.forward(TOY, p, tokens, chosen, selections)
        )(params)
        want_loss, want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(TOY, p, tokens, targets, chosen, selections)
        ))(params)
    # sgd at rate 1: the step's gradient is the parameters' change
    grads = jax.tree.map(lambda a, b: a - b, params, new_state.params)
    return {
        "logits": (logits, want_logits), "metrics": metrics, "losses": losses, "info": info,
        "loss": want_loss, "grads": (grads, want_grads), "left": left,
        "selections": selections,
    }


def test_the_model_against_the_reference_logits_and_both_losses(trained):
    _close(*trained["logits"], tol=2e-5)
    metrics, losses = trained["metrics"], trained["losses"]
    assert float(metrics["loss"]) == pytest.approx(float(trained["loss"]), rel=2e-5)
    want_aux = TOY["indexer_loss_weight"] * losses["index_kl"] + losses["load_balance"]
    assert float(metrics["aux_loss"]) == pytest.approx(float(want_aux), rel=2e-5)
    layers = TOY["num_hidden_layers"]
    assert float(metrics["dsa_index_kl"]) == pytest.approx(
        float(losses["index_kl"]) / layers, rel=2e-5
    )
    t = TOY["train"]["seq_len"]
    assert float(metrics["dsa_selected_share"]) == pytest.approx(
        family.selected_pairs(TOY) / family.causal_pairs(t)
    )
    assert 0 < float(metrics["dsa_tile_live"]) <= 1


def test_the_models_selection_is_the_references_own(trained):
    """In float32 on both sides no key is near enough its row's threshold to
    flip: the reference's own selection is the program's, layer by layer."""
    for layer, infos in enumerate(trained["info"]["selection"]):
        for b, info in enumerate(infos):
            np.testing.assert_array_equal(
                np.asarray(info["own"]), np.asarray(trained["selections"][layer, b])
            )
            assert int(info["flipped"]) == 0


PARAM_PATHS = [
    "layer_0/attn/q/kernel", "layer_0/attn/k/kernel", "layer_0/attn/v/kernel",
    "layer_0/attn/o/kernel", "layer_0/attn/q_norm/scale", "layer_0/attn/index_q/kernel",
    "layer_0/attn/index_k/kernel", "layer_0/attn/index_k_norm/scale",
    "layer_0/attn/index_k_norm/bias", "layer_0/attn/index_w/kernel",
    "layer_1/attn/index_q/kernel", "layer_1/attn/index_w/kernel",
    "layer_0/moe/router/kernel", "layer_0/moe/gate", "layer_1/moe/down",
    "layer_0/ln1/scale", "layer_1/ln2/scale", "embed/embedding", "lm_head/kernel",
]


@pytest.mark.parametrize("path", PARAM_PATHS)
def test_the_models_gradients_against_the_references(trained, path):
    got, want = trained["grads"]
    for key in path.split("/"):
        got, want = got[key], want[key]
    assert float(jnp.max(jnp.abs(want))) > 0
    _close(got, want, tol=2e-4)


# -- positions ---------------------------------------------------------------


@pytest.mark.parametrize("width, sections", [(128, [16, 24, 24]), (64, [16, 24, 24]), (16, [2, 3, 3])])
def test_three_equal_position_streams_are_the_plain_rotation(width, sections):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3, width), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(40)[None, :], (2, 40))
    got = reference.rotate(x, jnp.stack([positions] * 3), 1e7, sections)
    _close(got, rope(x, positions, 1e7), tol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(reference.text_positions(jnp.zeros((2, 40), jnp.int32))[1]),
        np.asarray(positions),
    )


def test_streams_that_differ_turn_their_own_frequencies_only():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 1, 128), jnp.float32)
    same = jnp.broadcast_to(jnp.arange(8)[None, None, :], (3, 1, 8))
    moved = same.at[1].add(5)        # the height stream alone
    a = reference.rotate(x, same, 1e7, [16, 24, 24])
    b = reference.rotate(x, moved, 1e7, [16, 24, 24])
    differs = np.abs(np.asarray(a - b))[0, :, 0].max(axis=0) > 0
    stream = np.asarray(reference.stream_of_frequency(64, [16, 24, 24]))
    assert stream.tolist() == [0] * 16 + [1] * 24 + [2] * 24
    np.testing.assert_array_equal(differs[:64], stream == 1)
    np.testing.assert_array_equal(differs[64:], stream == 1)


# -- the expert layer's share under softmax scores ---------------------------

E, K, D, F = 8, 3, 32, 16
LAYER = {
    "num_experts": E, "num_experts_per_tok": K, "norm_topk_prob": True,
    "router_aux_loss_coef": 0.01,
}


def _layer(held):
    return DroplessMoE(
        num_experts=E, top_k=K, d_ff=F, norm_topk_prob=True, aux_weight=0.01,
        z_weight=0.0, score_func="softmax", held=held, dtype=jnp.float32,
    )


@pytest.mark.parametrize(
    "sizes", [(8,), (4, 4), (2, 6), (1, 7), (2, 2, 2, 2), (1,) * 8],
    ids=lambda sizes: "x".join(map(str, sizes)),
)
def test_the_shares_of_a_softmax_layer_add_up_and_count_the_auxiliary_loss_once(sizes):
    """Every chip routes over all E experts with softmax scores and weights
    renormalised over the top-k, and computes what its own experts give: the
    parts add up to the uncut layer; the load-balancing loss, over all E
    experts' counts, is the same number on every chip (the objective of the
    deployment counts it once, not once a chip)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, D), jnp.float32)
    params = jax.jit(_layer(None).init)(jax.random.PRNGKey(2), x)["params"]
    with jax.default_matmul_precision("highest"):
        uncut, balance, _ = reference.mixture(
            dict(LAYER, share={"router_experts": E, "experts_first": 0}),
            params, x.reshape(-1, D),
        )
        total, first = jnp.zeros_like(uncut), 0
        for count in sizes:
            here = dict(params, **{
                bank: params[bank][first:first + count] for bank in ("gate", "up", "down")
            })
            part, sown = _layer((first, count)).apply(
                {"params": here}, x, mutable=["losses", "metrics"]
            )
            assert float(sown["metrics"]["moe_rows_dropped"][0]) == 0
            assert float(sown["losses"]["load_balance"][0]) == pytest.approx(float(balance), rel=1e-5)
            want, _, _ = reference.mixture(
                dict(LAYER, num_experts=count,
                     share={"router_experts": E, "experts_first": first}),
                here, x.reshape(-1, D),
            )
            _close(part.reshape(-1, D), want, tol=1e-5)
            total = total + part.reshape(-1, D)
            first += count
    _close(total, uncut, tol=1e-5)


# -- what the older configurations still are ----------------------------------


def test_the_new_fields_default_to_what_ran_before():
    arch = ArchSpec()
    assert arch.sparse_attention is None and arch.layer_types is None
    assert transformer_module.Attention(num_heads=2).sparse is None
    assert SparseAttentionSpec() == SparseAttentionSpec(16, 64, 2048, 1.0)
    assert set(transformer_module.DSA_SCOPES) == {
        "dsa_index", "dsa_select", "attn_sparse", "dsa_target"
    }


@pytest.mark.parametrize("kind", ["attention", "sliding_attention"])
def test_an_attention_layer_of_the_older_kinds_lowers_without_the_selection(kind):
    model = TransformerLM(
        vocab_size=64, d_model=32, num_heads=4, num_layers=1, d_ff=64,
        arch=ArchSpec(layer_types=(kind,), sliding_window=8),
    )
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    assert "index_q" not in params["params"]["layer_0"]["attn"]
    assert "losses" not in params and "metrics" not in params
    text = jax.jit(model.apply).lower(params, tokens).as_text()
    assert "dsa_" not in text and "top_k" not in text


@pytest.mark.parametrize("rms", [1.0, 0.5])
def test_the_familys_start_changes_the_embedding_tables_first_values_alone(rms):
    """The benchmark's stand-in for trained weights is on the benchmark's side:
    the family's model is the program's class, draws what that class draws but
    an embedding table ``rms * d_model ** 0.5`` times as large, and applies as
    the class does."""
    config = dict(TOY, train=dict(TOY["train"], start={"embedding_rms": rms}))
    started = family.build(config, 1, 0)["model"]
    assert isinstance(started, TransformerLM)
    assert not any("init" in f.name for f in dataclasses.fields(ArchSpec))
    plain = TransformerLM(**{
        f.name: getattr(started, f.name) for f in dataclasses.fields(TransformerLM)
        if f.name not in ("parent", "name")
    })
    tokens = jnp.zeros((1, 16), jnp.int32)
    got = jax.jit(started.init)(jax.random.PRNGKey(0), tokens)["params"]
    want = jax.jit(plain.init)(jax.random.PRNGKey(0), tokens)["params"]
    table = got["embed"].pop("embedding")
    drawn = want["embed"].pop("embedding")
    _close(table, drawn * rms * TOY["hidden_size"] ** 0.5, tol=1e-6)
    assert float(jnp.sqrt(jnp.mean(table ** 2))) == pytest.approx(rms, rel=0.05)
    assert jax.tree.all(jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), got, want))
    got["embed"]["embedding"] = table
    _close(
        jax.jit(started.apply)({"params": got}, tokens),
        jax.jit(plain.apply)({"params": got}, tokens), tol=0,
    )


def test_the_remat_policy_keeps_the_selections_thresholds():
    assert S.REMAT_NAMES == ("dsa_select", "dsa_di")
    for name in ("save_flash", "save_flash_qkv"):
        assert transformer_module._remat_policy(name) is not None
    assert transformer_module._remat_policy("full") is None


def _equations(jaxpr, inside=()):
    """Every equation with the equations it sits under, kernels' bodies left
    out."""
    for eqn in jaxpr.eqns:
        yield inside, eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _equations(sub, inside + (eqn,))


def _live_equations(fn, *args):
    """:func:`_equations` of ``fn(*args)``'s jaxpr after dead-code elimination."""
    from jax._src.interpreters import partial_eval as pe

    traced = jax.make_jaxpr(fn)(*args).jaxpr
    live, _ = pe.dce_jaxpr(traced, [True] * len(traced.outvars))
    return list(_equations(live))


def test_under_the_remat_policy_the_bisection_runs_once_and_no_pass_of_xla_reads_the_scores_for_the_loss(small_tiles):
    """A sparse layer's value and gradient under ``jax.checkpoint`` with
    ``save_flash``, after dead-code elimination: the select kernel once (its
    three rows are saved names, so the recomputation drops it), the target
    kernel once (the value and ``dI`` from one walk: ``dI``'s causal tiles
    are a saved name too, so the recomputation drops that call as well), and
    under ``dsa_target`` no reduction of a ``[T, T]`` operand outside a
    kernel: the indexer's row sums are the select kernel's."""
    def layer(*a):
        o, kl, _, _ = S.sparse_attention(*a, TOPK, interpret=True, blocks=BLOCKS)
        return jnp.sum(o * jnp.cos(jnp.arange(DH))) + kl

    step = jax.value_and_grad(
        jax.checkpoint(layer, policy=transformer_module._remat_policy("save_flash")),
        argnums=tuple(range(6)),
    )
    kernels, passes = [], []
    for inside, eqn in _live_equations(step, *operands(9)):
        scopes = "/".join(str(e.source_info.name_stack) for e in inside + (eqn,))
        if eqn.primitive.name == "pallas_call":
            kernels.append(eqn.params["jaxpr"].debug_info.func_name)
        elif "dsa_target" in scopes and eqn.primitive.name.startswith("reduce_"):
            passes += [v.aval.shape for v in eqn.invars if v.aval.shape == (T, T)]
    assert kernels.count("_select_kernel") == 1
    assert kernels.count("_target_kernel") == 1
    assert kernels.count("_index_bwd_kernel") == 1
    assert kernels.count("_index_fwd_kernel") == 2      # the scores are made again, as before
    assert passes == []


def _pallas_calls(fn, *args):
    """``(kernel body's name, its results' shapes)`` of every Pallas call that
    survives dead-code elimination in ``fn(*args)``."""
    return [
        (eqn.params["jaxpr"].debug_info.func_name, [v.aval.shape for v in eqn.outvars])
        for _, eqn in _live_equations(fn, *args) if eqn.primitive.name == "pallas_call"
    ]


def test_a_forward_that_nothing_differentiates_writes_no_gradient_of_the_scores(small_tiles):
    """The loss's primal calls the target kernel for the rows' KL alone: one
    output, nothing the size of ``[T, T]`` or of its causal tiles; under a
    gradient the same call has the tiles as its second output, once."""
    layer = lambda *a: S.sparse_attention(*a, TOPK, interpret=True, blocks=BLOCKS)[:2]  # noqa: E731
    target = [shapes for name, shapes in _pallas_calls(layer, *operands(9)) if name == "_target_kernel"]
    assert target == [[(T, 1)]]
    loss = lambda *a: layer(*a)[1]  # noqa: E731
    calls = _pallas_calls(jax.grad(loss, argnums=(3, 4, 5)), *operands(9))
    target = [shapes for name, shapes in calls if name == "_target_kernel"]
    assert target == [[(T, 1), (10 * 64, 64)]]
    assert [name for name, _ in calls].count("_index_bwd_kernel") == 1
