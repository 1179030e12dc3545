"""The block-diffusion training step: ``ops/attention.py``'s third mask kind
(the rule, the kernels' spans in interpret mode, the census of their tiles),
``TransformerLM`` under ``ArchSpec.block_diffusion`` (what leaks and what does
not, the model against the plain reference of
``benchmark/reference/block_diffusion_lm.py``), the forward process of
``data/block_diffusion.py``, the loss head of ``train/step.py``, the eight
shares of a layer, and every refusal. Each comparison with the reference is
computed once a module, as one jitted program (``conftest.py``)."""

import functools
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.families import block_diffusion_lm as family
from benchmark.reference import block_diffusion_lm as reference
from edl_tpu.data.block_diffusion import noise_draws, noised, noised_batch
from edl_tpu.models import ArchSpec, BlockDiffusionSpec, TransformerLM
from edl_tpu.models.moe import DroplessMoE
from edl_tpu.models.transformer import Attention
from edl_tpu.obs import trace as obs_trace
from edl_tpu.train.step import create_state, make_block_diffusion_loss, make_train_step

A = importlib.import_module("edl_tpu.ops.attention")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs", "sdar_30b_a3b.json")) as f:
    TOY = json.load(f)


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    scale = max(np.max(np.abs(want)), 1e-12)
    assert np.max(np.abs(got - want)) / scale <= tol


# -- the rule -----------------------------------------------------------------


def by_hand(length, block):
    """Visible(i, j) written out position by position."""
    seen = np.zeros((2 * length, 2 * length), bool)
    for i in range(2 * length):
        for j in range(2 * length):
            clean_i, clean_j = i < length, j < length
            block_i, block_j = (i % length) // block, (j % length) // block
            if clean_i:
                seen[i, j] = clean_j and block_j <= block_i
            elif clean_j:
                seen[i, j] = block_j < block_i       # never its own block's answers
            else:
                seen[i, j] = block_j == block_i      # its own block, both ways
    return seen


@pytest.mark.parametrize("length,block", [(8, 2), (16, 4), (24, 4), (12, 3), (24, 6)])
def test_the_mask_is_the_definition_written_out_by_hand(length, block):
    t = 2 * length
    got = A._sees(np.arange(t)[:, None], np.arange(t)[None, :], None, (length, block))
    want = by_hand(length, block)
    assert (np.asarray(got) == want).all()
    assert want.sum() == length * (length + block)           # the family's pair count
    assert not want[:length, length:].any()                  # clean rows see no noised key
    ref = reference.visible(np.arange(t)[:, None], np.arange(t)[None, :], length, block)
    assert (np.asarray(ref) == want).all()
    dense = A._dense_causal_mask(jnp.zeros((t, t)), None, (length, block))
    assert ((np.asarray(dense) == 0) == want).all()


# -- the kernels, in the interpreter ------------------------------------------

# (L, B, block_q, block_k): the clean diagonal, the strict boundary of a noised
# row's clean keys and the own-block tile fall in different tiles; GQA 4 : 2
KERNEL_CASES = {
    "square": (64, 4, 16, 16), "tall": (64, 4, 32, 16), "wide": (64, 4, 16, 32),
    "blocks_of_6": (48, 6, 24, 12), "one_tile_a_half": (32, 4, 32, 32),
}


@functools.lru_cache(maxsize=None)
def kernel_against_dense(case, pair=False):
    length, block, bq, bk = KERNEL_CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(keys[0], (1, 4, 2 * length, 16), jnp.float32)
    k = jax.random.normal(keys[1], (1, 2, 2 * length, 16), jnp.float32)
    v = jax.random.normal(keys[2], (1, 2, 2 * length, 16), jnp.float32)
    w = jax.random.normal(keys[3], (1, 4, 2 * length, 16), jnp.float32)
    bd = (length, block)

    def both(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return dict(zip(("out", "dq", "dk", "dv"), (out, *vjp(w))))

    capacity = A._vmem_capacity
    if pair:  # a head whose dq does not fit the chip: the dq / dkv pair
        A._vmem_capacity = lambda: 0
    try:
        got = both(lambda q, k, v: A.flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk, block_diffusion=bd
        ))
    finally:
        A._vmem_capacity = capacity
    want = both(lambda q, k, v: A.attention_reference(
        q, k, v, causal=True, block_diffusion=bd
    ))
    return got, want


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_kernels_match_the_dense_reference_under_the_mask(case, what):
    got, want = kernel_against_dense(case)
    _close(got[what], want[what], tol=2e-5)


@pytest.mark.parametrize("what", ["dq", "dk", "dv"])
def test_the_dq_dkv_pair_knows_the_mask_too(what):
    got, want = kernel_against_dense("wide", pair=True)
    _close(got[what], want[what], tol=2e-5)


def test_the_kernels_ran_and_said_what_they_walked():
    obs_trace.get_tracer().reset_notes()
    kernel_against_dense.cache_clear()
    kernel_against_dense("square")
    notes = [args for name, args in obs_trace.get_tracer().notes() if name == "attn_tiles"]
    assert {n["kernel"] for n in notes} == {"flash2_fwd", "flash2_bwd"}
    for note in notes:
        assert note["mask"] == "block_diffusion" and (note["length"], note["block"]) == (64, 4)
        assert note["path"] == "kernel" and "why" not in note
        assert note["visible"] == pytest.approx(64 * 68 / 128 ** 2)
        assert note["interior"] + note["edge"] >= note["visible"]


def test_off_the_tpu_the_route_notes_the_walk_the_shape_would_get():
    """One source for the walk: the dense reference's call leaves the same
    ``attn_tiles`` census, as a plan (``path`` plain, ``why`` backend)."""
    obs_trace.get_tracer().reset_notes()
    bd, t = (64, 4), 128
    q = jnp.zeros((1, 2, t, 16), jnp.float32)
    A.attention(q, q, q, causal=True, block_diffusion=bd)
    notes = {a["kernel"]: a for n, a in obs_trace.get_tracer().notes() if n == "attn_tiles"}
    assert set(notes) == {"flash2_fwd", "flash2_bwd"}
    for kernel, kind, side in (("flash2_fwd", "fwd", "kv"), ("flash2_bwd", "bwd", "q")):
        note, blocks = notes[kernel], A._flash2_blocks(kind, t, t, None, None, bd)
        assert (note["path"], note["why"], note["mask"]) == ("plain", "backend", "block_diffusion")
        assert (note["block_q"], note["block_k"]) == blocks
        census = A.tile_census(t, t, *blocks, True, None, side, bd)
        assert note["interior"] + note["edge"] == pytest.approx(census["interior"] + census["edge"])
    obs_trace.get_tracer().reset_notes()
    A.attention(q[:, :, :16], q[:, :, :16], q[:, :, :16], causal=True, block_diffusion=(8, 8))
    assert not [n for n, _ in obs_trace.get_tracer().notes() if n == "attn_tiles"]   # no blocks tile it
    obs_trace.get_tracer().reset_notes()
    A.attention(q, q, q, causal=True)
    assert not [n for n, _ in obs_trace.get_tracer().notes() if n == "attn_tiles"]   # another mask: as it was


# -- the census ---------------------------------------------------------------


@pytest.mark.parametrize("side", ["kv", "q"])
@pytest.mark.parametrize("length,block,bq,bk", [
    (64, 4, 16, 16), (64, 4, 32, 16), (64, 4, 16, 32), (128, 4, 32, 64), (64, 8, 16, 16),
    (48, 6, 24, 12),
])
def test_tile_census_against_a_count_by_brute_force(length, block, bq, bk, side):
    t = 2 * length
    bd = (length, block)
    assert A._spans_fit(bq, bk, t, t, None, side, bd)
    seen = by_hand(length, block).reshape(t // bq, bq, t // bk, bk)
    some, every = seen.any(axis=(1, 3)), seen.all(axis=(1, 3))
    tile = bq * bk / t ** 2
    census = A.tile_census(t, t, bq, bk, True, None, side, bd)
    assert census["interior"] == pytest.approx(every.sum() * tile)
    assert census["edge"] == pytest.approx((some & ~every).sum() * tile)
    assert census["dead"] == pytest.approx((~some).sum() * tile)
    # every step of the walk that is live holds a tile with a visible pair,
    # each such tile once, and the steps left over hold the last one again
    own, other = (bq, bk) if side == "kv" else (bk, bq)
    steps = A._bd_steps(bd, bq, bk)[side != "kv"]
    for i in range(t // own):
        runs = A._bd_runs(A._bd_seen(np.int64(i), own, bd, side, np), other, np)
        held = [A._bd_block(runs, np.int64(s), np) for s in range(steps)]
        live = [int(b) for b, on in held if on]
        want = np.flatnonzero(some[i] if side == "kv" else some[:, i])
        assert live == list(want)
        assert all(int(b) == live[-1] for b, on in held if not on)


def test_the_cells_shape_walks_under_a_third_of_the_rectangle():
    """``interior + edge`` at L = 8192, B = 4 with the blocks the kernels get:
    0.3125 of the 2 L x 2 L rectangle where 0.2501 is visible and a causal
    walk reads above 0.5."""
    t, bd = 16384, (8192, 4)
    for kind, side in (("fwd", "kv"), ("bwd", "q"), ("dq", "kv")):
        blocks = A._flash2_blocks(kind, t, t, None, None, bd)
        assert A._spans_fit(*blocks, t, t, None, side, bd)
        census = A.tile_census(t, t, *blocks, True, None, side, bd)
        assert census["interior"] + census["edge"] <= 0.35
        assert census["interior"] + census["edge"] >= 8192 * 8196 / t ** 2
    causal = A.tile_census(t, t, 1024, 1024, True)
    assert causal["interior"] + causal["edge"] > 0.5
    assert A._flash2_blocks("fwd", t, t, None, None, bd) == (1024, 1024)
    # a block divides a half, never the two halves together
    assert A._flash2_blocks("fwd", 96, 96, None, None, (48, 4)) == (48, 48)
    assert not A._spans_fit(32, 32, 96, 96, None, "kv", (48, 4))
    assert not A._spans_fit(4, 16, 64, 64, None, "kv", (32, 4))   # two blocks at least


# -- the model: what leaks and what does not -----------------------------------

LENGTH, BLOCK, MASK = 16, 4, 31


@pytest.fixture(scope="module")
def dense_lm():
    """A dense model under the spec in float32 (no expert layer: an expert
    layer's auxiliary counts aside, it mixes no positions), its parameters, and
    a jitted ``tokens -> (logits, the last block's stream)``."""
    model = TransformerLM(
        vocab_size=32, d_model=32, num_heads=4, num_kv_heads=2, num_layers=2, d_ff=64,
        dtype=jnp.float32, qk_norm="head",
        arch=ArchSpec(head_dim=8, block_diffusion=BlockDiffusionSpec(BLOCK, MASK)),
    )
    x0 = np.random.default_rng(0).integers(0, MASK, (1, LENGTH))
    tokens, _ = noised_batch(x0, 5, 0, BLOCK, MASK, 0.3)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]

    @jax.jit
    def run(tokens):
        logits, state = model.apply(
            {"params": params}, tokens, capture_intermediates=lambda m, _: m.name == "layer_1"
        )
        return logits, state["intermediates"]["layer_1"]["__call__"][0]

    return tokens, run


@pytest.mark.parametrize("position", [0, 5, 11, 15])
def test_a_clean_token_reaches_no_noised_logit_of_its_own_or_an_earlier_block(dense_lm, position):
    tokens, run = dense_lm
    logits, stream = run(tokens)
    changed = tokens.copy()
    changed[0, position] = (tokens[0, position] + 7) % MASK
    logits2, stream2 = run(changed)
    through = (position // BLOCK + 1) * BLOCK       # noised positions of blocks <= b(i)
    assert np.array_equal(np.asarray(logits[0, :through]), np.asarray(logits2[0, :through]))
    if through < LENGTH:  # and it does reach the later blocks
        assert np.abs(np.asarray(logits[0, through:] - logits2[0, through:])).max() > 0
    # in the clean half it reaches its own block on, and nothing before
    first = position // BLOCK * BLOCK
    assert np.array_equal(np.asarray(stream[0, :first]), np.asarray(stream2[0, :first]))


@pytest.mark.parametrize("position", [0, 6, 9, 15])
def test_a_noised_token_reaches_its_own_block_and_no_clean_stream(dense_lm, position):
    tokens, run = dense_lm
    logits, stream = run(tokens)
    changed = tokens.copy()
    changed[0, LENGTH + position] = (tokens[0, LENGTH + position] + 3) % MASK
    logits2, stream2 = run(changed)
    own = slice(position // BLOCK * BLOCK, (position // BLOCK + 1) * BLOCK)
    moved = np.abs(np.asarray(logits - logits2))[0].max(axis=-1)
    assert (moved[own] > 0).all()
    moved[own] = 0
    assert (moved == 0).all()
    assert np.array_equal(np.asarray(stream[0, :LENGTH]), np.asarray(stream2[0, :LENGTH]))


def test_the_model_reads_positions_that_repeat_and_scores_the_noised_half(dense_lm):
    tokens, run = dense_lm
    logits, stream = run(tokens)
    assert logits.shape == (1, LENGTH, 32) and stream.shape == (1, 2 * LENGTH, 32)
    # a noised copy that masks nothing, in the first block: its rows see what the
    # clean rows of that block see less their own answers; with everything equal
    # but the mask, the two halves' first-block streams differ
    assert not np.allclose(np.asarray(stream[0, :BLOCK]), np.asarray(stream[0, LENGTH:LENGTH + BLOCK]))
    notes = [a for n, a in obs_trace.get_tracer().notes() if n == "block_diffusion_shape"]
    assert any(
        a["length"] == LENGTH and a["block"] == BLOCK and a["positions"] == 2 * LENGTH
        and a["head_rows"] == LENGTH and a["logit_bytes"] == 4 * LENGTH * 32
        for a in notes
    )
    routes = [a for n, a in obs_trace.get_tracer().notes() if n == "attn_route"]
    assert any(a.get("mask") == "block_diffusion" and a["length"] == LENGTH for a in routes)


# -- the model against the plain reference ------------------------------------

DRAWN = dict(
    family.as_drawn(TOY), train=dict(family.as_drawn(TOY)["train"], compute_dtype="float32"),
)


def _paths(tree):
    return [
        "/".join(str(k.key) for k in path)
        for path, _ in jax.tree_util.tree_leaves_with_path(tree)
    ]


PARAM_PATHS = _paths(jax.eval_shape(
    lambda: family.build(DRAWN, 1, 0)["model"].init(
        jax.random.PRNGKey(0), np.zeros((1, 2 * TOY["train"]["seq_len"]), np.int32)
    )["params"]
))


@pytest.fixture(scope="module")
def toy_twin():
    """The toy twin in float32, three steps at a rate that moves every scale off
    1, then the program's and the reference's logits, losses and gradients on a
    fourth batch, each side one jitted program."""
    job = family.build(DRAWN, 1, 0)
    state = create_state(
        job["model"], jax.random.PRNGKey(0), job["sample_input"], optax.adamw(1e-2)
    )
    step = make_train_step(job["loss"], donate=False)
    pool = family.host_batches(DRAWN, 1, 0, n_batches=4)
    for batch in pool[:3]:
        state, metrics = step(state, batch)
    tokens, (labels, weights) = pool[3]
    layers = range(DRAWN["num_hidden_layers"])

    def program_loss(params):
        logits, sown = state.apply_fn(
            {"params": params}, tokens, mutable=["losses", "intermediates"]
        )
        aux = sum(jnp.sum(leaf) for leaf in jax.tree.leaves(sown["losses"]))
        routers = jnp.stack([
            sown["intermediates"]["layer_%d" % i]["moe"]["router_logits"][0] for i in layers
        ])
        return job["loss"](logits, (labels, weights))[0] + aux, (logits, routers)

    def plain_loss(params, config=DRAWN, forward=reference.forward):
        logits, info = forward(config, params, tokens)
        loss = reference.weighted_cross_entropy(logits, labels, weights) + info["load_balance"]
        return loss, (logits, info["router_logits"])

    with jax.default_matmul_precision("highest"):
        (got_loss, (got_logits, got_routers)), got_grads = jax.jit(
            jax.value_and_grad(program_loss, has_aux=True)
        )(state.params)
        (want_loss, (want_logits, want_routers)), want_grads = jax.jit(
            jax.value_and_grad(plain_loss, has_aux=True)
        )(state.params)
        also = jax.jit(
            lambda p: reference.loss(DRAWN, p, tokens, labels, weights)
        )(state.params)
    return {
        "metrics": metrics, "sown": state.sown, "params": state.params,
        "batch": (tokens, labels, weights), "plain_loss": plain_loss,
        "got": {"logits": got_logits, "loss": got_loss, "routers": got_routers,
                "grads": got_grads},
        "want": {"logits": want_logits, "loss": want_loss, "routers": want_routers,
                 "grads": want_grads, "loss_fn": also},
    }


@pytest.mark.parametrize("what", ["logits", "loss", "routers"])
def test_the_toy_twin_matches_the_reference(toy_twin, what):
    _close(toy_twin["got"][what], toy_twin["want"][what])
    if what == "loss":
        _close(toy_twin["want"]["loss_fn"], toy_twin["want"]["loss"], tol=1e-6)
    if what == "logits":
        assert toy_twin["got"]["logits"].shape == (1, TOY["train"]["seq_len"], TOY["vocab_size"])


@pytest.mark.parametrize("path", PARAM_PATHS)
def test_every_parameters_gradient_matches_the_reference(toy_twin, path):
    def leaf(tree):
        for key in path.split("/"):
            tree = tree[key]
        return tree

    want = leaf(toy_twin["want"]["grads"])
    assert float(jnp.max(jnp.abs(want))) > 0
    _close(leaf(toy_twin["got"]["grads"]), want, tol=1e-3)


def test_the_step_reports_the_loss_heads_gauges(toy_twin):
    metrics = toy_twin["metrics"]
    assert {"bd_masked_share", "bd_masked_ce", "accuracy", "aux_loss", "loss"} <= set(metrics)
    assert 0.0 < float(metrics["bd_masked_share"]) < 1.0
    assert make_block_diffusion_loss().gauges == ("bd_masked_share", "bd_masked_ce")


@pytest.mark.parametrize("fault", [
    "reads_its_own_blocks_answers", "plain_causal", "no_one_over_t", "bfloat16_routers",
])
def test_a_wrong_reference_fails_the_comparison(toy_twin, fault, monkeypatch):
    """What the comparison is worth: a reference whose noised rows read their
    own block's clean tokens, one whose mask is plain causal, a loss without
    its ``1 / t``, and routers in bfloat16 each read far outside the limits the
    sound comparison keeps (2e-4 here; the family's on the chip)."""
    tokens, labels, weights = toy_twin["batch"]
    params, want = toy_twin["params"], toy_twin["want"]

    if fault == "reads_its_own_blocks_answers":
        def visible(i, j, length, block):
            clean_i, clean_j = i < length, j < length
            block_i, block_j = (i % length) // block, (j % length) // block
            return jnp.where(
                clean_i, clean_j & (block_j <= block_i),
                (clean_j & (block_j <= block_i)) | (~clean_j & (block_j == block_i)),
            )
        monkeypatch.setattr(reference, "visible", visible)
    elif fault == "plain_causal":
        monkeypatch.setattr(reference, "visible", lambda i, j, length, block: j <= i)
    elif fault == "bfloat16_routers":
        def coarse(params):
            params = dict(params)
            for name in list(params):
                if name.startswith("layer_"):
                    layer = dict(params[name])
                    moe = dict(layer["moe"])
                    moe["router"] = jax.tree.map(
                        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), moe["router"]
                    )
                    layer["moe"] = moe
                    params[name] = layer
            return params
    with jax.default_matmul_precision("highest"):
        if fault == "no_one_over_t":
            logits, _ = jax.jit(lambda p: reference.forward(DRAWN, p, tokens))(params)
            flat = reference.weighted_cross_entropy(logits, labels, (weights > 0).astype(jnp.float32))
            sound = reference.weighted_cross_entropy(logits, labels, weights)
            assert abs(float(flat) - float(sound)) / float(sound) > 0.05
            return
        if fault == "bfloat16_routers":
            _, (_, routers) = jax.jit(toy_twin["plain_loss"])(coarse(params))
            off = float(jnp.max(jnp.abs(routers - want["routers"])) / jnp.max(jnp.abs(want["routers"])))
            assert off > 1e-3                     # the sound comparison keeps 2e-4
            return
        # not the fixture's jit: that traced the sound mask
        _, (logits, _) = jax.jit(lambda p: toy_twin["plain_loss"](p))(params)
    off = float(jnp.max(jnp.abs(logits - want["logits"])) / jnp.max(jnp.abs(want["logits"])))
    assert off > 0.05


# -- the forward process ------------------------------------------------------


def test_the_forward_process_is_the_references_on_the_same_draws():
    x0 = np.random.default_rng(1).integers(0, 99, (3, 24))
    t, u = noise_draws(11, 4, x0.shape, 4, 0.001)
    tokens, (labels, weights) = noised(x0, t, u, 4, 99)
    x_t, m, w = reference.forward_process(x0, t, u, 4, 99)
    assert tokens.dtype == labels.dtype == np.int32 and weights.dtype == np.float32
    assert np.array_equal(tokens[:, :24], x0) and np.array_equal(tokens[:, 24:], x_t)
    assert np.array_equal(labels, x0)
    np.testing.assert_allclose(weights, w, rtol=1e-6)
    assert ((tokens[:, 24:] == 99) == m).all() and ((weights > 0) == m).all()
    # one level a block: the weights of a block's masked positions are equal
    for row in weights.reshape(3, 6, 4):
        for blk in row:
            assert len(set(blk[blk > 0])) <= 1
    assert (t > 0.001).all() and (t <= 1).all() and (u >= 0).all() and (u < 1).all()


def test_a_resumed_data_order_replays_its_noise():
    x0 = np.random.default_rng(2).integers(0, 99, (2, 16))
    order = [noised_batch(x0, 7, i, 4, 99, 0.001) for i in range(4)]
    # a job killed after batch 1 and resumed asks for batches 2 and 3 again, alone
    for i in (3, 2):
        tokens, (labels, weights) = noised_batch(x0, 7, i, 4, 99, 0.001)
        assert np.array_equal(tokens, order[i][0])
        assert np.array_equal(weights, order[i][1][1])
    assert not np.array_equal(order[0][0], order[1][0])
    assert not np.array_equal(order[0][0], noised_batch(x0, 8, 0, 4, 99, 0.001)[0])


@pytest.mark.parametrize("case", ["block", "shapes", "level_zero", "level_above_one"])
def test_the_forward_process_refuses_what_is_not_a_level_a_block(case):
    x0, t, u = np.zeros((1, 8), np.int64), np.full((1, 2), 0.5), np.zeros((1, 8))
    if case == "block":
        with pytest.raises(ValueError, match="in blocks of 3"):
            noised(x0, t, u, 3, 9)
    elif case == "shapes":
        with pytest.raises(ValueError, match="want t"):
            noised(x0, np.full((1, 8), 0.5), u, 4, 9)
    elif case == "level_zero":
        with pytest.raises(ValueError, match=r"lies in \(0, 1\]"):
            noised(x0, np.zeros((1, 2)), u, 4, 9)
    else:
        with pytest.raises(ValueError, match=r"lies in \(0, 1\]"):
            noised(x0, np.full((1, 2), 1.5), u, 4, 9)


# -- the loss head ------------------------------------------------------------


def test_the_loss_head_weighs_and_counts():
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 11), jnp.float32)
    labels = jnp.array([[1, 2, 3, 4, 5, 6], [0, 0, 10, 10, 3, 3]])
    weights = jnp.array([[2.0, 0, 0, 4.0, 0, 0], [0, 1.25, 0, 0, 0, 10.0]])
    loss, metrics = make_block_diffusion_loss()(logits, (labels, weights))
    ce = -np.take_along_axis(
        np.asarray(jax.nn.log_softmax(logits)), np.asarray(labels)[..., None], -1
    )[..., 0]
    scored = np.asarray(weights) > 0
    assert float(loss) == pytest.approx(float((np.asarray(weights) * ce).sum() / 12), rel=1e-6)
    assert float(metrics["bd_masked_share"]) == pytest.approx(4 / 12)
    assert float(metrics["bd_masked_ce"]) == pytest.approx(float(ce[scored].mean()), rel=1e-6)
    right = np.asarray(jnp.argmax(logits, -1) == labels)
    assert float(metrics["accuracy"]) == pytest.approx(right[scored].mean())
    # nothing masked: a loss of 0 and no division by it
    loss, metrics = make_block_diffusion_loss()(logits, (labels, jnp.zeros((2, 6))))
    assert float(loss) == 0 and float(metrics["bd_masked_ce"]) == 0


# -- the share ----------------------------------------------------------------

E, K, D, F, HELD = 128, 8, 32, 16, 16


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Every chip routes over all 128 experts and computes what its own sixteen
    give; nothing is computed on every chip alike (no shared expert). The eight
    parts are the uncut layer as the reference, given all 128 experts as one
    share, computes it."""
    def config(first, count):
        return {
            "num_experts_per_tok": K, "num_experts": count, "norm_topk_prob": True,
            "train": {"load_balance_coef": 0.001},
            "share": {"router_experts": E, "experts_first": first},
        }

    def layer(held):
        return DroplessMoE(
            num_experts=E, top_k=K, d_ff=F, norm_topk_prob=True, aux_weight=0.001,
            z_weight=0.0, score_func="softmax", held=held, dtype=jnp.float32,
        )

    def banks(params, first):
        return dict(params, **{
            bank: params[bank][first:first + HELD] for bank in ("gate", "up", "down")
        })

    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    x = jax.random.normal(keys[0], (2, 24, D), jnp.float32)
    params = jax.jit(layer(None).init)(keys[1], x)["params"]
    with jax.default_matmul_precision("highest"):
        uncut, info = jax.jit(
            lambda p: reference.mixture(config(0, E), p, x.reshape(-1, D))
        )(params)

        @jax.jit
        def parts(params):
            out = []
            for first in range(0, E, HELD):
                y, sown = layer((first, HELD)).apply(
                    {"params": banks(params, first)}, x, mutable=["metrics"]
                )
                out.append((y.reshape(-1, D), sown["metrics"]))
            return out

        total, held_rows = jnp.zeros_like(uncut), 0.0
        for first, (part, gauges) in zip(range(0, E, HELD), parts(params)):
            assert float(gauges["moe_rows_dropped"][0]) == 0
            held_rows += float(gauges["moe_rows_held"][0])
            want, _ = reference.mixture(config(first, HELD), banks(params, first), x.reshape(-1, D))
            _close(part, want, tol=1e-5)
            total = total + part
    assert held_rows == pytest.approx(1.0)
    assert float(info["rows_held"]) == pytest.approx(1.0)
    _close(total, uncut, tol=1e-5)


# -- the refusals -------------------------------------------------------------


@pytest.mark.parametrize("case", [
    "lm_decode", "lm_window", "lm_sparse", "lm_mtp", "lm_mixer", "lm_attention_fn",
    "lm_odd", "lm_not_whole_blocks", "attention_decode", "attention_window",
    "attention_sparse", "attention_fn", "window_with_it", "not_causal", "wrong_length",
    "flash_with_lse", "flash_block_grads",
])
def test_what_does_not_go_with_the_mask_is_refused_by_name(case):
    from edl_tpu.models import MTPSpec, SparseAttentionSpec
    from edl_tpu.models.mamba import MambaSpec

    spec = BlockDiffusionSpec(4, 31)
    key, tokens = jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32)

    def lm(arch, **more):
        return TransformerLM(
            vocab_size=32, d_model=32, num_heads=2, num_layers=1, d_ff=32, arch=arch, **more
        )

    def refused(model, match, tokens=tokens, error=NotImplementedError):
        with pytest.raises(error, match=match):
            jax.eval_shape(model.init, key, tokens)

    x = jnp.zeros((1, 32, 32))
    q = jnp.zeros((1, 2, 32, 8))
    if case == "lm_decode":
        refused(lm(ArchSpec(block_diffusion=spec), decode=True), "block_diffusion with decode=True")
    elif case == "lm_window":
        refused(lm(ArchSpec(block_diffusion=spec, sliding_window=8)), "a window")
    elif case == "lm_sparse":
        refused(lm(ArchSpec(block_diffusion=spec, sparse_attention=SparseAttentionSpec())),
                "sparse_attention")
    elif case == "lm_mtp":
        refused(lm(ArchSpec(block_diffusion=spec, mtp=MTPSpec())), "block_diffusion with mtp")
    elif case == "lm_mixer":
        refused(lm(ArchSpec(block_diffusion=spec, layer_types=("mamba",), mamba=MambaSpec(2, 16, 8))),
                "a mixer that is not attention")
    elif case == "lm_attention_fn":
        refused(lm(ArchSpec(block_diffusion=spec), attention_fn=A.attention_reference),
                "an attention_fn")
    elif case == "lm_odd":
        refused(lm(ArchSpec(block_diffusion=spec)), r"\[B, 2 L\] ids",
                jnp.zeros((1, 31), jnp.int32), ValueError)
    elif case == "lm_not_whole_blocks":
        refused(lm(ArchSpec(block_diffusion=spec)), "whole blocks of 4",
                jnp.zeros((1, 36), jnp.int32), ValueError)
    elif case.startswith("attention"):
        more = {
            "attention_decode": dict(decode=True), "attention_window": dict(window=8),
            "attention_sparse": dict(sparse=SparseAttentionSpec()),
            "attention_fn": dict(attention_fn=A.attention_reference),
        }[case]
        name = {"attention_decode": "decode", "attention_window": "window",
                "attention_sparse": "sparse", "attention_fn": "attention_fn"}[case]
        with pytest.raises(NotImplementedError, match="block_diffusion with %s" % name):
            jax.eval_shape(
                Attention(2, block_diffusion=4, **more).init, key, x,
                jnp.zeros((1, 32), jnp.int32),
            )
    elif case == "window_with_it":
        for fn in (A.attention, A.flash_attention, A.attention_reference):
            with pytest.raises(ValueError, match="a window .* and block_diffusion .* together"):
                fn(q, q, q, causal=True, window=8, block_diffusion=(16, 4))
    elif case == "not_causal":
        with pytest.raises(ValueError, match="needs causal=True"):
            A.attention(q, q, q, causal=False, block_diffusion=(16, 4))
    elif case == "wrong_length":
        with pytest.raises(ValueError, match="2 L queries and keys"):
            A.attention(q, q, q, causal=True, block_diffusion=(8, 4))
        with pytest.raises(ValueError, match="B dividing L"):
            A.attention(q, q, q, causal=True, block_diffusion=(16, 3))
    elif case == "flash_with_lse":
        with pytest.raises(NotImplementedError, match="flash_with_lse takes no block_diffusion"):
            A.flash_with_lse(q, q, q, causal=True, block_diffusion=(16, 4))
    else:
        with pytest.raises(NotImplementedError, match="flash_block_grads takes no block_diffusion"):
            A.flash_block_grads(
                q, q, q, q, jnp.zeros((1, 2, 32)), jnp.zeros((1, 2, 32)), causal=True,
                block_diffusion=(16, 4),
            )
