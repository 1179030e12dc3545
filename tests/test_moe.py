"""MoE: switch routing, capacity, aux loss, expert-parallel sharding.

Net-new capability (no MoE in the reference); validated on the virtual
8-device CPU mesh like every other sharded path.

And the dropless layer's way to a token's chosen scores (``models/moe.py:
_picked`` / ``_sent_home``, PR 65): a comparison with the chosen indices
against the gather out of ``[N, E]`` and its transpose, to the bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import optax

import edl_tpu.models.moe as moe_module
from edl_tpu.models import MOE_EP_RULES, DroplessMoE, SwitchMoE, TransformerLM
from edl_tpu.obs import trace as obs_trace
from edl_tpu.parallel import make_mesh, shard_batch, shard_params_by_rules
from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

slow = pytest.mark.slow  # compile-heavy / multi-process integration: the three Switch classes


B, S, D, E = 4, 16, 32, 4


def make_moe(capacity_factor=4.0):
    return SwitchMoE(
        num_experts=E, d_ff=64, capacity_factor=capacity_factor,
        dtype=jnp.float32,
    )


@slow
class TestSwitchMoE:
    def test_forward_shape_and_aux_loss(self):
        moe = make_moe()
        x = jax.random.normal(jax.random.PRNGKey(0), (B, S, D))
        variables = moe.init(jax.random.PRNGKey(1), x)
        out, mutated = moe.apply({"params": variables["params"]}, x, mutable=["losses"])
        assert out.shape == (B, S, D)
        (aux,) = jax.tree.leaves(mutated["losses"])
        # aux >= aux_weight (its minimum is aux_weight at perfect balance)
        assert float(aux) >= moe.aux_weight * 0.99

    def test_capacity_drops_reduce_output(self):
        """With capacity 1 token/expert, most tokens are dropped: their MoE
        output is exactly zero (the Block's residual carries them)."""
        moe = SwitchMoE(
            num_experts=E, d_ff=64, capacity_factor=E / S, dtype=jnp.float32
        )  # capacity = 1
        x = jax.random.normal(jax.random.PRNGKey(0), (1, S, D))
        variables = moe.init(jax.random.PRNGKey(1), x)
        out, _ = moe.apply({"params": variables["params"]}, x, mutable=["losses"])
        zero_rows = int(jnp.sum(jnp.all(out[0] == 0.0, axis=-1)))
        assert zero_rows >= S - E, zero_rows  # at most E survive

    def test_routing_is_sparse_top1(self):
        """Scaling ONE expert's output weights must double exactly the
        tokens routed to it and leave every other token untouched — dense
        (softmax-mixture) routing would perturb all tokens."""
        moe = make_moe()
        x = jax.random.normal(jax.random.PRNGKey(0), (1, S, D))
        variables = moe.init(jax.random.PRNGKey(1), x)
        out1, _ = moe.apply({"params": variables["params"]}, x, mutable=["losses"])
        wo2 = variables["params"]["wo"].at[0].multiply(2.0)  # expert 0 only
        params2 = {**variables["params"], "wo": wo2}
        out2, _ = moe.apply({"params": params2}, x, mutable=["losses"])
        changed = np.any(
            np.abs(np.asarray(out2[0]) - np.asarray(out1[0])) > 1e-6, axis=-1
        )
        assert 0 < changed.sum() < S, changed.sum()  # some tokens, not all
        np.testing.assert_allclose(  # routed tokens scale exactly 2x
            np.asarray(out2[0][changed]), np.asarray(out1[0][changed]) * 2.0,
            rtol=1e-5,
        )
        np.testing.assert_array_equal(  # the rest are bit-identical
            np.asarray(out2[0][~changed]), np.asarray(out1[0][~changed])
        )

    def test_expert_parallel_matches_unsharded(self):
        moe = make_moe()
        x = jax.random.normal(jax.random.PRNGKey(0), (B, S, D))
        variables = moe.init(jax.random.PRNGKey(1), x)
        ref, _ = moe.apply({"params": variables["params"]}, x, mutable=["losses"])

        mesh = make_mesh({"dp": 2, "ep": 4})
        with mesh:
            # bare SwitchMoE: param paths are "/wi"-style, no "moe/" prefix
            bare_rules = [(r"/w[io]", spec) for _pat, spec in MOE_EP_RULES]
            params = shard_params_by_rules(
                mesh, variables["params"], bare_rules
            )
            assert params["wi"].sharding.spec[0] == "ep"
            xs = shard_batch(mesh, x)
            out, _ = jax.jit(
                lambda v, t: moe.apply(v, t, mutable=["losses"])
            )({"params": params}, xs)
            jax.block_until_ready(out)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@slow
class TestMoETransformer:
    def test_moe_lm_trains_with_aux_loss(self):
        lm = TransformerLM(
            vocab_size=64, d_model=32, num_heads=4, num_layers=2,
            d_ff=64, dtype=jnp.float32, num_experts=4, moe_every=2,
        )
        tokens = jax.random.randint(jax.random.PRNGKey(0), (B, S), 0, 64)
        labels = jnp.roll(tokens, -1, axis=1)
        state = create_state(lm, jax.random.PRNGKey(1), tokens, optax.adam(1e-3))
        assert "moe" in state.params["layer_1"], list(state.params)

        def lm_loss(logits, y):
            return cross_entropy_loss(
                logits.reshape(-1, logits.shape[-1]), y.reshape(-1)
            )

        step = make_train_step(lm_loss, aux_losses=True)
        first = None
        for _ in range(10):
            state, metrics = step(state, (tokens, labels))
            if first is None:
                first = float(metrics["loss"])
        assert "aux_loss" in metrics and float(metrics["aux_loss"]) > 0
        assert float(metrics["loss"]) < first

    def test_moe_lm_ep_sharded_step(self):
        lm = TransformerLM(
            vocab_size=64, d_model=32, num_heads=4, num_layers=2,
            d_ff=64, dtype=jnp.float32, num_experts=4, moe_every=2,
        )
        tokens = jax.random.randint(jax.random.PRNGKey(0), (8, S), 0, 64)
        labels = jnp.roll(tokens, -1, axis=1)
        state = create_state(lm, jax.random.PRNGKey(1), tokens, optax.adam(1e-3))

        def lm_loss(logits, y):
            return cross_entropy_loss(
                logits.reshape(-1, logits.shape[-1]), y.reshape(-1)
            )

        mesh = make_mesh({"dp": 2, "ep": 4})
        step = make_train_step(lm_loss, aux_losses=True)
        with mesh:
            state = state.replace(
                params=shard_params_by_rules(mesh, state.params, MOE_EP_RULES)
            )
            batch = shard_batch(mesh, (tokens, labels))
            new_state, metrics = step(state, batch)
            jax.block_until_ready(metrics["loss"])
        wi = new_state.params["layer_1"]["moe"]["wi"]
        assert wi.sharding.spec and wi.sharding.spec[0] == "ep"


@slow
class TestTop2Routing:
    """top_k=2 (GShard-style): each token mixes its two best experts with
    renormalized gates; 1st choices claim capacity before 2nd choices."""

    @pytest.mark.parametrize("k", [1, 2])
    def test_matches_dense_mixture_when_capacity_ample(self, k):
        """k=2: renormalized two-expert mixture. k=1 pins the Switch
        contract y = p_top1(x) * E(x) — the combine weight must be the
        RAW gate prob, not renormalized to a constant 1."""
        e, d = 4, 8
        moe = SwitchMoE(
            num_experts=e, d_ff=16, capacity_factor=8.0, top_k=k,
            dtype=jnp.float32,
        )
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, d))
        vars_ = moe.init(jax.random.PRNGKey(1), x)
        out = moe.apply(vars_, x)

        p = vars_["params"]
        logits = x @ p["router"]["kernel"]
        probs = jax.nn.softmax(logits, axis=-1)
        tp, ti = jax.lax.top_k(probs, k)
        if k > 1:
            tp = tp / tp.sum(-1, keepdims=True)
        ffn = lambda v, i: jnp.einsum(
            "bsf,fd->bsd",
            jax.nn.gelu(jnp.einsum("bsd,df->bsf", v, p["wi"][i])),
            p["wo"][i],
        )
        want = jnp.zeros_like(x)
        for i in range(e):
            yi = ffn(x, i)
            for c in range(k):
                w = jnp.where(ti[..., c] == i, tp[..., c], 0.0)
                want = want + w[..., None] * yi
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-5
        )

    def test_top2_trains_and_top1_unchanged(self):
        for k in (1, 2):
            moe = SwitchMoE(num_experts=4, d_ff=16, top_k=k, dtype=jnp.float32)
            x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8))
            vars_ = moe.init(jax.random.PRNGKey(1), x)

            def loss_fn(params):
                out, aux = moe.apply(
                    {"params": params}, x, mutable=["losses"]
                )
                return jnp.sum(out**2) + sum(
                    jnp.sum(jnp.asarray(l))
                    for l in jax.tree.leaves(aux["losses"])
                )

            g = jax.grad(loss_fn)(vars_["params"])
            norms = [float(jnp.linalg.norm(l)) for l in jax.tree.leaves(g)]
            assert all(np.isfinite(n) for n in norms)
            assert any(n > 0 for n in norms)

    def test_choice_major_capacity_priority(self):
        """A 2nd choice must never evict another token's 1st choice.

        Setup: 2 experts, capacity 1, 2 tokens. Token 0 prefers e0 then
        e1; token 1 prefers e1 then e0. Choice-major queues serve BOTH
        tokens via their 1st choice (2nd choices find the slots taken).
        Token-major ordering would instead let token 0's 2nd choice take
        e1's only slot and silently zero out token 1 — the regression
        this test pins."""
        e, d = 2, 2
        # capacity = int(cf * k * s / e) = int(0.5 * 2 * 2 / 2) = 1
        moe = SwitchMoE(
            num_experts=e, d_ff=8, capacity_factor=0.5, top_k=2,
            dtype=jnp.float32,
        )
        x = jnp.asarray([[[1.0, 0.0], [0.0, 1.0]]])  # [1, 2, 2]
        vars_ = moe.init(jax.random.PRNGKey(3), x)
        # force the router: token 0 -> logits (2, 1); token 1 -> (1, 2)
        params = jax.tree.map(lambda a: a, vars_["params"])
        params["router"]["kernel"] = jnp.asarray([[2.0, 1.0], [1.0, 2.0]])
        out = moe.apply({"params": params}, x)

        # expected: each token served ONLY by its 1st choice, weighted by
        # its renormalized first-choice gate
        probs = jax.nn.softmax(x @ params["router"]["kernel"], axis=-1)
        tp, ti = jax.lax.top_k(probs, 2)
        tp = tp / tp.sum(-1, keepdims=True)
        ffn = lambda v, i: (
            jax.nn.gelu(v @ params["wi"][i]) @ params["wo"][i]
        )
        want = jnp.stack(
            [
                tp[0, 0, 0] * ffn(x[0, 0], int(ti[0, 0, 0])),
                tp[0, 1, 0] * ffn(x[0, 1], int(ti[0, 1, 0])),
            ]
        )[None]
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-6
        )
        # and in particular: token 1 is NOT zeroed out
        assert float(jnp.abs(out[0, 1]).sum()) > 1e-6


# -- a token's chosen scores, by comparison (PR 65) ---------------------------

def gathered(scores, idx):
    """``_picked`` as the parent had it; jax's own rule transposes it."""
    return jnp.take_along_axis(scores, idx, axis=-1)


def scattered(grad, idx, experts):
    """``_sent_home`` as the parent had it: the gather's transpose, a
    scatter-add into zeros."""
    like = jax.ShapeDtypeStruct(idx.shape[:-1] + (experts,), grad.dtype)
    return jax.linear_transpose(lambda s: gathered(s, idx), like)(grad)[0]


# the six bias-routed cells' (experts, choices), and the toy layer's
WIDTHS = [(512, 22), (512, 8), (320, 8), (128, 8), (64, 4), (8, 2)]


@pytest.mark.parametrize("direction", ["forward", "backward", "top_k_backward"])
@pytest.mark.parametrize("experts,k", WIDTHS, ids=["%dx%d" % w for w in WIDTHS])
def test_picked_is_the_gather_to_the_bit(experts, k, direction):
    """Float32 scores of every sign and size, the ``top_k`` of scores plus a
    bias: ``_picked`` is ``take_along_axis``, its ``jax.vjp`` the gather's, and
    ``_top_k_kept``'s values send their gradient home as ``lax.top_k``'s do,
    each bit for bit (a sum's one term among zeros is that term)."""
    keys = jax.random.split(jax.random.PRNGKey(experts + k), 3)
    scores = jax.random.normal(keys[0], (96, experts), jnp.float32) * 1e3
    idx = jax.lax.top_k(scores + jax.random.normal(keys[1], (experts,)) * 1e3, k)[1]
    grad = jax.random.normal(keys[2], (96, k), jnp.float32) * 1e-3
    if direction == "forward":
        got, want = moe_module._picked(scores, idx), gathered(scores, idx)
        assert got.shape == (96, k) and got.dtype == jnp.float32
    elif direction == "backward":
        (got,) = jax.vjp(lambda s: moe_module._picked(s, idx), scores)[1](grad)
        (want,) = jax.vjp(lambda s: gathered(s, idx), scores)[1](grad)
        assert got.shape == (96, experts) and np.count_nonzero(got) == 96 * k
    else:
        (got,) = jax.vjp(lambda s: moe_module._top_k_kept(s, k)[0], scores)[1](grad)
        (want,) = jax.vjp(lambda s: jax.lax.top_k(s, k)[0], scores)[1](grad)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


LAYERS = {
    "scores_chosen": {},  # the first branch: ``choice is probs``
    "bias": dict(score_func="sigmoid", bias_rate=1e-3, norm_topk_prob=True, route_scale=2.5),
    "groups": dict(score_func="sigmoid", bias_rate=1e-3, norm_topk_prob=True,
                   n_group=4, topk_group=2),
    "held": dict(score_func="sigmoid", bias_rate=1e-3, norm_topk_prob=True, route_scale=2.5,
                 held=(2, 4), shared_d_ff=16),
}


def _value_and_gradients(form):
    """A toy layer's loss (its output's square and its sown losses), the
    gradients of every parameter and of the input, and the bias it leaves.
    Primitive by primitive: inside one jitted program the CPU's fusions contract
    a product and a sum differently around the two forms, 1e-5 of a gradient."""
    layer = DroplessMoE(num_experts=8, top_k=2, d_ff=24, dtype=jnp.float32, **LAYERS[form])
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32), jnp.float32)
    variables = dict(jax.jit(layer.init)(jax.random.PRNGKey(1), x))
    if "batch_stats" in variables:  # a bias that changes the choice
        bias = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (8,), jnp.float32)
        variables["batch_stats"] = {"router_bias": bias}

    def loss(params, x):
        y, left = layer.apply(
            {**variables, "params": params}, x, mutable=["losses", "metrics", "batch_stats"]
        )
        return jnp.sum(y * y) + sum(jax.tree.leaves(left["losses"])), left.get("batch_stats")

    return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(variables["params"], x)


@pytest.mark.parametrize("form", list(LAYERS))
def test_the_layers_loss_and_gradients_are_the_parents_to_the_bit(monkeypatch, form):
    """With a bias, with groups, with a held share and where the scores
    themselves choose: the layer's loss, every gradient and the bias's step are
    bit for bit what the parent's gather and scatter-add give."""
    got = _value_and_gradients(form)
    monkeypatch.setattr(moe_module, "_picked", gathered)
    monkeypatch.setattr(moe_module, "_sent_home", scattered)
    want = _value_and_gradients(form)
    router = np.asarray(got[1][0]["router"]["kernel"])
    assert np.isfinite(router).all() and np.abs(router).max() > 0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_moe_shape_note_says_how_the_scores_are_picked():
    tracer = obs_trace.get_tracer()
    tracer.reset_notes()
    layer = DroplessMoE(num_experts=8, top_k=2, d_ff=24, **LAYERS["held"])
    jax.eval_shape(lambda x: layer.init(jax.random.PRNGKey(0), x), jnp.zeros((1, 16, 32)))
    (note,) = [a for name, a in tracer.notes() if name == "moe_shape"]
    assert note["picked"] == "compare" and note["held"] == 4
