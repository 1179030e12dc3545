"""Trinity-Mini's mechanisms at toy widths on the CPU: the program against the
plain reference of ``benchmark/reference/afmoe_lm.py`` (logits, loss, every
parameter's gradient, the bias after three steps), the share tied to the model
(what all the shares of a layer give adds up to the uncut layer), today's
``DroplessMoE`` bit for bit under ``held = (0, E)``, and the window in every
route of ``ops/attention.py`` against a dense mask written here."""

import functools
import importlib
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.families import afmoe_lm as family
from benchmark.reference import afmoe_lm as reference
from edl_tpu.models.moe import DroplessMoE
from edl_tpu.train.step import create_state, make_train_step

A = importlib.import_module("edl_tpu.ops.attention")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs", "trinity_mini.json")) as f:
    TOY = json.load(f)


def _paths(tree):
    return [
        "/".join(str(k.key) for k in path)
        for path, _ in jax.tree_util.tree_leaves_with_path(tree)
    ]


@pytest.fixture(scope="module")
def trained():
    """The toy twin in float32 (the CPU's attention is the dense route, the
    grouped matmul ``ragged_dot``: what is compared is the model's
    mathematics), three steps at a rate that moves every scale off 1, then
    the program's and the reference's outputs and gradients on a third batch."""
    job = family.build(TOY, 1, 0)
    model = job["model"].clone(dtype=jnp.float32)
    state = create_state(
        model, jax.random.PRNGKey(0), job["sample_input"], optax.adamw(1e-2)
    )
    step = make_train_step(job["loss"], donate=False)
    pool = family.host_batches(TOY, 1, 0, n_batches=4)
    biases, counts = [state.batch_stats], []
    sown = jax.jit(lambda variables, tokens: state.apply_fn(
        variables, tokens, mutable=["intermediates"]
    ))
    for batch in pool[:3]:
        _, left = sown({"params": state.params, "batch_stats": state.batch_stats}, batch[0])
        counts.append({
            name: np.bincount(
                np.asarray(layer["moe"]["top_idx"][0]).reshape(-1),
                minlength=TOY["share"]["router_experts"],
            )
            for name, layer in left["intermediates"].items()
        })
        state, _ = step(state, batch)
        biases.append(state.batch_stats)
    tokens, targets = pool[3]
    variables = {"params": state.params, "batch_stats": state.batch_stats}

    def program_loss(params):
        logits = state.apply_fn({**variables, "params": params}, tokens)
        return job["loss"](logits, targets)[0], logits

    with jax.default_matmul_precision("highest"):
        (got_loss, got_logits), got_grads = jax.jit(jax.value_and_grad(
            program_loss, has_aux=True
        ))(state.params)
        want_logits, info = jax.jit(
            lambda p: reference.forward(TOY, p, state.batch_stats, tokens)
        )(state.params)
        want_loss, want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(TOY, p, state.batch_stats, tokens, targets)
        ))(state.params)
    return {
        "state": state, "biases": biases, "counts": counts, "info": info,
        "got": {"logits": got_logits, "loss": got_loss, "grads": got_grads},
        "want": {"logits": want_logits, "loss": want_loss, "grads": want_grads},
    }


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    scale = max(np.max(np.abs(want)), 1e-12)
    assert np.max(np.abs(got - want)) / scale <= tol


PARAM_PATHS = _paths(jax.eval_shape(
    lambda: family.build(TOY, 1, 0)["model"].init(
        jax.random.PRNGKey(0), np.zeros((1, TOY["train"]["seq_len"]), np.int32)
    )["params"]
))


@pytest.mark.parametrize("what", ["logits", "loss"])
def test_program_matches_the_reference(trained, what):
    _close(trained["got"][what], trained["want"][what])


@pytest.mark.parametrize("path", PARAM_PATHS)
def test_every_parameters_gradient_matches_the_reference(trained, path):
    got, want = trained["got"]["grads"], trained["want"]["grads"]
    for key in path.split("/"):
        got, want = got[key], want[key]
    assert np.max(np.abs(np.asarray(want))) > 0  # the parameter is in the graph
    _close(got, want, tol=1e-3)


@pytest.mark.parametrize("layer", ["layer_1", "layer_2", "layer_3"])
def test_the_bias_after_three_steps_follows_the_references_rule(trained, layer):
    """Step by step: the bias the train step left in ``TrainState`` against the
    reference's rule on that step's own counts; it moved, kept its mean at
    zero, and a forward pass that may not write it leaves it alone."""
    bias = np.zeros(TOY["share"]["router_experts"], np.float32)
    for before, after, counts in zip(
        trained["biases"], trained["biases"][1:], trained["counts"]
    ):
        np.testing.assert_array_equal(before[layer]["moe"]["router_bias"], bias)
        bias = np.asarray(reference.bias_update(TOY, jnp.asarray(bias), counts[layer]))
        np.testing.assert_allclose(after[layer]["moe"]["router_bias"], bias, atol=1e-9)
    assert np.max(np.abs(bias)) >= TOY["load_balance_coeff"]
    assert abs(float(np.mean(bias))) < 1e-8
    state = trained["state"]
    _, left = state.apply_fn(
        {"params": state.params, "batch_stats": state.batch_stats},
        np.zeros((1, 16), np.int32), mutable=["intermediates"],
    )
    assert "batch_stats" not in left


def test_the_reference_routes_as_the_program_and_nothing_is_dropped(trained):
    state = trained["state"]
    tokens = family.host_batches(TOY, 1, 0, n_batches=4)[3][0]
    _, left = state.apply_fn(
        {"params": state.params, "batch_stats": state.batch_stats}, tokens,
        mutable=["intermediates", "metrics"],
    )
    for j, name in enumerate(["layer_1", "layer_2", "layer_3"]):
        seen, sown = left["intermediates"][name]["moe"], left["metrics"][name]["moe"]
        np.testing.assert_array_equal(
            np.sort(seen["top_idx"][0], axis=-1),
            np.sort(trained["info"]["experts"][j], axis=-1),
        )
        assert float(sown["moe_rows_dropped"][0]) == 0
        assert float(sown["moe_rows_held"][0]) == pytest.approx(
            float(trained["info"]["rows_held"][j])
        )
        first, held = TOY["share"]["experts_first"], TOY["num_experts"]
        counts = np.asarray(trained["info"]["counts"][j])
        assert float(sown["moe_held_load_max"][0]) == pytest.approx(
            counts[first:first + held].max() / counts.mean()
        )


# -- the share, tied to the model ---------------------------------------------

E, K, D, F = 8, 3, 32, 16
LAYER = {
    "num_experts": E, "num_experts_per_tok": K, "num_shared_experts": 1,
    "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826,
    "load_balance_coeff": 0.001,
}


def _layer(held, **over):
    spec = dict(
        num_experts=E, top_k=K, d_ff=F, norm_topk_prob=True, aux_weight=0.0,
        z_weight=0.0, score_func="sigmoid", route_scale=2.826, bias_rate=0.001,
        shared_d_ff=F, held=held, dtype=jnp.float32,
    )
    spec.update(over)
    return DroplessMoE(**spec)


@pytest.fixture(scope="module")
def whole_layer():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, D), jnp.float32)
    variables = jax.jit(_layer(None).init)(jax.random.PRNGKey(2), x)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (E,), jnp.float32)
    return x, variables["params"], {"router_bias": bias - jnp.mean(bias)}


@pytest.mark.parametrize("load", ["as_it_falls", "all_on_one_share"])
@pytest.mark.parametrize(
    "sizes", [(8,), (4, 4), (2, 6), (1, 7), (2, 2, 2, 2), (3, 1, 4)],
    ids=lambda sizes: "x".join(map(str, sizes)),
)
def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(whole_layer, sizes, load):
    """Every chip routes over all E experts and computes what its own give;
    the shared expert is on every chip alike and counts once. The routed
    parts of all the shares plus one shared expert are the uncut layer, as
    the reference (given all E experts as one share) computes it. Under
    ``all_on_one_share`` a bias sends every token's K choices to experts
    0..K-1: the first share's rows outgrow its usual buffer (twice its
    balanced share) and the layer takes the whole N * k, dropping none."""
    x, params, stats = whole_layer
    if load == "all_on_one_share":
        stats = {"router_bias": jnp.where(jnp.arange(E) < K, 10.0, 0.0)}
    shared = lambda t: reference.swiglu(params["shared"], t)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.mixture(
            dict(LAYER, share={"router_experts": E, "experts_first": 0}),
            params, stats["router_bias"], x.reshape(-1, D),
        )
        total, first = shared(x.reshape(-1, D)), 0
        for count in sizes:
            here = dict(params, **{
                bank: params[bank][first:first + count]
                for bank in ("gate", "up", "down")
            })
            part, sown = _layer((first, count)).apply(
                {"params": here, "batch_stats": stats}, x, mutable=["metrics"]
            )
            assert float(sown["metrics"]["moe_rows_dropped"][0]) == 0
            if load == "all_on_one_share" and first == 0:
                assert float(sown["metrics"]["moe_rows_held"][0]) == pytest.approx(min(count, K) / K)
            total = total + part.reshape(-1, D) - shared(x.reshape(-1, D))
            # the reference given the same share agrees chip by chip
            want, _ = reference.mixture(
                dict(LAYER, num_experts=count,
                     share={"router_experts": E, "experts_first": first}),
                here, stats["router_bias"], x.reshape(-1, D),
            )
            _close(part.reshape(-1, D), want, tol=1e-5)
            first += count
    _close(total, uncut, tol=1e-5)


@pytest.mark.parametrize("norm_topk_prob", [False, True])
@pytest.mark.parametrize("what", ["value", "gradients", "sown"])
def test_holding_every_expert_is_todays_layer_bit_for_bit(what, norm_topk_prob):
    """``held = (0, E)`` with softmax scores, no bias and no shared expert goes
    through the share's code (the sentinel group, the masked sums) and must
    give what ``held = None``, the path OLMoE runs, gives, to the bit."""
    kind = dict(
        norm_topk_prob=norm_topk_prob, aux_weight=1e-2, z_weight=1e-3,
        score_func="softmax", route_scale=1.0, bias_rate=0.0, shared_d_ff=0,
        dtype=jnp.bfloat16,
    )
    today, share = _layer(None, **kind), _layer((0, E), **kind)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 40, D), jnp.bfloat16)
    params = jax.jit(today.init)(jax.random.PRNGKey(5), x)["params"]
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(share.init, jax.random.PRNGKey(5), x)["params"]
    )

    def run(layer):
        def loss(params, x):
            y, sown = layer.apply({"params": params}, x, mutable=["losses", "metrics"])
            aux = sum(jnp.sum(leaf) for leaf in jax.tree.leaves(sown["losses"]))
            return jnp.sum(jnp.square(y.astype(jnp.float32))) + aux, (y, sown)

        (_, (y, sown)), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(params, x)
        return {"value": y, "gradients": grads, "sown": sown}

    got, want = run(share)[what], run(today)[what]
    if what == "sown":  # the share sows three gauges more, and agrees on the rest
        assert float(got["metrics"].pop("moe_rows_held")[0]) == 1.0
        assert float(got["metrics"].pop("moe_rows_dropped")[0]) == 0.0
        assert float(got["metrics"].pop("moe_held_load_max")[0]) == pytest.approx(
            float(got["metrics"]["moe_load_max"][0])
        )
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _what_the_chip_leaves(real):
    """``grouped_matmul`` as the Megablox kernels behave on the chip: rows past
    the groups' sum are never written, in the value or in the rows' gradient,
    and hold whatever the memory held (here: NaN); cotangent rows there are
    never read."""

    def poison(a, sizes):
        return jnp.where((jnp.arange(a.shape[0]) >= jnp.sum(sizes))[:, None], jnp.nan, a)

    @jax.custom_vjp
    def grouped(lhs, rhs, sizes):
        return poison(real(lhs, rhs, sizes), sizes)

    def fwd(lhs, rhs, sizes):
        out, vjp = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
        return poison(out, sizes), (vjp, sizes)

    def bwd(residuals, grad):
        vjp, sizes = residuals
        unread = (jnp.arange(grad.shape[0]) >= jnp.sum(sizes))[:, None]
        d_lhs, d_rhs = vjp(jnp.where(unread, 0, grad))
        return poison(d_lhs, sizes), d_rhs, None

    grouped.defvjp(fwd, bwd)
    return grouped


@pytest.mark.parametrize("what", ["value", "gradients"])
def test_rows_past_the_groups_sum_may_hold_anything(whole_layer, monkeypatch, what):
    """The first chip run of the share trained to NaN in one step: the rows of
    pairs held elsewhere are uninitialised memory after a Megablox call, and
    the routing weights' gradient summed over them. With those rows poisoned
    the layer's value and every gradient are what they are without."""
    import edl_tpu.models.moe as moe

    x, params, stats = whole_layer
    here = dict(params, **{b: params[b][2:5] for b in ("gate", "up", "down")})
    layer = _layer((2, 3))

    def run():
        def loss(params, x):
            y = layer.apply({"params": params, "batch_stats": stats}, x)
            return jnp.sum(jnp.square(y)), y

        (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(here, x)
        return {"value": y, "gradients": grads}[what]

    want = run()
    monkeypatch.setattr(moe, "grouped_matmul", _what_the_chip_leaves(moe.grouped_matmul))
    got = run()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


# -- what a block's recomputation finds saved of the layer ---------------------


def _under(names, layer_kwargs, variables, x):
    """Value, gradients and the moved bias of a remat-wrapped layer whose
    policy saves ``names``; jitted, so the CPU's fusions are a step's."""
    import flax.linen as nn

    layer = nn.remat(
        DroplessMoE, policy=jax.checkpoint_policies.save_only_these_names(*names)
    )(**layer_kwargs)

    def loss(params, x):
        y, moved = layer.apply(
            dict(variables, params=params), x, mutable=["batch_stats", "losses", "metrics"]
        )
        aux = sum(jnp.sum(leaf) for leaf in jax.tree.leaves(moved.get("losses", {})))
        kept = (y, moved.get("batch_stats", {}), moved["metrics"])
        return jnp.sum(jnp.square(y.astype(jnp.float32))) + aux, kept

    (_, kept), grads = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
    )(variables["params"], x)
    return kept, grads


@pytest.mark.parametrize("load", ["inside_the_buffer", "outgrows_it"])
@pytest.mark.parametrize("held", [(0, K), None], ids=["held", "whole"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("choice", ["scores", "grouped"])
def test_a_recomputation_handed_the_route_and_the_products_gives_the_same(
    choice, dtype, held, load
):
    """``save_flash`` keeps the layer's ``REMAT_NAMES``; the tensors it keeps
    are the ones the recomputation would make, so the value, every gradient,
    the moved bias and the gauges are those of a policy that keeps none: to
    the bit on the CPU, float32 and bfloat16 alike, whether the step's rows
    fit the held experts' buffer or take the whole ``N k`` (a bias sends every
    token to the first ``K`` experts), with the scores themselves chosen from
    (the ``top_k`` whose values are the weights) or a bias under the choice
    and the choice inside the best groups."""
    from edl_tpu.models import moe
    from edl_tpu.models.transformer import MOE_NAMES

    assert MOE_NAMES == moe.REMAT_NAMES == ("moe_route", "moe_held")
    kind = dict(
        scores=dict(score_func="softmax", bias_rate=0.0, aux_weight=1e-2, z_weight=1e-3),
        grouped=dict(n_group=4, topk_group=3),
    )[choice]
    kwargs = dict(
        num_experts=E, top_k=K, d_ff=F, norm_topk_prob=True, aux_weight=0.0,
        z_weight=0.0, score_func="sigmoid", route_scale=2.826, bias_rate=0.001,
        shared_d_ff=F, held=held, dtype=dtype,
    )
    kwargs.update(kind)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 24, D), dtype)
    variables = jax.jit(DroplessMoE(**kwargs).init)(jax.random.PRNGKey(8), x)
    variables = {k: v for k, v in variables.items() if k in ("params", "batch_stats")}
    if load == "outgrows_it":
        if choice == "scores":  # no bias to lean on: the router itself prefers them
            x = jnp.abs(x)
            router = variables["params"]["router"]
            router["kernel"] = jnp.where(jnp.arange(E) < K, 1.0, -1.0) + 0.01 * router["kernel"]
        else:
            variables["batch_stats"] = {
                "router_bias": jnp.where(jnp.arange(E) < K, 10.0, 0.0)
            }
    (y, stats, gauges), grads = _under(moe.REMAT_NAMES, kwargs, variables, x)
    if held is not None:
        assert float(gauges["moe_rows_dropped"][0]) == 0
        fits = float(gauges["moe_rows_held"][0]) <= 2 * held[1] / E
        assert fits == (load == "inside_the_buffer")
    if choice != "scores":
        assert not np.array_equal(
            np.asarray(stats["router_bias"]), np.asarray(variables["batch_stats"]["router_bias"])
        )
    want = _under((), kwargs, variables, x)
    for a, b in zip(
        jax.tree.leaves(((y, stats, gauges), grads)), jax.tree.leaves(want), strict=True
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in jax.tree.leaves(grads))
    router = np.asarray(grads[0]["router"]["kernel"])
    assert np.abs(router).max() > 0


def test_a_dense_step_lowers_to_the_same_text_with_and_without_the_layers_names(monkeypatch):
    """The names exist only inside ``DroplessMoE``: a model without one finds
    nothing of theirs to save, and its lowered step is the same text whether
    the policy lists them or not."""
    from edl_tpu.models import TransformerLM, transformer
    from edl_tpu.train import cross_entropy_loss

    def lowered():
        lm = TransformerLM(vocab_size=128, d_model=64, num_heads=4, num_kv_heads=2,
                           num_layers=2, d_ff=160, remat=True)
        tokens = np.zeros((2, 32), np.int32)
        state = jax.eval_shape(
            lambda: create_state(lm, jax.random.PRNGKey(0), tokens, optax.adamw(3e-4))
        )
        loss = lambda logits, y: cross_entropy_loss(  # noqa: E731
            logits.reshape(-1, logits.shape[-1]), y.reshape(-1)
        )
        return make_train_step(loss, numerics=True).lower(state, (tokens, tokens)).as_text()

    with_names = lowered()
    monkeypatch.setattr(transformer, "MOE_NAMES", ())
    assert lowered() == with_names


# -- the window, in every route -----------------------------------------------

T, HD, BLOCK = 64, 8, 16
WINDOWS = {"under_a_block": 5, "across_blocks": 24, "whole_sequence": 64, "past_it": 200}
GROUPS = {"mha": (4, 4), "gqa8to1": (8, 1)}


def _dense_mask_attention(q, k, v, window):
    """Softmax attention under ``i - window < j <= i``, written out."""
    group = q.shape[1] // k.shape[1]
    k, v = (np.repeat(np.asarray(a, np.float64), group, axis=1) for a in (k, v))
    scores = np.einsum("bhqd,bhkd->bhqk", np.asarray(q, np.float64), k) * HD ** -0.5
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    scores = np.where((j <= i) & (j > i - window), scores, -np.inf)
    probs = np.exp(scores - scores.max(axis=-1, keepdims=True))
    probs /= probs.sum(axis=-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", probs, v)


@pytest.fixture(scope="module")
def qkvw():
    def make(h, h_kv):
        keys = jax.random.split(jax.random.PRNGKey(h * 10 + h_kv), 4)
        shapes = [(1, h, T, HD), (1, h_kv, T, HD), (1, h_kv, T, HD), (1, h, T, HD)]
        return [jax.random.normal(k, s, jnp.float32) for k, s in zip(keys, shapes)]
    return {name: make(*heads) for name, heads in GROUPS.items()}


@pytest.fixture(scope="module")
def dense_route(qkvw):
    """``(span, heads) -> (out, dq, dk, dv)`` of the dense route under the
    window, one jitted program, once for the seven routes that read it."""
    @functools.lru_cache(maxsize=None)
    def outputs(span, heads):
        q, k, v, w = qkvw[heads]

        @jax.jit
        def run(q, k, v, w):
            out, vjp = jax.vjp(lambda q, k, v: A.attention_reference(
                q, k, v, causal=True, window=WINDOWS[span]), q, k, v)
            return (out, *vjp(w))

        return run(q, k, v, w)

    return outputs


@pytest.fixture(scope="module")
def flash2_route(qkvw):
    """The kernels' forward of a ``(span, heads)`` once for the five routes
    that start from it, and a backward call once for the two gradients'
    routes that read it (an interpreted Pallas call is a compile a call)."""

    class Route:
        @staticmethod
        @functools.lru_cache(maxsize=None)
        def forward(span, heads):
            q, k, v, _ = qkvw[heads]
            return A._flash2_forward(
                q, k, v, True, HD ** -0.5, BLOCK, BLOCK, True, WINDOWS[span]
            )

        @staticmethod
        @functools.lru_cache(maxsize=None)
        def backward(span, heads, pair):
            """``((dq, dk, dv), whether the fused kernel ran)``; ``pair``: a
            head whose dq no VMEM holds, so dq and dk/dv apart."""
            q, k, v, w = qkvw[heads]
            got, lse = Route.forward(span, heads)
            b, h = q.shape[:2]
            capacity = 0 if pair else A._VMEM_V5E
            with mock.patch.object(A, "_vmem_capacity", lambda: capacity), mock.patch.object(
                A, "_flash2_bwd_kernel", wraps=A._flash2_bwd_kernel
            ) as fused:
                grads = A._flash2_backward_kernels(
                    q, k, v, w, lse.reshape(b * h, T), A._bwd_delta(w, got, b, h, T, HD),
                    True, HD ** -0.5, BLOCK, BLOCK, True, WINDOWS[span],
                )
            return grads, fused.called

    return Route


@pytest.mark.parametrize("heads", list(GROUPS))
@pytest.mark.parametrize("span", list(WINDOWS))
@pytest.mark.parametrize("route", [
    "dense", "flash", "flash2_forward", "flash2_dq", "flash2_dkv",
    "flash2_pair_dq", "flash2_pair_dkv",
])
def test_the_window_in_every_route_against_a_dense_mask(
    qkvw, dense_route, flash2_route, route, span, heads
):
    q, k, v, w = qkvw[heads]
    window = WINDOWS[span]
    scale = HD ** -0.5
    want = _dense_mask_attention(q, k, v, window)
    # the gradients' truth: jax's own, through the dense route checked first
    out, want_dq, want_dk, want_dv = dense_route(span, heads)
    if route == "dense":
        _close(out, want, tol=1e-5)
        return
    if route == "flash":  # the public name, its custom vjp and the window
        fn = lambda q, k, v: A.flash_attention(  # noqa: E731
            q, k, v, causal=True, block_q=BLOCK, block_k=BLOCK, window=window
        )
        with mock.patch.object(
            A, "_flash2_forward", wraps=A._flash2_forward
        ) as pipelined:
            got, vjp = jax.vjp(fn, q, k, v)
        assert pipelined.call_count == 1
        _close(got, want, tol=1e-5)
        for a, b in zip(vjp(w), (want_dq, want_dk, want_dv)):
            _close(a, b, tol=1e-5)
        return
    got, lse = flash2_route.forward(span, heads)
    if route == "flash2_forward":
        _close(got, want, tol=1e-5)
        if window >= T:  # the causal kernel, to the bit
            plain, _ = A._flash2_forward(q, k, v, True, scale, BLOCK, BLOCK, True)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(plain))
        return
    (dq, dk, dv), fused = flash2_route.backward(span, heads, "pair" in route)
    assert fused == ("pair" not in route)
    if route.endswith("dq"):
        _close(dq, want_dq, tol=1e-5)
    else:
        _close(dk, want_dk, tol=1e-5)
        _close(dv, want_dv, tol=1e-5)


def test_a_window_needs_a_causal_mask_and_a_key():
    q = jnp.zeros((1, 2, 32, 8))
    for causal, window in ((False, 8), (True, 0)):
        for fn in (A.attention, A.flash_attention, A.attention_reference):
            with pytest.raises(ValueError, match="a window"):
                fn(q, q, q, causal=causal, window=window)
    with pytest.raises(ValueError, match="causal"):
        A.attention(q, q, q, causal=False, window=8)
    # fewer steps than blocks: what lies outside the window is not walked
    assert A._span_steps(2048, 256, 1024, 8192, 8192) == (3, 12)
    assert A._span_steps(2048, 512, 1024, 8192, 8192) == (3, 6)


# -- the attention layer's projections fence their weight gradients; banks do not


def test_the_toy_twins_step_fences_its_head_projections_and_nothing_else(unfence):
    """Four attention layers with a gate: twenty barriers more than the
    unfenced step's (the model sows, so the step is never split), the ring
    names ``q``, ``k``, ``v``, ``g``, ``o`` and no bank ``[E, d, w]``, and
    the tree is the unfenced model's."""
    from edl_tpu.obs import trace as obs_trace

    job = family.build(TOY, 1, 0)
    batch = family.host_batches(TOY, 1, 0, n_batches=1)[0]
    obs_trace.get_tracer().reset_notes()
    tracer = obs_trace.get_tracer()
    before = len([e for e in tracer.to_events() if e["name"] == "dw_apart"])
    barriers, trees = [], []
    for fenced in (True, False):
        if not fenced:
            unfence()
        state = create_state(
            job["model"], jax.random.PRNGKey(0), job["sample_input"], job["optimizer"]
        )
        trees.append(jax.tree.map(lambda a: (a.shape, a.dtype), state.params))
        text = make_train_step(job["loss"], numerics=True).lower(state, batch).as_text()
        barriers.append(text.count("optimization_barrier"))
    assert barriers[0] - barriers[1] == 5 * TOY["num_hidden_layers"]
    assert trees[0] == trees[1]
    banks = [
        leaf.shape for leaf in jax.tree.leaves(state.params)
        if leaf.ndim == 3 and leaf.shape[0] == TOY["num_experts"]
    ]
    assert banks  # the held experts' banks are rank 3 too
    noted = [e["args"] for e in tracer.to_events() if e["name"] == "dw_apart"][before:]
    hidden, heads = TOY["hidden_size"], TOY["num_attention_heads"]
    kv, head_dim = TOY["num_key_value_heads"], TOY["head_dim"]
    assert sorted((a["kernel"], tuple(a["shape"])) for a in noted) == [
        ("g", (hidden, heads, head_dim)), ("k", (hidden, kv, head_dim)),
        ("o", (heads, head_dim, hidden)), ("q", (hidden, heads, head_dim)),
        ("v", (hidden, kv, head_dim)),
    ]
    assert all(a["dtype"] == "float32" for a in noted)  # the toy's compute dtype
    assert all(a["bytes"] == 4 * int(np.prod(a["shape"])) for a in noted)


def test_the_steps_rule_takes_no_leaf_of_trinitys_own_tree():
    """``grads_apart`` judges rank 2 only: at the published widths its count
    stays 0, and the twenty-five projections are the layer's to fence."""
    from edl_tpu.train import step as step_module

    with open(os.path.join(ROOT, "benchmark", "configs", "trinity_mini.json")) as f:
        config = json.load(f)
    job = family.build(config, 1, 0)
    params = jax.eval_shape(
        lambda: job["model"].init(jax.random.PRNGKey(0), job["sample_input"])
    )["params"]
    leaves = jax.tree_util.tree_leaves_with_path(params)
    assert sum(step_module.taken_apart(leaf) for _, leaf in leaves) == 0
    heads = {}
    for path, leaf in leaves:
        keys = [str(k.key) for k in path]
        if "attn" in keys and keys[-2] in ("q", "k", "v", "g", "o"):
            heads.setdefault(keys[-2], set()).add(leaf.shape)
    assert heads == {
        "q": {(2048, 32, 128)}, "g": {(2048, 32, 128)},
        "k": {(2048, 4, 128)}, "v": {(2048, 4, 128)}, "o": {(32, 128, 2048)},
    }
