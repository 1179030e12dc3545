"""Latent attention with a query rank and the multi-token-prediction module
(``LatentAttentionSpec.q_lora_rank``, ``ArchSpec.mtp``): the layer against the
reference's, the toy model against ``benchmark/reference/mla_mtp_lm.py`` (both
logits, both losses, the objective and its gradient leaf by leaf, a whole
step), what the two fields leave alone at ``None``, and the eight shares of an
expert layer adding up to the uncut one."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import loss_logits_gradients

from benchmark.families import mla_mtp_lm as family
from benchmark.reference import mla_mtp_lm as reference
from benchmark.reference.afmoe_lm import swiglu
from edl_tpu.models import (
    ArchSpec,
    DroplessMoE,
    LatentAttention,
    LatentAttentionSpec,
    MTPSpec,
    TransformerLM,
)
from edl_tpu.obs import trace as obs_trace
from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs", "glm_4_7_flash.json")) as f:
    TOY = json.load(f)
WEIGHT = TOY["train"]["mtp_loss_weight"]


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    scale = max(np.max(np.abs(want)), 1e-12)
    assert np.max(np.abs(got - want)) / scale <= tol, np.max(np.abs(got - want)) / scale


# -- the layer ------------------------------------------------------------------

def latent_layer(q_rank, dtype=jnp.float32):
    spec = family.latent_spec(TOY)
    spec = LatentAttentionSpec(**{**spec.__dict__, "q_lora_rank": q_rank})
    return LatentAttention(
        TOY["num_attention_heads"], spec, dtype, TOY["rms_norm_eps"], float(TOY["rope_theta"])
    )


@pytest.fixture(scope="module")
def layer_inputs():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, TOY["hidden_size"]), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(64)[None], (2, 64))
    params = jax.jit(latent_layer(TOY["q_lora_rank"]).init)(
        jax.random.PRNGKey(1), x, positions
    )["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 8))
    params = jax.tree.map(  # norm scales that are not one
        lambda a: a * (1 + 0.2 * jax.random.normal(next(keys), a.shape)) if a.ndim == 1 else a,
        params,
    )
    return x, positions, params


@pytest.mark.parametrize("what", ["out", "q", "gradients"])
def test_latent_attention_with_a_query_rank_equals_the_references_layer(layer_inputs, what):
    x, positions, params = layer_inputs
    layer = latent_layer(TOY["q_lora_rank"])
    assert set(params) == {"q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "o"}
    assert params["q_a"]["kernel"].shape == (TOY["hidden_size"], TOY["q_lora_rank"])
    with jax.default_matmul_precision("highest"):
        if what == "out":
            _close(layer.apply({"params": params}, x, positions),
                   reference.latent_attention(TOY, params, x))
        elif what == "q":
            _, left = layer.apply({"params": params}, x, positions, mutable=["intermediates"])
            _close(left["intermediates"]["queries"][0], reference.queries(TOY, params, x))
        else:
            w = jax.random.normal(jax.random.PRNGKey(3), x.shape)
            got = jax.jit(jax.grad(
                lambda p, x: jnp.sum(layer.apply({"params": p}, x, positions) * w), (0, 1)
            ))(params, x)
            want = jax.jit(jax.grad(
                lambda p, x: jnp.sum(reference.latent_attention(TOY, p, x) * w), (0, 1)
            ))(params, x)
            for (path, a), b in zip(
                jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)
            ):
                assert float(jnp.linalg.norm(b)) > 0, jax.tree_util.keystr(path)
                _close(a, b, tol=1e-3)


def test_without_a_query_rank_the_layer_is_the_one_it_was(layer_inputs):
    """``q_lora_rank=None``: the parameters are the five there were, the
    lowered text names no query latent and holds as many matmuls as before
    (q, kv_a, kv_b, o and the attention's two), and the instant says so."""
    x, positions, _ = layer_inputs
    layer = latent_layer(None)
    tracer = obs_trace.get_tracer()
    before = len(tracer.to_events())
    params = jax.jit(layer.init)(jax.random.PRNGKey(1), x, positions)["params"]
    assert set(params) == {"q", "kv_a", "kv_norm", "kv_b", "o"}
    # a check that collects intermediates (Ling's) meets the sown queries under
    # a name of their own: ``q`` is this layer's projection
    _, left = layer.apply({"params": params}, x, positions, mutable=["intermediates"])
    assert left["intermediates"]["queries"][0].shape[-2:] == (
        TOY["num_attention_heads"], TOY["qk_nope_head_dim"] + TOY["qk_rope_head_dim"]
    )
    text = jax.jit(layer.apply).lower({"params": params}, x, positions).as_text()
    ranked = jax.jit(latent_layer(TOY["q_lora_rank"]).apply).lower(
        {"params": jax.eval_shape(
            latent_layer(TOY["q_lora_rank"]).init, jax.random.PRNGKey(1), x, positions
        )["params"]}, x, positions,
    ).as_text()
    assert text.count("stablehlo.dot_general") + 1 == ranked.count("stablehlo.dot_general")
    notes = [e for e in tracer.to_events()[before:] if e.get("name") == "mla_shape"]
    # once a shape: the ranked layer's was noted when the fixture traced it
    assert [e["args"]["q_rank"] for e in notes] == [None]


# -- the toy model against the reference ---------------------------------------

def shaken(params, seed=7):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 400))
    return jax.tree.map(
        lambda a: a * (1 + 0.2 * jax.random.normal(next(keys), a.shape))
        if a.ndim <= 2 and a.size < 4096 else a,
        params,
    )


def toy_lm(remat=False, dtype=jnp.float32, **changes):
    # the head as the class draws it: the cell's start puts it at zero
    return family.build(family.as_drawn(TOY), 1, 0)["model"].clone(
        remat=remat, dtype=dtype, **changes
    )


def lm_loss(logits, targets):
    return cross_entropy_loss(logits.reshape(-1, logits.shape[-1]), targets.reshape(-1))


@pytest.fixture(scope="module")
def toy_variables():
    x, y = family.host_batches(TOY, 2, 0, n_batches=1)[0]
    x = np.where(x == 0, 1, x)                              # id 0 is the module's padding
    variables = jax.jit(toy_lm().init)(jax.random.PRNGKey(0), x)
    bias = jax.tree.map(
        lambda a: 0.02 * jax.random.normal(jax.random.PRNGKey(a.size), a.shape),
        variables["batch_stats"],
    )
    stats = jax.tree.map(lambda b: b - jnp.mean(b), bias)
    return shaken(variables["params"]), stats, jnp.asarray(x), jnp.asarray(y)


def program_objective(lm, p, stats, x, y):
    """``(objective, (logits, module's logits, L_main, L_mtp))`` as the step
    forms it: the loss head on the model's output plus every sown loss."""
    logits, left = lm.apply(
        {"params": p, "batch_stats": stats}, x, mutable=["losses", "metrics", "intermediates"]
    )
    main = lm_loss(logits, y)[0]
    sown = sum(jnp.sum(leaf) for leaf in jax.tree.leaves(left["losses"]))
    return main + sown, (
        logits, left["intermediates"]["mtp_logits"][0], main, left["metrics"]["mtp_loss"][0]
    )


@pytest.fixture(scope="module")
def reference_outputs(toy_variables):
    params, stats, x, y = toy_variables

    def plain(p):
        logits, ahead, _ = reference.forward(TOY, p, stats, x)
        main, mtp = reference.losses(logits, ahead, x, y)
        return main + WEIGHT * mtp, (logits, ahead, main, mtp)

    with jax.default_matmul_precision("highest"):
        return loss_logits_gradients(plain, params)


@pytest.fixture(scope="module")
def program_outputs(toy_variables):
    params, stats, x, y = toy_variables

    @functools.lru_cache(maxsize=None)
    def outputs(remat):
        lm = toy_lm(remat=remat)
        with jax.default_matmul_precision("highest"):
            return loss_logits_gradients(
                lambda p: program_objective(lm, p, stats, x, y), params
            )

    return outputs


VALUES = ("logits", "mtp_logits", "loss", "mtp_loss", "objective")
GROUPS = ("embed", "layer_0", "layer_1", "layer_2", "ln_f", "lm_head", "mtp_enorm",
          "mtp_hnorm", "mtp_eh_proj", "mtp_block", "mtp_norm")


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("what", VALUES + GROUPS)
def test_the_toy_lm_equals_the_plain_reference(program_outputs, reference_outputs, remat, what):
    """Both logits, both losses and the objective, and the objective's gradient
    for every leaf of each parameter group: the embedding's and the head's are
    sums of two uses, the trunk's carries the module's term."""
    got_obj, got, got_grads = program_outputs(remat)
    want_obj, want, want_grads = reference_outputs
    assert set(got_grads) == set(GROUPS)
    if what == "objective":
        assert float(got_obj) == pytest.approx(float(want_obj), rel=1e-5)
        assert float(got_obj) == pytest.approx(float(got[2] + WEIGHT * got[3]), rel=1e-6)
    elif what in VALUES:
        i = VALUES.index(what)
        if what == "mtp_logits":  # the two padded positions are left out of every mean
            _close(got[i][:, :-2], want[i][:, :-2])
        elif "logits" in what:
            _close(got[i], want[i])
        else:
            assert float(got[i]) == pytest.approx(float(want[i]), rel=1e-5)
    else:
        for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(got_grads[what]),
            jax.tree.leaves(want_grads[what]),
        ):
            assert float(jnp.linalg.norm(b)) > 0, jax.tree_util.keystr(path)
            _close(a, b, tol=1e-3)


def test_the_module_moves_the_trunk_the_embedding_and_the_head(toy_variables):
    """The module's term reaches every group it should: with its weight at
    zero the module's own leaves get no gradient and the others another."""
    params, stats, x, y = toy_variables
    with_it = toy_lm()
    without = toy_lm(arch=with_it.arch.__class__(
        **{**with_it.arch.__dict__, "mtp": MTPSpec(loss_weight=0.0)}
    ))
    grads = [
        jax.jit(jax.grad(lambda p, lm=lm: program_objective(lm, p, stats, x, y)[0]))(params)
        for lm in (with_it, without)
    ]
    for group in GROUPS:
        a, b = (np.concatenate([np.ravel(v) for v in jax.tree.leaves(g[group])]) for g in grads)
        if group.startswith("mtp_"):
            assert np.abs(b).max() == 0 and np.abs(a).max() > 0, group
        else:
            assert np.abs(a - b).max() > 1e-6 * np.abs(a).max(), group


def test_the_two_unscored_positions_give_no_gradient(toy_variables):
    """The last two positions carry weight 0: the module's loss is the mean
    over the other T-2 of its own logits against the ids two ahead, and the
    padding id's row of the table (id 0, which the tokens do not hold) gets no
    gradient from the position that reads it."""
    params, stats, x, y = toy_variables
    lm = toy_lm()
    assert not bool(jnp.any(x == 0))

    def sown(p):
        _, left = lm.apply(
            {"params": p, "batch_stats": stats}, x, mutable=["losses", "metrics", "intermediates"]
        )
        return left["losses"]["mtp_loss"][0], left

    (weighted, left), grads = jax.jit(jax.value_and_grad(sown, has_aux=True))(params)
    ahead = left["intermediates"]["mtp_logits"][0]
    logp = jax.nn.log_softmax(ahead[:, :-2], axis=-1)
    mean = -jnp.mean(jnp.take_along_axis(logp, x[:, 2:, None], axis=-1))
    assert float(left["metrics"]["mtp_loss"][0]) == pytest.approx(float(mean), rel=1e-5)
    assert float(weighted) == pytest.approx(WEIGHT * float(mean), rel=1e-5)
    table = grads["embed"]["embedding"]
    assert float(jnp.max(jnp.abs(table[0]))) == 0.0
    assert float(jnp.max(jnp.abs(table[int(x[0, 1])]))) > 0.0


def test_a_whole_steps_gradients_are_the_references(toy_variables, reference_outputs):
    """Through ``create_state`` and ``make_train_step`` as the trainer calls
    them, with plain SGD at rate 1 and no line of ``train/step.py`` for the
    module: what the step takes off every parameter is the gradient of the
    reference's objective; ``state.sown`` lists ``aux_loss`` and ``mtp_loss``,
    ``metrics["loss"]`` is ``L_main + aux``, and the numerics bundle's ``sown``
    carries both."""
    params, stats, x, y = toy_variables
    want_obj, (_, _, want_main, want_mtp), want_grads = reference_outputs
    lm = toy_lm(remat=True)
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.sgd(1.0))
    assert {"aux_loss", "mtp_loss"} <= set(state.sown)
    state = state.replace(params=params, batch_stats=stats)
    with jax.default_matmul_precision("highest"):
        after, metrics = make_train_step(lm_loss, numerics=True, donate=False)(state, (x, y))
    taken = jax.tree.map(lambda before, now: before - now, params, after.params)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(taken), jax.tree.leaves(want_grads)
    ):
        _close(a, b, tol=2e-3)
    assert float(metrics["loss"]) == pytest.approx(float(want_obj), rel=1e-5)
    assert float(metrics["mtp_loss"]) == pytest.approx(float(want_mtp), rel=1e-5)
    assert float(metrics["aux_loss"]) == pytest.approx(WEIGHT * float(want_mtp), rel=1e-5)
    assert float(metrics["loss"] - metrics["aux_loss"]) == pytest.approx(float(want_main), rel=1e-5)
    bundle = metrics["_numerics"]["sown"]
    assert float(bundle["mtp_loss"]) == float(metrics["mtp_loss"])
    assert float(bundle["aux_loss"]) == float(metrics["aux_loss"])


def test_the_lm_trains_through_the_step_and_exports_its_gauge():
    lm = toy_lm(remat=True, dtype=jnp.bfloat16)
    x, y = family.host_batches(TOY, 3, 1, n_batches=1)[0]   # a shape no test has noted
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.adamw(1e-3))
    step = make_train_step(lm_loss, numerics=True, donate=False)
    first = None
    for _ in range(5):
        state, metrics = step(state, (x, y))
        first = first if first is not None else float(metrics["mtp_loss"])
    assert float(metrics["mtp_loss"]) < first and np.isfinite(float(metrics["loss"]))
    from edl_tpu.obs import metrics as obs_metrics
    from edl_tpu.obs import numerics as obs_numerics

    obs_numerics.publish_sown({k: np.asarray(metrics[k]) for k in state.sown})
    assert "edl_train_mtp_loss " in obs_metrics.default_registry().render()
    t, vocab = TOY["train"]["seq_len"], TOY["vocab_size"]
    noted = [args for name, args in obs_trace.get_tracer().notes() if name == "mtp_shape"]
    assert {
        "depth": 1, "tq": t, "scored": t - 2, "loss_weight": WEIGHT, "vocab": vocab,
        "mixer": "latent_attention", "logit_bytes": 4 * 3 * t * vocab,
    } in noted


# -- what the fields leave alone ---------------------------------------------------

def plain_lm(**arch):
    return TransformerLM(
        vocab_size=64, d_model=32, num_heads=2, num_layers=2, d_ff=64, dtype=jnp.float32,
        arch=ArchSpec(**arch) if arch else None,
    )


def test_without_the_module_the_model_is_the_one_it_was():
    """``mtp=None``: the parameters and the lowered step are those of a model
    whose ``ArchSpec`` never heard of the field, and nothing is sown."""
    x = np.ones((2, 16), np.int32)
    texts = []
    for lm in (plain_lm(), plain_lm(mtp=None)):
        state = create_state(lm, jax.random.PRNGKey(0), x, optax.sgd(0.1))
        assert state.sown == () and not any(k.startswith("mtp") for k in state.params)
        texts.append(make_train_step(lm_loss).lower(state, (x, x)).as_text())
    assert texts[0] == texts[1] and "mtp" not in texts[0]
    with_it = create_state(plain_lm(mtp=MTPSpec()), jax.random.PRNGKey(0), x, optax.sgd(0.1))
    assert with_it.sown == ("aux_loss", "mtp_loss")
    assert "mtp" in make_train_step(lm_loss).lower(with_it, (x, x)).as_text()


def test_the_models_output_stays_the_main_logits():
    x = np.ones((2, 16), np.int32)
    lm = plain_lm(mtp=MTPSpec())
    variables = lm.init(jax.random.PRNGKey(0), x)
    trunk = {k: v for k, v in variables["params"].items() if not k.startswith("mtp_")}
    got = lm.apply({"params": variables["params"]}, x)
    want = plain_lm().apply({"params": trunk}, x)
    assert got.shape == (2, 16, 64)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_decode_ignores_the_module():
    x = np.ones((2, 1), np.int32)
    lm = plain_lm(mtp=MTPSpec()).clone(decode=True, max_decode_len=8)
    variables = lm.init(jax.random.PRNGKey(0), x)
    assert not any(k.startswith("mtp") for k in variables["params"])
    assert "losses" not in variables and "metrics" not in variables
    trained = plain_lm(mtp=MTPSpec()).init(jax.random.PRNGKey(0), np.ones((2, 8), np.int32))
    logits, _ = lm.apply(
        {"params": trained["params"], "cache": variables["cache"]}, x, mutable=["cache"]
    )
    assert logits.shape == (2, 1, 64)


@pytest.mark.parametrize("depth", [0, 2])
def test_another_depth_raises(depth):
    with pytest.raises(ValueError, match="depth"):
        plain_lm(mtp=MTPSpec(depth=depth)).init(jax.random.PRNGKey(0), np.ones((1, 8), np.int32))


def test_a_tied_head_is_used_twice_too():
    x = np.arange(1, 33, dtype=np.int32).reshape(2, 16)
    lm = plain_lm(mtp=MTPSpec(), tie_embeddings=True)
    variables = lm.init(jax.random.PRNGKey(0), x)
    assert "lm_head" not in variables["params"]

    def sown(p):
        _, left = lm.apply({"params": p}, x, mutable=["losses"])
        return left["losses"]["mtp_loss"][0]

    grads = jax.grad(sown)(variables["params"])
    assert float(jnp.max(jnp.abs(grads["embed"]["embedding"]))) > 0


# -- the share -------------------------------------------------------------------------

E, K, F, CHIPS = 64, 4, 24, 8


def expert_layer(held=None):
    return DroplessMoE(
        num_experts=E, top_k=K, d_ff=F, norm_topk_prob=True, aux_weight=0.0, z_weight=0.0,
        score_func="sigmoid", route_scale=1.8, bias_rate=1e-3, shared_d_ff=F, held=held,
        dtype=jnp.float32,
    )


def layer_config(held):
    first, count = held or (0, E)
    return {
        "num_experts_per_tok": K, "n_routed_experts": count, "norm_topk_prob": True,
        "routed_scaling_factor": 1.8, "n_shared_experts": 1,
        "train": {"expert_bias_rate": 1e-3},
        "share": {"router_experts": E, "experts_first": first},
    }


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Eight chips hold eight experts each of sixty-four: the router, its bias,
    the top-4 and the renormalisation (times 1.8) at the whole width on every
    chip; what they put out, the shared expert counted once, is the reference's
    uncut layer."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32), jnp.float32)
    params = jax.jit(expert_layer().init)(jax.random.PRNGKey(1), x)["params"]
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(2), (E,))
    stats = {"router_bias": bias - jnp.mean(bias)}
    tokens = x.reshape(-1, x.shape[-1])
    held = E // CHIPS
    program = jax.jit(lambda first, p: expert_layer((first, held)).apply(
        {"params": p, "batch_stats": stats}, x
    ), static_argnums=0)
    with jax.default_matmul_precision("highest"):
        want, info = reference.mixture(layer_config(None), params, stats["router_bias"], tokens)
        shared = swiglu(params["shared"], tokens)
        outputs = []
        for first in range(0, E, held):
            banks = {name: params[name][first:first + held] for name in ("gate", "up", "down")}
            outputs.append(program(first, {**params, **banks}).reshape(tokens.shape))
            _close(outputs[-1], reference.mixture(
                layer_config((first, held)), {**params, **banks}, stats["router_bias"], tokens
            )[0])
    _close(sum(outputs) - (CHIPS - 1) * shared, want)
    assert int(jnp.sum(info["counts"])) == tokens.shape[0] * K
