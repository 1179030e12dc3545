"""The OLMoE path end to end on the CPU: the dropless expert layer against a
dense mixture, the grouped matmul against a loop over groups, the auxiliary
terms against hand arithmetic, an OLMoE-shaped ``TransformerLM`` against the
benchmark's plain reference, and the trainer finding what the model sows with
no flag from its caller."""

import functools
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import loss_logits_gradients
from jax.interpreters import partial_eval as pe

from benchmark.families.transformer_lm import LOGITS_REL_TOL
from benchmark.reference import moe_lm as reference
from edl_tpu.checkpoint import CheckpointManager
from edl_tpu.models import MOE_EP_RULES, DroplessMoE, MoESpec, TransformerLM
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import profile as obs_profile
from edl_tpu.ops import grouped_matmul
from edl_tpu.parallel import make_mesh, replicated, shard_batch
from edl_tpu.parallel.sharding_rules import spec_for_path
from edl_tpu.train import (
    ElasticTrainer,
    create_state,
    cross_entropy_loss,
    make_train_step,
)

D = 16


def dense_mixture(params, x, k, norm):
    """Every expert on every token, masked by the routing weights."""
    tokens = x.reshape(-1, x.shape[-1])
    e = params["gate"].shape[0]
    probs = jax.nn.softmax(tokens @ params["router"]["kernel"])
    weights, chosen = jax.lax.top_k(probs, k)
    if norm:
        weights = weights / weights.sum(-1, keepdims=True)
    mask = jnp.zeros_like(probs).at[
        jnp.arange(tokens.shape[0])[:, None], chosen
    ].set(weights)
    y = jnp.zeros_like(tokens)
    for i in range(e):
        hidden = jax.nn.silu(tokens @ params["gate"][i]) * (tokens @ params["up"][i])
        y = y + mask[:, i:i + 1] * (hidden @ params["down"][i])
    return y.reshape(x.shape)


@pytest.mark.parametrize("norm", [False, True], ids=["raw", "norm_topk_prob"])
@pytest.mark.parametrize("k,e", [(1, 4), (2, 4), (1, 8), (2, 8), (8, 8)])
def test_layer_equals_a_dense_mixture(k, e, norm):
    layer = DroplessMoE(num_experts=e, top_k=k, d_ff=24, norm_topk_prob=norm,
                        dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(k * 10 + e), (2, 13, D))
    params = jax.jit(layer.init)(jax.random.PRNGKey(1), x)["params"]

    def value_and_grads(fn):  # one jitted program a side
        def objective(p, x):
            y = fn(p, x)
            return jnp.sum(jnp.sin(y)), y

        (_, y), grads = jax.jit(jax.value_and_grad(objective, (0, 1), has_aux=True))(params, x)
        return y, grads

    value, got = value_and_grads(lambda p, x: layer.apply({"params": p}, x))
    want_value, want = value_and_grads(lambda p, x: dense_mixture(p, x, k, norm))
    np.testing.assert_allclose(value, want_value, atol=2e-6)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-5)


@pytest.mark.parametrize("k", [1, 2])
def test_no_token_is_dropped_when_one_expert_takes_them_all(k):
    e = 8
    layer = DroplessMoE(num_experts=e, top_k=k, d_ff=24, dtype=jnp.float32)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (2, 32, D))) + 0.1
    params = jax.jit(layer.init)(jax.random.PRNGKey(1), x)["params"]
    # positive inputs and a router whose first k columns tower over the rest
    router = jnp.zeros((D, e)).at[:, :k].set(
        5.0 * (k - jnp.arange(k, dtype=jnp.float32))
    )
    params = {**params, "router": {"kernel": router}}
    y, sown = jax.jit(lambda p: layer.apply(
        {"params": p}, x, mutable=["metrics", "intermediates"]
    ))(params)
    chosen = sown["intermediates"]["top_idx"][0]
    assert set(np.unique(chosen)) == set(range(k))     # all 64 tokens, k experts
    assert float(sown["metrics"]["moe_load_max"][0]) == pytest.approx(e / k)
    want = jax.jit(lambda p: dense_mixture(p, x, k, False))(params)
    np.testing.assert_allclose(y, want, atol=2e-6)
    assert float(jnp.min(jnp.max(jnp.abs(y), axis=-1))) > 0  # every token got an answer


def test_router_gradient_of_a_bfloat16_layer_equals_the_float32_mixture():
    """The router's kernel is reached through the routing weights alone, and
    those enter inside the experts' activation: 8 of 64 experts, the banks
    and the rows in bfloat16, held to the benchmark's LM-against-reference
    tolerance (largest difference over largest magnitude)."""
    k, e = 8, 64
    layer = DroplessMoE(num_experts=e, top_k=k, d_ff=24, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, D))
    params = jax.jit(layer.init)(jax.random.PRNGKey(1), x)["params"]

    def router_grad(fn):
        def objective(kernel):
            p = {**params, "router": {"kernel": kernel}}
            return jnp.sum(jnp.sin(fn(p, x)))
        return jax.jit(jax.grad(objective))(params["router"]["kernel"])

    got = router_grad(lambda p, x: layer.apply({"params": p}, x))
    want = router_grad(lambda p, x: dense_mixture(p, x, k, False))
    assert got.dtype == jnp.float32 and float(jnp.max(jnp.abs(want))) > 0
    worst = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert worst <= LOGITS_REL_TOL, worst


def loop_over_groups(lhs, rhs, sizes):
    out, start = [], 0
    for g, n in enumerate(sizes):
        out.append(lhs[start:start + n] @ rhs[g])
        start += n
    return jnp.concatenate(out)


@pytest.mark.parametrize("implementation", ["ragged_dot", "pallas"])
@pytest.mark.parametrize(
    "sizes", [[5, 0, 17, 15], [0, 0, 37, 0], [1, 2, 3, 130]],
    ids=["an_empty_group", "one_group_has_all", "no_multiple_of_a_tile"],
)
def test_grouped_matmul_equals_a_loop_over_groups(implementation, sizes):
    m, k, n = sum(sizes), 24, 40
    keys = jax.random.split(jax.random.PRNGKey(m), 3)
    lhs = jax.random.normal(keys[0], (m, k))
    rhs = jax.random.normal(keys[1], (len(sizes), k, n))
    w = jax.random.normal(keys[2], (m, n))

    def fn(a, b):
        return grouped_matmul(
            a, b, jnp.asarray(sizes), implementation,
            interpret=implementation == "pallas",
        )

    got = jax.value_and_grad(lambda a, b: jnp.sum(fn(a, b) * w), (0, 1))(lhs, rhs)
    want = jax.value_and_grad(
        lambda a, b: jnp.sum(loop_over_groups(a, b, sizes) * w), (0, 1)
    )(lhs, rhs)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)


HAND_LOGITS = np.log(np.array(
    [[4.0, 2.0, 1.0, 1.0], [1.0, 5.0, 1.0, 1.0], [2.0, 2.0, 3.0, 1.0]], np.float32
))


@pytest.mark.parametrize("term", ["load_balance", "router_z"])
def test_auxiliary_terms_by_hand(term):
    """Three tokens, four experts, top-2. Logits are logs of small integers,
    so every number below is a fraction one can check on paper."""
    alpha, beta = 0.5, 0.25
    layer = DroplessMoE(num_experts=4, top_k=2, d_ff=8, aux_weight=alpha,
                        z_weight=beta, dtype=jnp.float32)
    x = jnp.eye(3, D)[None]                       # token t is unit vector t
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)["params"]
    router = jnp.zeros((D, 4)).at[:3].set(HAND_LOGITS)
    params = {**params, "router": {"kernel": router}}
    _, sown = layer.apply({"params": params}, x, mutable=["losses"])
    # p = [4 2 1 1]/8, [1 5 1 1]/8, [2 2 3 1]/8: top-2 are {0,1}, {1, one of
    # 0/2/3 at 1/8 (the first: 0)}, {2, one of 0/1 at 2/8 (the first: 0)}
    # assignments: expert 0 x3, expert 1 x2, expert 2 x1 of 6; P = column means
    share = np.array([3, 2, 1, 0]) / 6.0
    mean_p = np.array([7, 9, 5, 3]) / 24.0
    want = {
        "load_balance": alpha * 4 * float(share @ mean_p),
        # every row sums to 8 before the log: logsumexp = log 8
        "router_z": beta * float(np.log(8.0) ** 2),
    }
    assert float(sown["losses"][term][0]) == pytest.approx(want[term], rel=1e-5)


TOY = {
    "hidden_size": 32, "intermediate_size": 24, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_key_value_heads": 4, "num_experts": 8,
    "num_experts_per_tok": 2, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "vocab_size": 64,
    "train": {"load_balance_coef": 0.01, "router_z_coef": 0.001},
}


def toy_lm(layers, dtype=jnp.float32, remat=False, top_k=2, norm=False):
    return TransformerLM(
        vocab_size=64, d_model=32, num_heads=4, num_kv_heads=4,
        num_layers=layers, d_ff=24, dtype=dtype, remat=remat, norm_eps=1e-5,
        qk_norm=True,
        moe=MoESpec(num_experts=8, top_k=top_k, d_ff=24, norm_topk_prob=norm,
                    aux_weight=0.01 / layers, z_weight=0.001 / layers),
    )


def lm_loss(logits, y):
    return cross_entropy_loss(logits.reshape(-1, logits.shape[-1]), y.reshape(-1))


def toy_batch(seed=0, b=4, t=16):
    tokens = np.random.default_rng(seed).integers(0, 64, (b, t + 1)).astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]


@functools.lru_cache(maxsize=None)
def program_and_reference(layers):
    """``(loss, logits, gradients)`` of the toy LM and of the plain reference
    at one batch, computed once for the cases that each look at one of them."""
    lm = toy_lm(layers)
    config = dict(TOY, num_hidden_layers=layers)
    x, y = toy_batch()
    params = jax.jit(lm.init)(jax.random.PRNGKey(3), x)["params"]
    # scales away from 1 so that a norm applied in the wrong place shows
    params = jax.tree.map(
        lambda p: p * 1.5 if p.ndim == 1 else p, params
    )

    def program(params):
        logits, sown = lm.apply({"params": params}, x, mutable=["losses"])
        extra = sum(jnp.sum(v) for v in jax.tree.leaves(sown["losses"]))
        return lm_loss(logits, y)[0] + extra, logits

    def plain(params):
        return reference.loss(config, params, x, y), reference.forward(config, params, x)[0]

    with jax.default_matmul_precision("highest"):
        return [loss_logits_gradients(fn, params) for fn in (program, plain)]


@pytest.mark.parametrize("what", ["logits", "loss", "gradients"])
@pytest.mark.parametrize("layers", [1, 2])
def test_olmoe_shaped_lm_equals_the_plain_reference(layers, what):
    (loss, logits, got), (want_loss, want_logits, want) = program_and_reference(layers)
    if what == "logits":
        np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    elif what == "loss":
        assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    else:
        flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
        for path, leaf in jax.tree_util.tree_leaves_with_path(want):
            np.testing.assert_allclose(
                flat_got[path], leaf, atol=2e-6, err_msg=str(path)
            )
        router = got["layer_0"]["moe"]["router"]["kernel"]
        assert float(jnp.max(jnp.abs(router))) > 0


def zero_loss(logits, y):
    return 0.0 * jnp.sum(logits), {}


def test_fit_adds_what_the_model_sows_with_no_flag(tmp_path):
    """A loss head that is zero: whatever moves a parameter comes from the
    sown terms, which no argument of the trainer asked for."""
    lm = toy_lm(1, remat=True)
    x, y = toy_batch(b=8)                         # the default mesh is dp over 8
    seen = {}
    trainer = ElasticTrainer(
        lm, optax.sgd(1.0), zero_loss, sample_input=np.zeros_like(x),
        ckpt_dir=str(tmp_path / "ckpt"), seed=5, log=False,
    )
    state = trainer.fit(
        lambda epoch: iter([(x, y)] * 3), epochs=1,
        on_epoch_end=lambda epoch, metrics: seen.update(metrics),
    )
    assert state.sown == ("aux_loss", "moe_load_max")
    assert float(seen["aux_loss"]) > 0 and float(seen["moe_load_max"]) >= 1.0
    assert float(seen["loss"]) == pytest.approx(float(seen["aux_loss"]))
    fresh = create_state(lm, jax.random.PRNGKey(5), x, optax.sgd(1.0))
    moved = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), state.params, fresh.params
    )
    assert moved["layer_0"]["moe"]["router"]["kernel"] > 0   # the auxiliary terms alone
    assert moved["layer_0"]["moe"]["down"] == 0              # no path from them to an expert
    # the gauges the benchmark's reader and a dashboard see, set at the epoch's end
    registry = obs_metrics.default_registry().snapshot()
    assert registry["edl_train_moe_load_max"][""] == pytest.approx(
        float(seen["moe_load_max"])
    )
    assert registry["edl_train_aux_loss"][""] == pytest.approx(float(seen["aux_loss"]))
    # what was sown is a by-product of a step: no leaf of the state or of the
    # checkpoint holds it
    for tree in (state, fresh):
        for path, _ in jax.tree_util.tree_leaves_with_path(tree):
            assert "losses" not in str(path) and "metrics" not in str(path)
    manager = CheckpointManager(str(tmp_path / "ckpt"))
    try:
        restored, status = manager.restore(fresh)
    finally:
        manager.close()
    assert status is not None and int(restored.step) == 3
    assert restored.sown == state.sown
    assert len(jax.tree.leaves(restored)) == len(jax.tree.leaves(fresh))
    np.testing.assert_array_equal(
        restored.params["layer_0"]["moe"]["router"]["kernel"],
        state.params["layer_0"]["moe"]["router"]["kernel"],
    )
    saved = [
        os.path.join(base, name)
        for base, _, names in os.walk(tmp_path / "ckpt") for name in names
    ]
    assert saved and not any("losses" in path for path in saved)


def test_a_model_with_sown_losses_is_never_split():
    lm = toy_lm(1)
    x, y = toy_batch()
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.adamw(1e-3))
    _, metrics = make_train_step(lm_loss, numerics=True)(state, (x, y))
    bundle = metrics["_numerics"]
    assert "half_sq" not in bundle                       # no half-batch pass
    assert set(bundle["sown"]) == {"aux_loss", "moe_load_max"}
    # the override for a hand-built state still works both ways
    _, off = make_train_step(lm_loss, aux_losses=False)(
        create_state(lm, jax.random.PRNGKey(0), x, optax.adamw(1e-3)), (x, y)
    )
    assert "aux_loss" not in off and "moe_load_max" not in off


def test_two_dp_devices_agree_with_one():
    lm = toy_lm(2)
    x, y = toy_batch(b=4)
    step = make_train_step(lm_loss, donate=False)
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.sgd(0.1))
    one_state, one = step(state, (x, y))
    with make_mesh({"dp": 2}, devices=jax.devices()[:2]) as mesh:
        placed = create_state(
            lm, jax.random.PRNGKey(0), x, optax.sgd(0.1), shardings=replicated(mesh)
        )
        two_state, two = step(placed, shard_batch(mesh, (x, y)))
    for name in ("loss", "aux_loss", "moe_load_max"):
        assert float(two[name]) == pytest.approx(float(one[name]), rel=1e-5)
    for a, b in zip(jax.tree.leaves(two_state.params), jax.tree.leaves(one_state.params)):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_ep_rules_name_the_dropless_banks():
    params = jax.jit(toy_lm(1).init)(jax.random.PRNGKey(0), toy_batch()[0])["params"]
    for bank in ("gate", "up", "down"):
        assert spec_for_path("layer_0/moe/" + bank, MOE_EP_RULES)[0] == "ep"
        assert params["layer_0"]["moe"][bank].shape[0] == 8
    assert spec_for_path("layer_0/moe/router/kernel", MOE_EP_RULES) != ("ep", None, None)


@pytest.mark.parametrize("scope", obs_profile.MOE_SCOPES)
def test_the_compiled_step_names_the_expert_layers_scopes(scope):
    lm = toy_lm(1, dtype=jnp.bfloat16, remat=True)
    x, y = toy_batch()
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.adamw(1e-3))
    compiled = make_train_step(lm_loss, numerics=True).lower(state, (x, y)).compile()
    table = obs_profile.scopes_of_hlo(compiled.as_text(), obs_profile.MOE_SCOPES)
    assert scope in set(table.values())
    phases = obs_profile.phases_of_hlo(compiled.as_text())
    both = {phases[name] for name, s in table.items() if s == scope and name in phases}
    assert "forward" in both or "backward" in both


def equations(jaxpr, inside=()):
    """Every equation of ``jaxpr`` and of the jaxprs in its parameters, each
    with the equations that enclose it, outermost first."""
    for eqn in jaxpr.eqns:
        yield inside, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub, inside + (eqn,))


@pytest.mark.parametrize("norm", [False, True], ids=["raw", "norm_topk_prob"])
@pytest.mark.parametrize("k", [1, 8])
def test_the_backward_reruns_no_down_projection_and_unsorts_no_rows(k, norm):
    """``jax.grad`` of a remat-wrapped (``save_flash``) one-layer LM, after
    dead-code elimination: 3 grouped matmuls forward, 2 recomputed (gate and
    up; the down projection's result is no residual of anything), 6 for the
    gradients. Under the ``remat2`` equation the combine leaves one gather,
    out of ``dy`` ``[N, D]``: its forward (a ``custom_vjp_call`` of N*k rows)
    is not run again."""
    lm = toy_lm(1, dtype=jnp.bfloat16, remat=True, top_k=k, norm=norm)
    assert lm.remat_policy == "save_flash"
    x, y = toy_batch()
    params = jax.jit(lm.init)(jax.random.PRNGKey(0), x)["params"]

    def loss(params):
        logits, sown = lm.apply({"params": params}, x, mutable=["losses"])
        extra = sum(jnp.sum(v) for v in jax.tree.leaves(sown["losses"]))
        return lm_loss(logits, y)[0] + extra

    traced = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    live, _ = pe.dce_jaxpr(traced, [True] * len(traced.outvars))
    grouped, combine_gathers = 0, set()
    for inside, eqn in equations(live):
        grouped += eqn.primitive.name == "ragged_dot_general"
        if eqn.primitive.name != "gather":
            continue
        scopes = "/".join(str(e.source_info.name_stack) for e in inside + (eqn,))
        names = [e.primitive.name for e in inside]
        if "remat2" in names and "moe_combine" in scopes:
            combine_gathers.add(("custom_vjp_call" in names, eqn.invars[0].aval.shape))
    assert grouped == 11
    assert combine_gathers == {(False, (x.size, 32))}


# sha256 of the lowered step of a dense TransformerLM at default arguments
# (jax 0.9.0, CPU): a new field at its default leaves the dense LM's program
# what it was. Taken on PR 26's commit. They were e3b7f7a's (5d70944e…,
# 77d4ea6a…) until that PR gave ``LMHead`` a backward of its own, which puts
# one convert and one optimization_barrier per pass into this text on purpose
# (``tests/test_lm_head.py`` pins that structure), and until PR 68, which made
# the loss head's rows ``ops/cross_entropy.py``'s one ``custom_vjp`` where
# optax's ``log_softmax`` and an ``argmax`` stood; these are PR 68's
DENSE_STEP = {
    True: "6ec74441bafa29f6303ebe05276b12ec4df51aa00d899586e63e866d9da529f9",
    False: "1191a975c4fa2fd5e5f3d1165bdbd7bece6849da4899ae46190e75cce0818329",
}
# the same step behind the attention projections' fence (PR 39): one
# ``optimization_barrier`` a projection and half-batch; with the fence off
# the text is still the one above
DENSE_STEP_FENCED = {
    True: "624eb3b4437a76c65b090037e14d27e4d673aa93859d8fa961ff52088c1ee801",
    False: "06c53fe929c6d23ace6a5051d0066532bb956439337e8612798292f06045b013",
}


@pytest.mark.parametrize("fenced", [True, False], ids=["fenced", "unfenced"])
@pytest.mark.parametrize("numerics", [True, False], ids=["numerics", "bare"])
def test_the_dense_lm_lowers_to_the_step_it_was(unfence, numerics, fenced):
    if not fenced:
        unfence()
    lm = TransformerLM(vocab_size=128, d_model=64, num_heads=4, num_kv_heads=2,
                       num_layers=2, d_ff=160, remat=True)
    tokens = np.zeros((4, 32), np.int32)
    state = jax.eval_shape(
        lambda: create_state(lm, jax.random.PRNGKey(0), tokens, optax.adamw(3e-4))
    )
    assert state.sown == ()
    text = make_train_step(lm_loss, numerics=numerics).lower(
        state, (tokens, tokens)
    ).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        DENSE_STEP_FENCED if fenced else DENSE_STEP
    )[numerics]
