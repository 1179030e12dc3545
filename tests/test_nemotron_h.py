"""Nemotron-H's mechanisms at toy sizes on the CPU: blocks of one branch each
through ``TransformerLM`` (``ArchSpec.one_branch``), the ungated squared-ReLU
expert layer in a latent (``DroplessMoE(gated=False, activation="relu2",
latent=...)``) and Mamba-2 in groups with its gated norm by group, each against
``benchmark/reference/nemotron_h_lm.py``, which imports nothing from
``edl_tpu.models``: logits, loss and every gradient, a whole step through
``make_train_step``, the shares adding up to the uncut layer (the experts through
the latent, the Mamba-2 heads before ``W_out``), the defaults lowering as the
parent's, the sharding rules on the new leaves, and every matmul of the compiled
step under a part of ``STEP_PARTS``.
"""

import functools
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import loss_logits_gradients, value_and_gradients

from benchmark.families import nemotron_h_lm as family
from benchmark.reference import nemotron_h_lm as reference
from benchmark.reference import ssm_lm as ssm_reference
from edl_tpu.models import (
    MOE_EP_RULES,
    ArchSpec,
    DroplessMoE,
    Mamba2Mixer,
    MambaSpec,
    MoESpec,
    TransformerLM,
)
from edl_tpu.models.transformer import FEED_FORWARD_TYPES, LAYER_TYPES
from edl_tpu.obs import profile as obs_profile
from edl_tpu.obs import trace as obs_trace
from edl_tpu.parallel.sharding_rules import (
    TRANSFORMER_TP_RULES,
    shard_params_by_rules,
    spec_for_path,
)
from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(
    ROOT, "benchmark", "rehearsal", "configs", "nemotron_3_super_120b_a12b.json"
)) as f:
    TOY = json.load(f)
D = TOY["hidden_size"]
EXPERT_BLOCKS = family.expert_blocks(TOY)


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    scale = max(np.max(np.abs(want)), 1e-12)
    assert np.max(np.abs(got - want)) / scale <= tol


def shaken(params, seed=7):
    """Every vector (a norm's scale, a step's bias) off its start, so that a
    misplaced one shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 400))
    return jax.tree.map(
        lambda a: a * (1 + 0.2 * jax.random.normal(next(keys), a.shape))
        if a.ndim <= 2 and a.size < 4096 else a,
        params,
    )


def toy_lm(remat=False, dtype=jnp.float32):
    return family.build(TOY, 1, 0)["model"].clone(remat=remat, dtype=dtype)


def toy_batch(seed=0, b=2):
    return family.host_batches(TOY, b, seed, n_batches=1)[0]


def lm_loss(logits, targets):
    return cross_entropy_loss(logits.reshape(-1, logits.shape[-1]), targets.reshape(-1))


@pytest.fixture(scope="module")
def toy_variables():
    lm = toy_lm()
    x, y = toy_batch()
    variables = jax.jit(lm.init)(jax.random.PRNGKey(3), x)
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 8))

    def some_bias(a):  # as the rule leaves it: its mean at zero
        b = 0.02 * jax.random.normal(next(keys), a.shape)
        return b - jnp.mean(b)

    return shaken(variables["params"]), jax.tree.map(some_bias, variables["batch_stats"]), x, y


def test_the_toy_is_blocks_of_one_branch_each(toy_variables):
    params = toy_variables[0]
    assert TOY["hybrid_override_pattern"] == "MEM*E"
    assert family.arch_spec(TOY).layer_types == ("mamba", "moe", "mamba", "attention", "moe")
    assert family.arch_spec(TOY).one_branch and not set(FEED_FORWARD_TYPES) & set(LAYER_TYPES)
    assert set(params["layer_0"]) == {"mamba", "ln1"}                    # no feed-forward
    assert set(params["layer_1"]) == {"moe", "ln1"}                      # no mixer
    assert set(params["layer_3"]) == {"attn", "ln1"}
    moe = params["layer_1"]["moe"]
    assert set(moe) == {"router", "latent_down", "latent_up", "up", "down", "shared"}
    assert set(moe["shared"]) == {"up", "down"}                          # ungated: no gate
    latent, f = TOY["moe_latent_size"], TOY["moe_intermediate_size"]
    assert moe["router"]["kernel"].shape == (D, 32)                      # the whole router
    assert moe["latent_down"]["kernel"].shape == (D, latent)
    assert moe["up"].shape == (4, latent, f) and moe["down"].shape == (4, f, latent)
    assert moe["shared"]["up"]["kernel"].shape == (D, TOY["moe_shared_expert_intermediate_size"])
    assert params["layer_0"]["mamba"]["norm"].shape == (8 * 16,)


@pytest.fixture(scope="module")
def reference_outputs(toy_variables):
    """``(loss, logits, gradients)`` of the plain reference at the toy's batch."""
    params, stats, x, y = toy_variables

    def plain(p):
        return reference.loss(TOY, p, stats, x, y), reference.forward(TOY, p, stats, x)[0]

    with jax.default_matmul_precision("highest"):
        return loss_logits_gradients(plain, params)


@pytest.fixture(scope="module")
def reference_gradients(reference_outputs):
    return reference_outputs[2]


@pytest.fixture(scope="module")
def program_outputs(toy_variables):
    """``remat -> (loss, logits, gradients)`` of the toy LM, each computed once."""
    params, stats, x, y = toy_variables

    @functools.lru_cache(maxsize=None)
    def outputs(remat):
        lm = toy_lm(remat=remat)

        def program(p):
            logits = lm.apply({"params": p, "batch_stats": stats}, x)
            return lm_loss(logits, y)[0], logits

        with jax.default_matmul_precision("highest"):
            return loss_logits_gradients(program, params)

    return outputs


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("what", ["logits", "loss", "gradients"])
def test_the_one_branch_lm_equals_the_plain_reference(program_outputs, reference_outputs, remat, what):
    (loss, logits, got), (want_loss, want_logits, want) = program_outputs(remat), reference_outputs
    if what == "logits":
        _close(logits, want_logits)
        return
    if what == "loss":
        assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
        return
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(b)) > 0, name  # the parameter is in the graph
        _close(a, b, tol=1e-3)


def test_a_whole_steps_gradients_are_the_references(toy_variables, reference_gradients):
    """Through ``create_state`` and ``make_train_step`` as the trainer calls
    them, with plain SGD at rate 1: what the step takes off every parameter is
    the gradient of the reference's loss, and the bias it leaves is the
    reference's rule on the step's own counts."""
    params, stats, x, y = toy_variables
    lm = toy_lm(remat=True)
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.sgd(1.0))
    state = state.replace(params=params, batch_stats=stats)
    with jax.default_matmul_precision("highest"):
        after, metrics = make_train_step(lm_loss, numerics=False, donate=False)(state, (x, y))
        _, info = reference.forward(TOY, params, stats, x)
    taken = jax.tree.map(lambda before, now: before - now, params, after.params)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(taken), jax.tree.leaves(reference_gradients)
    ):
        assert float(jnp.linalg.norm(b)) > 0, jax.tree_util.keystr(path)
        _close(a, b, tol=2e-3)
    for j, i in enumerate(EXPERT_BLOCKS):
        _close(
            after.batch_stats["layer_%d" % i]["moe"]["router_bias"], info["bias_after"][j],
            tol=1e-5,
        )
    assert float(metrics["moe_rows_held"]) == pytest.approx(float(jnp.mean(info["rows_held"])))
    assert 0.0 < float(metrics["ssm_decay_mean"]) < 1.0


def test_the_lm_trains_through_the_step_and_exports_its_gauges():
    lm = toy_lm(remat=True, dtype=jnp.bfloat16)
    x, y = toy_batch(seed=1)
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.adamw(1e-3))
    gauges = ("ssm_decay_mean", "moe_rows_held", "moe_held_load_max", "moe_bias_absmax")
    assert set(gauges) <= set(state.sown)
    step = make_train_step(lm_loss, numerics=True, donate=False)
    first = None
    for _ in range(5):
        state, metrics = step(state, (x, y))
        first = first if first is not None else float(metrics["loss"])
    assert float(metrics["loss"]) < first and np.isfinite(float(metrics["loss"]))
    assert float(metrics["moe_rows_dropped"]) == 0
    from edl_tpu.obs import metrics as obs_metrics
    from edl_tpu.obs import numerics as obs_numerics

    obs_numerics.publish_sown({k: np.asarray(metrics[k]) for k in state.sown})
    rendered = obs_metrics.default_registry().render()
    for gauge in gauges:
        assert "edl_train_%s " % gauge in rendered


# -- the expert layer: ungated, relu2, in a latent ---------------------------

E, K, F, LATENT, SHARED = 16, 5, 24, 16, 40


def latent_layer(held=None, dtype=jnp.float32, **changes):
    spec = dict(
        num_experts=E, top_k=K, d_ff=F, norm_topk_prob=True, aux_weight=0.0, z_weight=0.0,
        score_func="sigmoid", route_scale=5.0, bias_rate=1e-3, shared_d_ff=SHARED,
        held=held, gated=False, activation="relu2", latent=LATENT,
    )
    spec.update(changes)
    return DroplessMoE(**spec, dtype=dtype)


def layer_config(held):
    """The layer above as the reference reads a configuration."""
    first, count = held or (0, E)
    return {
        "num_experts_per_tok": K, "n_routed_experts": count, "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 5.0, "n_shared_experts": 1,
        "mlp_hidden_act": "relu2", "train": {"expert_bias_rate": 1e-3},
        "share": {"router_experts": E, "experts_first": first},
    }


@pytest.fixture(scope="module")
def whole_layer():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32), jnp.float32)
    variables = jax.jit(latent_layer().init)(jax.random.PRNGKey(1), x)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(2), (E,))
    return variables["params"], {"router_bias": bias - jnp.mean(bias)}, x


def share_of(params, held):
    first, count = held
    banks = {name: params[name][first:first + count] for name in ("up", "down")}
    return {**params, **banks}


@pytest.mark.parametrize("held", [None, (4, 4)], ids=["all_held", "a_share"])
def test_the_latent_layer_equals_a_plain_loop_over_its_experts(whole_layer, held):
    """Forward and every gradient against the reference's ``mixture``: the
    experts one after another over all tokens, ``relu(W1 u)^2`` in the latent,
    the shared expert on the full width."""
    params, stats, x = whole_layer
    if held is not None:
        params = share_of(params, held)
    layer, config = latent_layer(held), layer_config(held)
    tokens = x.reshape(-1, x.shape[-1])
    weigh = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def program(p):
        return layer.apply({"params": p, "batch_stats": stats}, x)

    def plain(p):
        return reference.mixture(config, p, stats["router_bias"], tokens)[0].reshape(x.shape)

    with jax.default_matmul_precision("highest"):
        (value, got), (want_value, want) = (
            value_and_gradients(fn, params, weight=weigh, argnums=0) for fn in (program, plain)
        )
    _close(value, want_value)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        assert float(jnp.linalg.norm(b)) > 0, jax.tree_util.keystr(path)
        _close(a, b, tol=1e-3)


def test_the_shares_routed_parts_add_up_in_the_latent_to_the_uncut_layer(whole_layer):
    """Four chips hold four experts each. The parts ``r`` of the routed sum
    that the shares compute, summed IN THE LATENT and sent through ``W_up``
    once, plus the shared expert once, are the reference's uncut layer; and
    since ``W_up`` is linear, so is the sum of what the shares put out, the
    shared expert counted once."""
    params, stats, x = whole_layer
    tokens = x.reshape(-1, x.shape[-1])
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        want, _ = reference.mixture(layer_config(None), params, stats["router_bias"], tokens)
        shared = reference.ungated(params["shared"], tokens)
        routed, outputs = [], []
        for first in range(0, E, 4):
            held = (first, 4)
            y, left = latent_layer(held).apply(
                {"params": share_of(params, held), "batch_stats": stats}, x,
                mutable=["intermediates"],
            )
            routed.append(left["intermediates"]["routed_latent"][0])
            outputs.append(y.reshape(tokens.shape))
        through_up_once = sum(routed) @ params["latent_up"]["kernel"].astype(f32) + shared
    _close(through_up_once, want)
    _close(sum(outputs) - (len(outputs) - 1) * shared, want)
    assert float(jnp.max(jnp.abs(routed[0]))) > 0 and routed[0].shape == (tokens.shape[0], LATENT)


# sha256 of the layer's lowered value-and-gradient. Taken on PR 48's tree until
# PR 52 named what a remat policy keeps of the layer: against its parent's text
# the sigmoid form's differs in the numbers of jax's private functions alone
# (``_where_50`` is ``_where_54``), OLMoE's in its ``top_k``, whose values'
# gradient has a rule of its own now (``models/moe.py:_top_k_kept``). PR 65's:
# the chosen scores and their gradient go by comparison (``_picked``,
# ``_sent_home``) where the parent's text gathers and scatters, in both forms
# and nowhere else (``tests/test_moe.py`` holds the values to the parent's)
GOLDEN = {
    "olmoe": "8a0dd27f508fe1a26eb4e0e8d9689d9f63c665f7cbb4901f247b3eedd12ab349",
    "sigmoid_held_shared": "51b7e1a8cc09495513f3af0246dbcfffccf40063c4dcb931ea067edf9ac10fa9",
}
FORMS = {
    "olmoe": {},
    "sigmoid_held_shared": dict(
        score_func="sigmoid", bias_rate=1e-3, shared_d_ff=16, held=(2, 4), norm_topk_prob=True,
        route_scale=2.5, aux_weight=0.0, z_weight=0.0,
    ),
}


def lowered_digest(form):
    """sha256 of the lowered text (StableHLO, no source locations) of one
    ``DroplessMoE``'s value and gradients, in the form ``FORMS[form]``."""
    layer = DroplessMoE(num_experts=8, top_k=2, d_ff=24, dtype=jnp.float32, **FORMS[form])
    x = jnp.zeros((2, 16, 32), jnp.float32)
    variables = jax.jit(layer.init)(jax.random.PRNGKey(0), x)

    def value_and_gradients(v, x):
        def loss(p):
            y, _ = layer.apply(
                {**v, "params": p}, x, mutable=["losses", "metrics", "batch_stats"]
            )
            return jnp.sum(y * y)
        return jax.value_and_grad(loss)(v["params"])

    text = jax.jit(value_and_gradients).lower(variables, x).as_text()
    return hashlib.sha256(text.encode()).hexdigest(), set(variables["params"])


@pytest.mark.parametrize("form", sorted(FORMS))
def test_with_the_defaults_the_layer_lowers_as_the_parents(form):
    """``gated``, ``activation`` and ``latent`` at their defaults leave the
    layer instruction for instruction what it was before they existed: the
    lowered value and gradients have the digest the parent's layer gave for
    the same call (``lowered_digest`` run on PR 48's tree). A PR that changes
    the gated layer on purpose takes new digests from its own parent the same
    way."""
    digest, leaves = lowered_digest(form)
    assert leaves >= {"router", "gate", "up", "down"}
    assert digest == GOLDEN[form]


def test_an_activation_the_form_does_not_take_is_refused():
    x = jnp.zeros((1, 8, 16))
    with pytest.raises(ValueError, match="a gated expert's gate is one of silu, relu"):
        latent_layer(gated=True).init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="relu2, silu"):
        latent_layer(activation="gelu").init(jax.random.PRNGKey(0), x)


def test_each_traced_shape_leaves_one_moe_shape_instant():
    tracer = obs_trace.get_tracer()
    tracer.reset_notes()
    before = len([e for e in tracer.to_events() if e["name"] == "moe_shape"])
    layer, x = latent_layer((0, 4)), jnp.zeros((1, 64, 32))
    for _ in range(2):
        jax.eval_shape(lambda x: layer.init(jax.random.PRNGKey(0), x), x)
    found = [e["args"] for e in tracer.to_events() if e["name"] == "moe_shape"][before:]
    assert found == [{
        "experts": E, "held": 4, "top_k": K, "pairs": 64 * K, "buffer_rows": 160,
        "latent": LATENT, "width": F, "gated": False, "activation": "relu2",
        "route_from": "ff_input", "combine_rows": 160, "combine_tile": 128,
        "picked": "compare",
    }]


# -- Mamba-2 in groups --------------------------------------------------------

HEADS, P, N, GROUPS = 8, 8, 16, 4


def mamba_config(heads=HEADS, groups=GROUPS):
    return {
        "mamba_num_heads": heads, "mamba_head_dim": P, "n_groups": groups,
        "ssm_state_size": N, "use_conv_bias": True, "layer_norm_epsilon": 1e-5,
    }


def mamba_layer(heads=HEADS, groups=GROUPS):
    return Mamba2Mixer(MambaSpec(heads, P, N, n_groups=groups, chunk=16), jnp.float32, 1e-5)


@pytest.fixture(scope="module")
def grouped_mixer():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 48, 32), jnp.float32)
    params = shaken(jax.jit(mamba_layer().init)(jax.random.PRNGKey(1), x)["params"], seed=3)
    return params, x


def test_the_grouped_mixer_equals_the_recurrence_with_the_norm_by_group(grouped_mixer):
    params, x = grouped_mixer
    with jax.default_matmul_precision("highest"):
        got = mamba_layer().apply({"params": params}, x)
        want = reference.mamba_mixer(mamba_config(), params, x)
        over_all = ssm_reference.mamba_mixer(
            {"mamba_n_heads": HEADS, "mamba_d_head": P, "mamba_n_groups": GROUPS,
             "mamba_d_state": N, "mamba_conv_bias": True, "rms_norm_eps": 1e-5}, params, x,
        )
    _close(got, want)
    # the norm over all d_inner is another function: the groups' scales differ
    assert float(jnp.max(jnp.abs(over_all - want))) > 1e-2 * float(jnp.max(jnp.abs(want)))


def half_of(params, half):
    """The parameters of heads ``half * H/2 ..`` in groups ``half * G/2 ..``: a
    half of each of ``W_in``'s five segments and of the convolution's three."""
    d_inner, gn = HEADS * P, GROUPS * N
    cut = lambda a, width: a[..., half * width // 2:(half + 1) * width // 2]  # noqa: E731

    def segments(a, widths):
        parts, at = [], 0
        for width in widths:
            parts.append(cut(a[..., at:at + width], width))
            at += width
        return jnp.concatenate(parts, axis=-1)

    conv = (d_inner, gn, gn)
    rows = slice(half * d_inner // 2, (half + 1) * d_inner // 2)
    return {
        "in_proj": {"kernel": segments(params["in_proj"]["kernel"], (d_inner, *conv, HEADS))},
        "conv_kernel": segments(params["conv_kernel"], conv),
        "conv_bias": segments(params["conv_bias"], conv),
        "A_log": cut(params["A_log"], HEADS), "dt_bias": cut(params["dt_bias"], HEADS),
        "D": cut(params["D"], HEADS), "norm": params["norm"][rows],
        "out_proj": {"kernel": params["out_proj"]["kernel"][rows]},
    }


def test_the_two_halves_of_the_heads_concatenate_to_the_whole_before_w_out(grouped_mixer):
    """Two chips hold four heads in two groups each. What each computes before
    ``W_out`` is its channels of the whole mixer's, exactly: a head's state is
    its own and the norm a group's own; through their rows of ``W_out`` the two
    outputs sum to the whole's."""
    params, x = grouped_mixer
    gated = lambda left: left["intermediates"]["gated"][0]  # noqa: E731
    with jax.default_matmul_precision("highest"):
        sown = lambda layer: jax.jit(  # noqa: E731
            lambda p: layer.apply({"params": p}, x, mutable=["intermediates"])
        )
        whole_out, whole = sown(mamba_layer())(params)
        half_layer = sown(mamba_layer(HEADS // 2, GROUPS // 2))
        halves = [half_layer(half_of(params, half)) for half in (0, 1)]
        before_w_out = jax.jit(lambda p: reference.mamba_inner(mamba_config(), p, x))(params)
    together = jnp.concatenate([gated(left) for _, left in halves], axis=-1)
    _close(together, gated(whole), tol=1e-5)
    _close(together, before_w_out)
    _close(sum(out for out, _ in halves), whole_out, tol=1e-5)


def test_one_group_is_the_mixer_it_was():
    """``n_groups = 1``: the norm over all ``d_inner``, as ``reference/ssm_lm.py``
    (Granite's) writes it."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 32), jnp.float32)
    params = shaken(jax.jit(mamba_layer(groups=1).init)(jax.random.PRNGKey(1), x)["params"])
    config = {"mamba_n_heads": HEADS, "mamba_d_head": P, "mamba_n_groups": 1,
              "mamba_d_state": N, "mamba_conv_bias": True, "rms_norm_eps": 1e-5}
    with jax.default_matmul_precision("highest"):
        got = jax.jit(mamba_layer(groups=1).apply)({"params": params}, x)
        want = jax.jit(lambda p: ssm_reference.mamba_mixer(config, p, x))(params)
    _close(got, want)


# -- the block, the rules, the parts ------------------------------------------

def one_branch_lm(kinds, **changes):
    spec = dict(
        vocab_size=64, d_model=32, num_heads=2, num_layers=len(kinds), d_ff=48,
        moe=MoESpec(num_experts=8, top_k=2, d_ff=16, shared_d_ff=24, gated=False,
                    activation="relu2", latent=16),
        arch=ArchSpec(layer_types=tuple(kinds), mamba=MambaSpec(4, 8, 8, n_groups=2, chunk=8),
                      rope=False, one_branch=True),
    )
    spec.update(changes)
    return TransformerLM(**spec)


def test_a_dense_feed_forward_can_be_a_blocks_one_branch():
    lm = one_branch_lm(("attention", "mlp", "mamba", "moe"), dtype=jnp.float32)
    x = np.zeros((1, 16), np.int32)
    params = jax.jit(lm.init)(jax.random.PRNGKey(0), x)["params"]
    assert [set(params["layer_%d" % i]) - {"ln1"} for i in range(4)] == [
        {"attn"}, {"mlp"}, {"mamba"}, {"moe"}
    ]
    assert set(params["layer_1"]["mlp"]) == {"gate", "up", "down"}       # the SwiGLU of d_ff
    assert jax.jit(lm.apply)({"params": params}, x).shape == (1, 16, 64)


@pytest.mark.parametrize("kind", ["mamba", "attention", "moe", "mlp"])
def test_a_decode_call_on_a_one_branch_block_raises(kind):
    lm = one_branch_lm((kind,), decode=True)
    with pytest.raises(NotImplementedError, match="no decode"):
        lm.init(jax.random.PRNGKey(0), np.zeros((1, 4), np.int32))


def test_a_feed_forward_block_needs_one_branch_and_its_layer():
    x = np.zeros((1, 4), np.int32)
    two_branches = one_branch_lm(("moe",), arch=ArchSpec(layer_types=("moe",)))
    with pytest.raises(ValueError, match="under one_branch .* moe, mlp"):
        two_branches.init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="needs TransformerLM.moe"):
        one_branch_lm(("moe",), moe=None).init(jax.random.PRNGKey(0), x)


def test_the_rules_accept_the_new_leaves():
    """``MOE_EP_RULES`` split the two banks of an ungated expert over ``ep`` and
    leave the latent's projections, the router and the shared expert whole;
    the tensor-parallel rules take a one-branch dense block's SwiGLU and the
    attention block and have nothing to say of a Mamba-2 block's leaves."""
    lm = one_branch_lm(("mamba", "moe", "attention", "mlp"))
    params = jax.jit(lm.init)(jax.random.PRNGKey(0), np.zeros((1, 16), np.int32))["params"]
    rules = TRANSFORMER_TP_RULES + MOE_EP_RULES
    from edl_tpu.parallel.mesh import make_mesh

    with make_mesh({"ep": 2, "tp": 2}, devices=jax.devices()[:4]) as mesh:
        placed = shard_params_by_rules(mesh, params, rules)
    spec = lambda a: tuple(a.sharding.spec)  # noqa: E731
    moe = placed["layer_1"]["moe"]
    assert spec(moe["up"])[0] == "ep" and spec(moe["down"])[0] == "ep"
    assert "gate" not in moe
    for whole in (moe["latent_down"]["kernel"], moe["latent_up"]["kernel"],
                  moe["router"]["kernel"], moe["shared"]["up"]["kernel"],
                  moe["shared"]["down"]["kernel"]):
        assert not any(spec(whole))
    assert all(not any(spec(leaf)) for leaf in jax.tree.leaves(placed["layer_0"]["mamba"]))
    assert spec(placed["layer_3"]["mlp"]["up"]["kernel"]) == (None, "tp")
    assert spec_for_path("/layer_1/moe/latent_up/kernel", rules) == ()


@pytest.fixture(scope="module")
def compiled_toy_step():
    lm = toy_lm(remat=True, dtype=jnp.bfloat16)
    x, y = toy_batch(b=1)
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.adamw(1e-3))
    return make_train_step(lm_loss, numerics=False).lower(state, (x, y)).compile().as_text()


def test_every_named_matmul_of_the_compiled_step_is_under_a_part(compiled_toy_step):
    """No matmul that carries a name reads ``block``, ``loss`` or ``other``. (The
    CPU's compiler expands ``ragged_dot`` into dots WITHOUT an ``op_name``, which
    no table can place; on the chip the grouped matmuls are Megablox calls under
    ``moe_experts``, and ``tests/test_tpu_compile.py`` holds the step as the chip
    lowers it to no unplaced matmul at all.)"""
    program = obs_profile.HloProgram(compiled_toy_step)
    census = program.census()
    assert census["totals"]["matmuls"] > 0
    unplaced = {
        key: row["unplaced_matmuls"] for key, row in census["parts"].items()
        if row.get("unplaced_matmuls")
    }
    assert set(unplaced) <= {"other/other"}                 # under no name and no pass
    nameless = [
        name for name, opcode in program.opcode.items()
        if opcode == "dot" and name not in program.own and program.home[name] in program.run
    ]
    assert unplaced.get("other/other", 0) <= len(nameless)
    parts = {key.split("/")[0] for key in census["parts"]}
    assert {"moe_latent", "moe_shared", "moe_route", "ssm_proj", "ssm_scan", "attn", "head"} <= parts
    assert "moe_latent" in {part for _, part in obs_profile.STEP_PARTS}


@pytest.mark.parametrize("scope", ("moe_latent", "moe_shared", "moe_route", "moe_experts",
                                   "moe_combine", "ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate"))
def test_the_compiled_step_names_the_layers_scopes(compiled_toy_step, scope):
    scopes = ("moe_latent", "moe_shared") + obs_profile.MOE_SCOPES + (
        "ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate")
    assert scope in set(obs_profile.scopes_of_hlo(compiled_toy_step, scopes).values())
