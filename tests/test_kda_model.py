"""Ling-3.0-flash's language model at toy sizes on the CPU (``tests/test_kda.py``
holds its three mechanisms and its layers): the whole ``TransformerLM`` of Kimi
delta attention, latent attention and grouped expert routing against
``benchmark/reference/kda_lm.py``, which imports nothing from ``edl_tpu.models``:
logits, loss and every gradient, a whole step through ``make_train_step``, the
gauges it exports, the scopes its compiled step names, what remat saves.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from conftest import loss_logits_gradients

from benchmark.families import kda_lm as family
from benchmark.reference import kda_lm as reference
from edl_tpu.models import (
    ArchSpec,
    KimiDeltaSpec,
    LatentAttentionSpec,
    TransformerLM,
)
from edl_tpu.models.gated_delta import KDA_SCOPES
from edl_tpu.models.transformer import LAYER_TYPES, MLA_SCOPES
from edl_tpu.obs import profile as obs_profile
from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs", "ling_3_0_flash_vl.json")) as f:
    TOY = json.load(f)
D = TOY["hidden_size"]


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    scale = max(np.max(np.abs(want)), 1e-12)
    assert np.max(np.abs(got - want)) / scale <= tol


def shaken(params, seed=7):
    """Every vector (a norm's scale, a decay's bias) off its start, so that a
    misplaced one shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 400))
    return jax.tree.map(
        lambda a: a * (1 + 0.2 * jax.random.normal(next(keys), a.shape)) if a.ndim <= 2 and a.size < 4096 else a,
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


def test_the_toy_is_the_published_pattern(toy_variables):
    params = toy_variables[0]
    assert TOY["layer_types"] == ["linear_attention", "linear_attention", "full_attention"]
    assert family.arch_spec(TOY).layer_types == ("kda", "kda", "latent_attention")
    assert {"kda", "latent_attention"} <= set(LAYER_TYPES)
    assert set(params["layer_0"]) == {"kda", "mlp", "ln1", "ln2"}       # the dense layer
    assert set(params["layer_1"]) == {"kda", "moe", "ln1", "ln2"}
    assert set(params["layer_2"]) == {"attn", "moe", "ln1", "ln2"}
    assert set(params["layer_2"]["moe"]) == {"router", "gate", "up", "down", "shared"}
    assert params["layer_1"]["moe"]["router"]["kernel"].shape == (D, 32)  # the whole router
    assert params["layer_1"]["moe"]["gate"].shape == (4, D, 64)           # the held experts
    assert params["lm_head"]["kernel"].shape == (D, 128)                  # an untied head


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
def test_the_lm_equals_the_plain_reference(program_outputs, reference_outputs, remat, what):
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
        want = reference_gradients
        _, info = reference.forward(TOY, params, stats, x)
    taken = jax.tree.map(lambda before, now: before - now, params, after.params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(taken), jax.tree.leaves(want)):
        assert float(jnp.linalg.norm(b)) > 0, jax.tree_util.keystr(path)
        _close(a, b, tol=2e-3)
    for j, i in enumerate(range(TOY["first_k_dense_replace"], TOY["num_hidden_layers"])):
        _close(
            after.batch_stats["layer_%d" % i]["moe"]["router_bias"], info["bias_after"][j],
            tol=1e-5,
        )
    assert float(metrics["moe_groups_live"]) <= TOY["topk_group"]
    assert float(metrics["moe_groups_live"]) == pytest.approx(float(jnp.mean(info["groups_live"])))
    assert 0.0 < float(metrics["kda_decay_mean"]) < 1.0


def test_the_lm_trains_through_the_step_and_exports_its_gauges():
    lm = toy_lm(remat=True, dtype=jnp.bfloat16)
    x, y = toy_batch(seed=1)
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.adamw(1e-3))
    assert {"kda_decay_mean", "kda_state_absmax", "moe_groups_live"} <= set(state.sown)
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
    for gauge in ("kda_decay_mean", "kda_state_absmax", "moe_groups_live"):
        assert "edl_train_%s " % gauge in rendered


@pytest.fixture(scope="module")
def scopes_of_the_compiled_step():
    lm = toy_lm(remat=True, dtype=jnp.bfloat16)
    x, y = toy_batch(b=1)
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.adamw(1e-3))
    compiled = make_train_step(lm_loss, numerics=False).lower(state, (x, y)).compile()
    table = obs_profile.scopes_of_hlo(compiled.as_text(), KDA_SCOPES + MLA_SCOPES + ("moe_route",))
    return set(table.values())


@pytest.mark.parametrize("scope", KDA_SCOPES + MLA_SCOPES + ("moe_route",))
def test_the_compiled_step_names_the_layers_scopes(scopes_of_the_compiled_step, scope):
    assert scope in scopes_of_the_compiled_step


def test_under_save_flash_the_rules_loop_runs_once_forward_and_once_in_reverse():
    """The KDA rule keeps the scalar rule's names: a block's recomputation
    under ``save_flash`` finds the carry's products saved and does not run the
    loop again (two ``while`` loops a KDA layer: one forward, one reverse)."""
    lm = TransformerLM(
        vocab_size=64, d_model=32, num_heads=2, num_layers=1, d_ff=32, dtype=jnp.float32,
        remat=True, remat_policy="save_flash",
        arch=ArchSpec(layer_types=("kda",), kda=KimiDeltaSpec(2, 8, 8, chunk=16)),
    )
    x = np.zeros((1, 64), np.int32)
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.sgd(0.1))
    text = make_train_step(lm_loss, numerics=False).lower(state, (x, x)).as_text()
    assert text.count("stablehlo.while") == 2
    full = lm.clone(remat_policy="full")
    state = create_state(full, jax.random.PRNGKey(0), x, optax.sgd(0.1))
    text = make_train_step(lm_loss, numerics=False).lower(state, (x, x)).as_text()
    assert text.count("stablehlo.while") == 3


@pytest.mark.parametrize("kind", ["kda", "latent_attention"])
def test_a_decode_call_on_the_new_blocks_raises(kind):
    lm = TransformerLM(
        vocab_size=64, d_model=32, num_heads=2, num_layers=1, d_ff=32, decode=True,
        arch=ArchSpec(layer_types=(kind,), kda=KimiDeltaSpec(2, 8, 8),
                      latent_attention=LatentAttentionSpec(8, 8, 4, 8)),
    )
    with pytest.raises(NotImplementedError, match="no decode"):
        lm.init(jax.random.PRNGKey(0), np.zeros((1, 4), np.int32))


def test_an_unknown_layer_type_names_the_new_ones_among_the_known():
    lm = TransformerLM(
        vocab_size=64, d_model=32, num_heads=2, num_layers=1, d_ff=32,
        arch=ArchSpec(layer_types=("delta",)),
    )
    with pytest.raises(ValueError, match="kda, latent_attention"):
        lm.init(jax.random.PRNGKey(0), np.zeros((1, 4), np.int32))


def test_a_bound_that_is_none_below_zero_is_refused():
    lm = TransformerLM(
        vocab_size=64, d_model=32, num_heads=2, num_layers=1, d_ff=32,
        arch=ArchSpec(layer_types=("kda",), kda=KimiDeltaSpec(2, 8, 8, lower_bound=0.0)),
    )
    with pytest.raises(ValueError, match="lower_bound"):
        lm.init(jax.random.PRNGKey(0), np.zeros((1, 4), np.int32))


def test_a_gate_whose_bound_the_parents_rule_could_not_hold_trains_finite():
    """A bound of -12 a step is past the 5.5 the rule held until PR 51 (sixteen
    steps of it leave float32 under a middle reference), and the mixer refused
    it. The rule now holds for any ``g <= 0``: with the gate driven to its
    bound on most channels the model's loss and every gradient stay finite
    through five steps."""
    lm = TransformerLM(
        vocab_size=64, d_model=32, num_heads=2, num_layers=2, d_ff=32, remat=True,
        arch=ArchSpec(layer_types=("kda", "kda"),
                      kda=KimiDeltaSpec(2, 16, 16, chunk=32, lower_bound=-12.0)),
    )
    x = np.random.default_rng(0).integers(0, 64, (2, 65)).astype(np.int32)
    x, y = x[:, :-1], x[:, 1:]
    state = create_state(lm, jax.random.PRNGKey(0), x, optax.adamw(1e-3))
    # the decay's bias far up: the sigmoid at 1, g at the bound
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 30.0 if "dt_bias" in jax.tree_util.keystr(path) else a, state.params
    )
    state = state.replace(params=params)
    step = make_train_step(lm_loss, numerics=True, donate=False)
    for _ in range(5):
        state, metrics = step(state, (x, y))
        assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["kda_log_decay_min"]) < -11.9
    assert all(bool(jnp.isfinite(a).all()) for a in jax.tree.leaves(state.params))
