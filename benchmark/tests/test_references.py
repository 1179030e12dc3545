"""Both plain references against the program's models, at toy widths on the
CPU, on random parameters (a fresh ResNet has zero residual branches, which
would hide a wrong block)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import resnet_vd, transformer_lm
from benchmark.reference import resnet_vd as ref_resnet
from benchmark.reference import transformer_lm as ref_lm

REHEARSAL = os.path.join(
    os.path.dirname(os.path.dirname(__file__)), "rehearsal", "configs"
)


def load(name, **overrides):
    with open(os.path.join(REHEARSAL, name + ".json")) as f:
        return dict(json.load(f), **overrides)


def randomized(tree, seed):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten([
        leaf + 0.3 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)
    ])


@pytest.mark.parametrize("block,stages", [("bottleneck", [1, 2]), ("basic", [2, 1])])
def test_resnet_reference_agrees_with_the_program_in_float32(block, stages):
    config = load("resnet50_vd", block=block, stage_sizes=stages)
    job = resnet_vd.build(config, 4, 0)
    model = job["model"].clone(dtype=jnp.float32)  # the same arithmetic, exactly
    x, y = resnet_vd._items(config, 3, 4)
    variables = model.init(jax.random.PRNGKey(0), x)
    params = randomized(variables["params"], 1)
    got, _ = model.apply(
        {"params": params, "batch_stats": variables["batch_stats"]}, x,
        train=True, mutable=["batch_stats"],
    )
    want = ref_resnet.forward(config, params, x)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    head_loss, _ = job["loss"](got, y)
    np.testing.assert_allclose(head_loss, ref_resnet.loss(want, y), rtol=1e-5)


def test_resnet_check_passes_on_the_program_and_fails_on_a_wrong_one():
    config = load("resnet50_vd")
    job = resnet_vd.build(config, 8, 0)
    x, _ = resnet_vd._items(config, 3, 8)
    variables = job["model"].init(jax.random.PRNGKey(0), x)

    class State:
        params = randomized(variables["params"], 1)
        batch_stats = variables["batch_stats"]
        apply_fn = staticmethod(job["model"].apply)

    assert resnet_vd.check(config, State, 0)["ok"]
    # logits off by a fifth are a different model

    def wrong(variables, x, **kwargs):
        logits, mutated = job["model"].apply(variables, x, **kwargs)
        return logits * 1.2, mutated

    State.apply_fn = staticmethod(wrong)
    assert not resnet_vd.check(config, State, 0)["ok"]


def test_lm_reference_agrees_with_the_program_in_float32():
    config = load("mistral_7b", num_hidden_layers=2)
    job = transformer_lm.build(config, 2, 0)
    model = job["model"].clone(dtype=jnp.float32, remat=False)
    tokens, targets = transformer_lm.host_batches(config, 2, 0, n_batches=1)[0]
    params = randomized(model.init(jax.random.PRNGKey(0), tokens)["params"], 1)
    got = model.apply({"params": params}, tokens)
    want = ref_lm.forward(config, params, tokens)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    loss, _ = job["loss"](got, targets)
    np.testing.assert_allclose(loss, ref_lm.loss(want, targets), rtol=1e-5)


def test_lm_reference_reads_eps_and_theta_from_the_configuration():
    config = load("mistral_7b")
    job = transformer_lm.build(config, 2, 0)
    tokens, _ = transformer_lm.host_batches(config, 2, 0, n_batches=1)[0]
    params = job["model"].init(jax.random.PRNGKey(0), tokens)["params"]
    base = ref_lm.forward(config, params, tokens)
    other = ref_lm.forward(dict(config, rope_theta=500.0), params, tokens)
    assert float(jnp.max(jnp.abs(base - other))) > 1e-3


def test_dense_attention_is_causal_and_grouped():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 8, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 8, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 8, 16))
    out = ref_lm.causal_attention(q, k, v)
    # position 0 sees only itself: its output is v[0] of its own kv head
    np.testing.assert_allclose(out[0, 0, 0], v[0, 0, 0], rtol=1e-5)
    np.testing.assert_allclose(out[0, 3, 0], v[0, 1, 0], rtol=1e-5)
    # a change to a later key moves no earlier output
    k2 = k.at[:, :, 5].add(1.0)
    np.testing.assert_allclose(
        ref_lm.causal_attention(q, k2, v)[:, :, :5], out[:, :, :5], rtol=1e-5
    )
