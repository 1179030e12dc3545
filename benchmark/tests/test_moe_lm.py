"""The ``moe_lm`` family: its plain reference against the program at toy
widths on the CPU, its ``check`` passing on the program and failing on a wrong
one, and its operation counts against arithmetic done by hand."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import moe_lm
from benchmark.reference import moe_lm as reference

HERE = os.path.dirname(os.path.dirname(__file__))


def load(*parts, **overrides):
    with open(os.path.join(HERE, *parts)) as f:
        return dict(json.load(f), **overrides)


def toy(**overrides):
    return load("rehearsal", "configs", "olmoe_1b_7b.json", **overrides)


@pytest.mark.parametrize("norm", [False, True], ids=["raw", "norm_topk_prob"])
@pytest.mark.parametrize("layers", [1, 2])
def test_reference_agrees_with_the_program_in_float32(layers, norm):
    config = toy(num_hidden_layers=layers, norm_topk_prob=norm)
    job = moe_lm.build(config, 2, 0)
    model = job["model"].clone(dtype=jnp.float32, remat=False)
    tokens, targets = moe_lm.host_batches(config, 2, 0, n_batches=1)[0]
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    got, sown = model.apply({"params": params}, tokens, mutable=["losses"])
    with jax.default_matmul_precision("highest"):
        want, info = reference.forward(config, params, tokens)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    head, _ = job["loss"](got, targets)
    np.testing.assert_allclose(head, reference.cross_entropy(want, targets), rtol=1e-5)
    for term in ("load_balance", "router_z"):
        summed = sum(
            float(sown["losses"]["layer_%d" % i]["moe"][term][0]) for i in range(layers)
        )
        assert summed == pytest.approx(float(info[term]), rel=1e-5)


class State:
    def __init__(self, config):
        job = moe_lm.build(config, 2, 0)
        tokens, _ = moe_lm.host_batches(config, 2, 0, n_batches=1)[0]
        self.model = job["model"]
        self.params = self.model.init(jax.random.PRNGKey(0), tokens)["params"]
        self.apply_fn = self.model.apply


def test_check_passes_on_the_program_and_names_what_it_compared():
    config = toy()
    result = moe_lm.check(config, State(config), 0)
    assert result["ok"], result
    assert result["tokens_misrouted"] == 0
    assert result["flipped_share"] <= moe_lm.ROUTE_FLIP_LIMIT
    assert result["router_logits_rel_err"] <= moe_lm.ROUTER_LOGITS_REL_TOL
    assert set(result["aux_rel_err"]) == {"load_balance", "router_z"}
    assert result["grouped_matmul"]["rows"] == 2 * 128 * 2
    assert result["kernel"]["shape"][:3] == [2, 4, 4]     # the whole batch: never split


@pytest.mark.parametrize("fault", ["logits", "load_balance", "routing", "router"])
def test_check_fails_on_a_wrong_program(fault):
    config = toy()
    state = State(config)
    model = state.model

    def wrong(variables, tokens, **kwargs):
        logits, sown = model.apply(variables, tokens, **kwargs)
        if fault == "logits":
            logits = logits * 1.2
        elif fault == "load_balance":           # a dropped factor: f over tokens, not pairs
            moe = dict(sown["losses"]["layer_0"]["moe"])
            moe["load_balance"] = (moe["load_balance"][0] * 2,)
            sown = {**sown, "losses": {"layer_0": {"moe": moe}}}
        elif fault == "routing":                # one token to the experts next door
            moe = dict(sown["intermediates"]["layer_0"]["moe"])
            chosen = moe["top_idx"][0]
            moe["top_idx"] = (chosen.at[5].set((chosen[5] + 1) % config["num_experts"]),)
            sown = {**sown, "intermediates": {"layer_0": {"moe": moe}}}
        else:                                   # a router six bits short
            moe = dict(sown["intermediates"]["layer_0"]["moe"])
            z = moe["router_logits"][0]
            moe["router_logits"] = (z + 0.03 * jnp.max(jnp.abs(z)) * jnp.sign(z),)
            sown = {**sown, "intermediates": {"layer_0": {"moe": moe}}}
        return logits, sown

    state.apply_fn = wrong
    assert not moe_lm.check(config, state, 0)["ok"]


def test_olmoe_by_hand():
    config = load("configs", "olmoe_1b_7b.json")
    d, f, v, t = 2048, 1024, 50304, 4096
    attention = 4 * d * d                                  # q, k, v, o: MHA 16 x 128
    router = d * 64
    experts = 8 * 3 * d * f                                # the 8 a token meets
    assert (attention, router, experts, d * v) == (
        16_777_216, 131_072, 50_331_648, 103_022_592
    )
    assert moe_lm.matmul_params(config) == attention + router + experts + d * v
    causal = 3 * 2 * t * 16 * 128                          # forward x 3, half masked
    assert moe_lm.flops_per_item(config) == pytest.approx(
        6 * (attention + router + experts + d * v) + causal
    )
    assert moe_lm.flops_per_item(config) == pytest.approx(1.072e9, rel=0.001)
    # all sixteen layers: 6.9 B parameters held, 1.3 B met by a token
    full = dict(config, num_hidden_layers=16)
    held = 16 * (attention + router + 64 * 3 * d * f + 2 * d + 2 * d) + 2 * d * v + d
    assert held == pytest.approx(6.92e9, rel=0.002)
    assert moe_lm.matmul_params(full) + d * v == pytest.approx(1.28e9, rel=0.01)
    # the head's share of the counted work at depth 1, and the expert layer's
    assert 6 * d * v / moe_lm.flops_per_item(config) == pytest.approx(0.58, abs=0.01)
    assert 6 * experts / moe_lm.flops_per_item(config) == pytest.approx(0.28, abs=0.01)


def test_grouped_matmul_work_by_hand():
    config = load("configs", "olmoe_1b_7b.json")
    tokens = 4 * 4096
    rows = tokens * 8
    one = 2 * rows * 2048 * 1024                           # one grouped matmul
    assert one == pytest.approx(550e9, rel=0.001)
    assert moe_lm.moe_kernel_flops(config, tokens) == 9 * one   # 3 forward, 6 backward
    wide, narrow, bank = rows * 2048 * 2, rows * 1024 * 2, 64 * 2048 * 1024 * 2
    assert moe_lm.moe_kernel_bytes(config, tokens) == 9 * (wide + narrow + bank)
    # compute-bound on a v5e (197 TFLOP/s over 819 GB/s = 240 operations a byte)
    assert moe_lm.moe_kernel_flops(config, tokens) / moe_lm.moe_kernel_bytes(
        config, tokens
    ) > 400
    # the counted expert work of flops_per_item is the same nine matmuls
    assert moe_lm.moe_kernel_flops(config, tokens) == 6 * 8 * 3 * 2048 * 1024 * tokens


def test_the_file_keeps_every_published_number():
    """The catalog's ``config`` for OLMoE-1B-7B-0125-Instruct, key for key;
    only ``num_hidden_layers`` differs, and ``published`` holds its value."""
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 16,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304,
    }
    config = load("configs", "olmoe_1b_7b.json")
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == {"num_hidden_layers"}
    assert config["published"] == {"num_hidden_layers": 16}
    assert config["train"]["seq_len"] == 4096 and config["train"]["batch_per_chip"] == 4
