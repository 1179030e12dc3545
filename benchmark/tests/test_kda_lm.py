"""The ``kda_lm`` family: its operation and byte counts against arithmetic done
by hand, its reference against the program at toy widths (and what each limit
of ``check`` is for), the configuration file against the published one, its
readers on a hand-made trace, and the rehearsal of its cell."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import kda_timeline
from benchmark.families import kda_lm
from benchmark.reference import kda_lm as reference
from benchmark.tests.test_rehearse import NEEDS_A_DEVICE, run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "ling_3_0_flash_vl.json")
TOY = load("rehearsal", "configs", "ling_3_0_flash_vl.json")
BENCH = load("..", "BENCHMARK.json")
CELL = "ling_3_0_flash_vl.steady"


def test_ling_3_0_flash_vl_by_hand():
    d, t, h, hd = 2560, 8192, 16, 128
    kda = 6 * d * h * hd + d * h                    # q, k, v, decay, gate, out; beta
    assert kda_lm.kda_mixer_params(CONFIG) == kda == 31_498_240
    mla = d * h * 192 + d * 576 + 512 * h * 256 + h * 128 * d + d * h
    assert kda_lm.mla_mixer_params(CONFIG) == mla == 16_719_872
    dense = 3 * d * 6144
    assert dense == 47_185_920
    # the router at its published width, the shared expert, and 8 x 8 / 512 =
    # 1/8 routed expert a token, expected
    assert kda_lm.routed_experts_a_token(CONFIG) == 0.125
    expert_layer = d * 512 + 3 * d * 768 + 0.125 * 3 * d * 768
    assert expert_layer == 7_946_240
    head = d * 19_648
    params = 5 * kda + mla + dense + 5 * expert_layer + head
    assert kda_lm.layers(CONFIG, "linear_attention") == 5
    assert kda_lm.layers(CONFIG, "full_attention") == 1
    assert kda_lm.matmul_params(CONFIG) == params == 311_427_072
    attention = 2 * h * (t * t / 2) * (192 + 128)   # scores at 192, values at 128
    assert kda_lm.attention_forward_flops(CONFIG, 3) == 3 * attention
    rule = h * (64 * 5 * hd + 6 * hd * hd + 64 * 64 / 3)
    assert kda_lm.rule_forward_flops_per_token(CONFIG) == pytest.approx(rule)
    want = 6 * params + 3 * attention / t + 3 * 5 * rule
    assert kda_lm.flops_per_item(CONFIG) == pytest.approx(want)
    assert kda_lm.flops_per_item(CONFIG) == pytest.approx(2.0281e9, rel=0.001)
    # the mixers' projections are most of the counted work
    assert 6 * (5 * kda + mla) / want == pytest.approx(0.515, abs=0.002)


def test_the_kernels_work_by_hand():
    t, h = 8192, 16
    pairs = h * t * t / 2
    # forward: scores (192) and values (128); backward: scores again, dP (128),
    # dV (128), dK and dQ (192 each)
    assert kda_lm.kernel_flops(CONFIG, 2) == 2 * 2 * pairs * (4 * 192 + 3 * 128)
    tokens = 3 * t
    assert kda_lm.kda_scan_flops(CONFIG, tokens) == pytest.approx(
        3 * kda_lm.rule_forward_flops_per_token(CONFIG) * tokens * 5
    )
    a_token = 2 * 3 * h * 128 + 4 * h * 128 + 4 * h  # q, k, v; g; beta
    assert kda_lm.kda_scan_bytes(CONFIG, tokens) == (
        (a_token + 2 * h * 128) + (a_token + 2 * h * 128 + a_token)
    ) * tokens * 5
    rows = tokens * 0.125
    assert kda_lm.moe_kernel_flops(CONFIG, tokens) == 6 * 3 * rows * 2560 * 768 * 5


def test_the_configuration_keeps_every_published_width():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Ling-3.0-flash-VL")
    entry = next(c for c in BENCH["configs"] if c["name"] == "ling_3_0_flash_vl")
    assert entry["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k, "absent") != v}
    assert differs == set(entry["reduced"]) - {"layer_types"}
    for key in entry["reduced"]:
        assert CONFIG["published"][key] != CONFIG[key]
        if key in row["config"]:
            assert CONFIG["published"][key] == row["config"][key]
    for key, width in (
        ("hidden_size", 2560), ("intermediate_size", 6144), ("moe_intermediate_size", 768),
        ("head_dim", 128), ("kv_lora_rank", 512), ("qk_nope_head_dim", 128),
        ("qk_rope_head_dim", 64), ("v_head_dim", 128), ("num_experts_per_tok", 8),
        ("n_group", 8), ("topk_group", 4), ("routed_scaling_factor", 2.5),
        ("moe_shared_expert_intermediate_size", 768), ("short_conv_kernel_size", 4),
        ("kda_lower_bound", -5), ("rope_theta", 6_000_000),
    ):
        assert CONFIG[key] == width
    share = CONFIG["share"]
    assert share["router_experts"] == 512 == CONFIG["published"]["num_experts"]
    assert share["chips_a_layer"] == 64 and share["chips_a_heads"] == 2
    assert CONFIG["num_experts"] * share["chips_a_layer"] == 512
    assert CONFIG["vocab_size"] * share["chips_a_vocabulary"] == 157_184
    assert CONFIG["num_attention_heads"] * share["chips_a_heads"] == 32
    # the published rule written out, and the layers run a slice of it
    rule = ["full_attention" if (i + 1) % 6 == 0 else "linear_attention" for i in range(42)]
    assert CONFIG["published"]["layer_types"] == rule
    assert CONFIG["layer_types"] == rule[1:7]
    spec = kda_lm.moe_spec(CONFIG)
    assert (spec.num_experts, spec.held, spec.n_group, spec.topk_group) == (512, (0, 8), 8, 4)
    arch = kda_lm.arch_spec(CONFIG)
    assert arch.layer_types.count("kda") == 5 and arch.layer_types[4] == "latent_attention"
    assert arch.kda.num_heads == 16 and arch.kda.key_dim == 128
    assert arch.latent_attention.head_gate and arch.rope_theta == 6e6


def test_the_toy_twin_has_every_mechanism():
    assert set(TOY["layer_types"]) == {"linear_attention", "full_attention"}
    assert TOY["first_k_dense_replace"] == 1 and TOY["n_group"] > TOY["topk_group"] > 1
    assert TOY["num_experts"] < TOY["share"]["router_experts"]
    assert TOY["v_head_dim"] != TOY["qk_nope_head_dim"] + TOY["qk_rope_head_dim"]
    assert TOY["train"]["seq_len"] > TOY["train"]["rule_chunk"]     # a carried state


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        source = f.read().split('"""', 2)[2]                # after the docstring
    assert "edl_tpu" not in source
    assert "jax.lax.scan" in source                         # the rule a step at a time


@pytest.fixture(scope="module")
def toy_state():
    job = kda_lm.build(TOY, 1, 0)
    model = job["model"]
    tokens = kda_lm.host_batches(TOY, 1, 0, n_batches=1)[0][0]
    variables = model.init(jax.random.PRNGKey(0), tokens)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 200))
    params = jax.tree.map(
        lambda a: a * (1 + 0.2 * jax.random.normal(next(keys), a.shape)) if a.ndim == 1 else a,
        variables["params"],
    )

    def some_bias(a):  # as the rule leaves it: its mean at zero
        b = 0.02 * jax.random.normal(next(keys), a.shape)
        return b - jnp.mean(b)

    return model, params, jax.tree.map(some_bias, variables["batch_stats"]), tokens


def test_the_reference_agrees_with_the_program_in_float32(toy_state):
    model, params, stats, tokens = toy_state
    exact = model.clone(dtype=jnp.float32, remat=False)
    with jax.default_matmul_precision("highest"):
        got, left = exact.apply(
            {"params": params, "batch_stats": stats}, tokens,
            mutable=["intermediates", "metrics"],
        )
        want, info = reference.forward(TOY, params, stats, tokens)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    for j, i in enumerate(range(TOY["first_k_dense_replace"], TOY["num_hidden_layers"])):
        seen = left["intermediates"]["layer_%d" % i]["moe"]
        np.testing.assert_allclose(
            seen["router_logits"][0], info["router_logits"][j], rtol=1e-4, atol=1e-5
        )
        np.testing.assert_array_equal(
            np.sort(seen["top_idx"][0], axis=-1), np.sort(info["experts"][j], axis=-1)
        )
        sown = left["metrics"]["layer_%d" % i]["moe"]
        assert float(sown["moe_groups_live"][0]) == pytest.approx(
            float(info["groups_live"][j]), abs=1e-6
        )
        assert float(info["groups_live"][j]) <= TOY["topk_group"]


def _state(model, params, stats, apply_fn=None):
    return types.SimpleNamespace(
        params=params, batch_stats=stats, apply_fn=apply_fn or model.apply
    )


def test_check_passes_the_program_at_toy_widths(toy_state):
    model, params, stats, _ = toy_state
    result = kda_lm.check(TOY, _state(model, params, stats), 0)
    assert result["ok"], result
    assert result["rows_dropped"] == 0 and result["tokens_misrouted"] == 0
    assert result["router_arithmetic_rel_err"] <= 1e-6
    assert result["router_arithmetic_rel_err_of_a_bfloat16_router"] > (
        10 * kda_lm.ROUTER_ARITHMETIC_REL_TOL
    )
    assert result["rule"]["shape"][0] == [1, 128, 4, 16] and result["rule"]["chunk"] == 32
    assert set(result["rule"]["inputs"]) == set(kda_lm.RULE_ARGS)
    # the drawn inputs reach the safe gate's bound, the trained ones need not
    assert result["rule_drawn"]["log_decay_min"] < -4.99
    assert result["kernel"]["shape"] == [1, 4, 128, 24, 16]
    assert result["grouped_matmul"]["groups"] == 4           # the held experts'
    assert all(g <= TOY["topk_group"] for g in result["groups_live"])


def _changed(tree, path, change):
    tree = jax.tree.map(lambda a: a, tree)
    node = tree
    for key in path[:-1]:
        node[key] = dict(node[key])
        node = node[key]
    node[path[-1]] = change(node[path[-1]])
    return tree


@pytest.mark.parametrize("fault", [
    "a_dropped_tap", "a_dropped_head_gate", "no_latent_norm",
    "a_dropped_bias", "a_bias_not_moved", "a_bfloat16_router", "no_groups",
])
def test_check_fails_a_program_that_leaves_part_of_the_model_out(toy_state, fault):
    """Each mechanism's absence fails one of the check's limits: the program is
    given other parameters than the reference (a tap of zeros, a head gate of
    zeros' sigmoid, a latent's norm with another scale), a bias of zeros or one
    it does not move, a router rounded to bfloat16, or a choice
    that keeps all the groups. (Another rotation base moves the toy's logits by
    0.08 to 0.13 over 128 positions, under the limit a bfloat16 stream needs at
    the published widths: the base is held by the float32 comparison above, to
    2e-4, and by ``tests/test_kda.py``.)"""
    model, params, stats, _ = toy_state

    def apply_with(change_params=None, change_stats=None, freeze_bias=False,
                   coarse_router=False, other=None):
        def apply_fn(variables, tokens, **kwargs):
            variables = dict(variables)
            if change_params:
                variables["params"] = change_params(variables["params"])
            if change_stats:
                variables["batch_stats"] = change_stats(variables["batch_stats"])
            out = (other or model).apply(variables, tokens, **kwargs)
            if not kwargs.get("mutable"):
                return out
            logits, left = out
            left = jax.tree.map(lambda a: a, dict(left))
            if freeze_bias:
                left["batch_stats"] = variables["batch_stats"]
            if coarse_router:
                for layer in left["intermediates"].values():
                    if "moe" not in layer:
                        continue
                    moe = dict(layer["moe"])
                    moe["router_logits"] = tuple(
                        a.astype(jnp.bfloat16).astype(jnp.float32)
                        for a in moe["router_logits"]
                    )
                    layer["moe"] = moe
            return logits, left
        return apply_fn

    zero = jnp.zeros_like
    ungrouped = dict(TOY, topk_group=TOY["n_group"])        # every group kept: no limit
    apply_fn, failed_by = {
        "a_dropped_tap": (apply_with(lambda p: _changed(
            p, ("layer_1", "kda", "k_conv"), lambda w: w.at[0].set(0.0))), "logits_rel_err"),
        "a_dropped_head_gate": (apply_with(lambda p: _changed(
            p, ("layer_2", "attn", "g", "kernel"), zero)), "logits_rel_err"),
        "no_latent_norm": (apply_with(lambda p: _changed(
            p, ("layer_2", "attn", "kv_norm", "scale"), lambda w: 3.0 * w)), "logits_rel_err"),
        "a_dropped_bias": (apply_with(change_stats=lambda s: _changed(
            s, ("layer_1", "moe", "router_bias"), zero)), "tokens_misrouted"),
        "a_bias_not_moved": (apply_with(freeze_bias=True), "bias_abs_err"),
        "a_bfloat16_router": (apply_with(coarse_router=True), "router_arithmetic_rel_err"),
        "no_groups": (apply_with(other=model.clone(moe=kda_lm.moe_spec(ungrouped))),
                      "tokens_misrouted"),
    }[fault]
    result = kda_lm.check(TOY, _state(model, params, stats, apply_fn), 0)
    assert not result["ok"]
    limit = {"logits_rel_err": kda_lm.LOGITS_REL_TOL, "tokens_misrouted": 0,
             "bias_abs_err": kda_lm.BIAS_ABS_TOL,
             "router_arithmetic_rel_err": kda_lm.ROUTER_ARITHMETIC_REL_TOL}[failed_by]
    assert result[failed_by] > limit, (failed_by, result[failed_by])


def kda_of_layer_0(toy_state):
    model, params, _, tokens = toy_state
    x = jnp.asarray(params["embed"]["embedding"])[tokens[:1]].astype(jnp.bfloat16)
    return params["layer_0"]["kda"], x


MIXER_FAULTS = ["no_silu", "no_l2_norm", "no_lower_bound", "one_decay_a_head"]


@pytest.mark.parametrize("fault", MIXER_FAULTS)
def test_a_wrong_mixer_fails_the_rules_inputs_check(toy_state, fault, monkeypatch):
    """What the program's first layer hands its rule is held to the reference's
    forms: a dropped SiLU or L2 norm, a gate without its lower bound and one
    decay a head where the layer has one a channel each read far over the
    limit that judges it (``RULE_INPUTS_REL_TOL`` for q, k, v and beta,
    ``RULE_DECAY_REL_TOL`` for g); the program as it is reads under both."""
    from edl_tpu.models import KimiDeltaMixer
    from edl_tpu.models import gated_delta as mixer_module

    p, x = kda_of_layer_0(toy_state)
    good = kda_lm.rule_vs_reference(TOY, p, x)
    assert good["inputs_rel_err"] <= kda_lm.RULE_INPUTS_REL_TOL
    assert good["decay_rel_err"] <= kda_lm.RULE_DECAY_REL_TOL
    spec = kda_lm.kda_spec(TOY)
    if fault == "no_silu":
        monkeypatch.setattr(
            mixer_module, "causal_conv_silu",
            lambda m, taps, bias: reference.causal_conv(
                m.astype(jnp.float32), taps, 0.0).astype(m.dtype),
        )
    elif fault == "no_l2_norm":
        monkeypatch.setattr(mixer_module, "_unit", lambda m: m)
    elif fault == "no_lower_bound":
        spec = kda_lm.kda_spec(dict(TOY, kda_lower_bound=-1))
    mixer = KimiDeltaMixer(spec, jnp.bfloat16, TOY["rms_norm_eps"]).apply
    if fault == "one_decay_a_head":
        def mixer(variables, x, mutable, apply=mixer):  # noqa: F811
            out, sown = apply(variables, x, mutable=mutable)
            q, k, v, g, beta = sown["intermediates"]["rule_inputs"][0]
            g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
            sown = dict(sown, intermediates={"rule_inputs": ((q, k, v, g, beta),)})
            return out, sown
    bad = kda_lm.rule_vs_reference(TOY, p, x, mixer=mixer)
    if fault in ("no_lower_bound", "one_decay_a_head"):
        assert bad["decay_rel_err"] > 5 * kda_lm.RULE_DECAY_REL_TOL, bad["inputs"]
    else:
        assert bad["inputs_rel_err"] > 10 * kda_lm.RULE_INPUTS_REL_TOL, bad["inputs"]


def test_a_bfloat16_carried_state_fails_the_float32_runs_check(monkeypatch):
    """The precision below the stated one inside the rule: a state carried from
    chunk to chunk in bfloat16 fails the exact run's two limits on the drawn
    inputs, which the rule as it is passes by two orders of magnitude."""
    from edl_tpu.ops import gated_delta as rule_module

    good = kda_lm.rule_vs_reference(TOY, None, None, seed=5)
    assert good["exact_rel_err"] <= kda_lm.EXACT_REL_TOL / 30
    assert good["exact_state_rms_err"] <= kda_lm.EXACT_STATE_RMS_TOL / 30
    assert good["rel_err"] <= kda_lm.RULE_REL_TOL and good["state_rms_err"] <= kda_lm.STATE_RMS_TOL
    carry = rule_module._carry

    def coarse(state, inputs):
        after, new = carry(state, inputs)
        return after.astype(jnp.bfloat16).astype(jnp.float32), new

    monkeypatch.setattr(rule_module, "_carry", coarse)
    bad = kda_lm.rule_vs_reference(TOY, None, None, seed=5)
    assert bad["exact_rel_err"] > 3 * kda_lm.EXACT_REL_TOL
    assert bad["exact_state_rms_err"] > 3 * kda_lm.EXACT_STATE_RMS_TOL


def test_a_rule_with_one_decay_a_head_fails_the_rules_own_limits():
    """The scalar rule standing in for the per-channel one (the mean of a
    head's log-decays for every channel) reads far over ``RULE_REL_TOL`` on
    inputs whose channels decay at different rates."""
    from edl_tpu.ops import gated_delta_rule

    def scalar(q, k, v, g, beta, **kwargs):
        return gated_delta_rule(q, k, v, jnp.mean(g, axis=-1), beta, **kwargs)

    bad = kda_lm.rule_vs_reference(TOY, None, None, rule=scalar, seed=5)
    assert bad["rel_err"] > 5 * kda_lm.RULE_REL_TOL
    assert bad["state_rms_err"] > 5 * kda_lm.STATE_RMS_TOL


def test_the_two_width_kernels_comparison_fails_values_cut_to_the_keys_width():
    from edl_tpu.ops import attention

    good = kda_lm.mla_kernel_vs_reference(3, 1, 2, 128, 24, 16)
    assert good["max_rel_err"] <= kda_lm.KERNEL_REL_TOL
    half = lambda q, k, v, **kw: attention(q, k, v.at[..., 8:].set(0), **kw)  # noqa: E731
    bad = kda_lm.mla_kernel_vs_reference(3, 1, 2, 128, 24, 16, attn=half)
    assert bad["max_rel_err"] > 10 * kda_lm.KERNEL_REL_TOL


# the cell's six layers and its routing (8 of 64 experts held in 8 groups of
# 8, top-4 groups, top-8) at a width where bfloat16 reads what it reads at the
# published widths
WIDE = dict(
    TOY, hidden_size=256, intermediate_size=512, moe_intermediate_size=128,
    moe_shared_expert_intermediate_size=128, num_attention_heads=4, num_key_value_heads=4,
    head_dim=32, kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16, rotary_dim=16,
    v_head_dim=32, vocab_size=512, num_experts=8, num_experts_per_tok=8, n_group=8,
    topk_group=4, num_hidden_layers=6, layer_types=CONFIG["layer_types"],
    share=dict(TOY["share"], router_experts=64, experts_first=0),
    train=dict(TOY["train"], seq_len=512, rule_chunk=64),
)


@pytest.mark.parametrize("dtype, passes", [("bfloat16", True), ("float8_e4m3fn", False)])
def test_the_precision_below_fails_the_streams_limits(monkeypatch, dtype, passes):
    """The stated precision (bfloat16 compute) passes ``check``; an 8-bit float
    under the same program, the nearest precision below, fails the logits', the
    routers' and the flips' limits, each with room. No 8-bit float holds the
    rule's decayed operands at all (e^+-40 where ``float8_e4m3fn`` ends at 448:
    the logits are then not finite, which fails the check by itself), so the
    8-bit program here hands its rule bfloat16 operands."""
    from edl_tpu.models import gated_delta as mixer_module
    from edl_tpu.models import transformer

    rope = transformer.rope  # jax promotes no 8-bit float: rotate it as float32
    monkeypatch.setattr(
        transformer, "rope",
        lambda x, positions, theta: rope(x.astype(jnp.float32), positions, theta).astype(x.dtype)
        if x.dtype.itemsize == 1 else rope(x, positions, theta),
    )
    rule = mixer_module.kda_rule

    def rule_in_bfloat16(q, k, v, g, beta, **kwargs):
        if q.dtype.itemsize > 1:
            return rule(q, k, v, g, beta, **kwargs)
        o, state = rule(*(m.astype(jnp.bfloat16) for m in (q, k, v)), g, beta, **kwargs)
        return o.astype(q.dtype), state

    monkeypatch.setattr(mixer_module, "kda_rule", rule_in_bfloat16)
    model = kda_lm.build(WIDE, 1, 0)["model"]
    tokens = kda_lm.host_batches(WIDE, 1, 0, n_batches=1)[0][0]
    variables = model.init(jax.random.PRNGKey(0), tokens)
    coarse = model.clone(dtype=getattr(jnp, dtype), remat=False)
    result = kda_lm.check(
        WIDE, _state(coarse, variables["params"], variables["batch_stats"]), 0
    )
    assert result["tokens_misrouted"] == 0                  # a flip is still the rounding's
    readings = {
        name: result[name] / limit for name, limit in (
            ("logits_rel_err", kda_lm.LOGITS_REL_TOL),
            ("router_logits_rel_err", kda_lm.ROUTER_LOGITS_REL_TOL),
            ("flipped_share", kda_lm.ROUTE_FLIP_LIMIT),
        )
    }
    if passes:
        assert result["ok"], result
        assert all(r < 0.6 for r in readings.values()), readings
    else:
        assert not result["ok"]
        assert all(r > 1.5 for r in readings.values()), readings


def test_an_8_bit_mixer_fails_the_rules_inputs_limit(toy_state):
    """What an 8-bit mixer hands its rule (projections, convolutions and norms
    in ``float8_e4m3fn``) is over the limit for q, k and v by three times and
    more; the decay's own, wider limit is for the faults of form."""
    from edl_tpu.models import KimiDeltaMixer

    p, x = kda_of_layer_0(toy_state)
    coarse = KimiDeltaMixer(kda_lm.kda_spec(TOY), jnp.float8_e4m3fn, TOY["rms_norm_eps"]).apply

    def mixer(variables, x, mutable):
        out, sown = coarse(variables, x.astype(jnp.float8_e4m3fn), mutable=mutable)
        q, k, v, g, beta = sown["intermediates"]["rule_inputs"][0]
        q, k, v = (m.astype(jnp.bfloat16) for m in (q, k, v))   # what the rule can hold
        return out, dict(sown, intermediates={"rule_inputs": ((q, k, v, g, beta),)})

    bad = kda_lm.rule_vs_reference(TOY, p, x, mixer=mixer)
    assert min(bad["inputs"][name] for name in "qkv") > 3 * kda_lm.RULE_INPUTS_REL_TOL


def test_the_references_route_keeps_the_choice_inside_the_best_groups():
    config = dict(TOY, n_group=4, topk_group=2, num_experts_per_tok=2)
    # group 0 holds the single best expert, groups 1 and 2 the best pairs
    scores = np.full((1, 16), 0.1, np.float32)
    scores[0, 0] = 0.9
    scores[0, 4:6] = 0.6
    scores[0, 8:10] = 0.5
    logits = jnp.log(scores / (1 - scores))
    weights, experts, margin, _ = reference.route(config, logits, jnp.zeros(16))
    # sums of the two best: 1.0, 1.2, 1.0 (a tie the argsort breaks for group 0), 0.2
    assert sorted(np.asarray(experts[0]).tolist()) in ([0, 4], [0, 5])
    assert float(jnp.sum(weights)) == pytest.approx(2.5, rel=1e-5)
    scores[0, 8:10] = 0.3                                   # no tie: groups 1 and 0
    _, experts, margin, _ = reference.route(
        config, jnp.log(scores / (1 - scores)), jnp.zeros(16)
    )
    assert sorted(np.asarray(experts[0]).tolist()) == [0, 4]
    # the 2nd's lead over the 3rd inside the kept groups is 0 (4 and 5 tie)
    assert float(margin[0]) == pytest.approx(0.0, abs=1e-6)


def test_the_references_rule_moves_the_bias_against_the_load():
    config = {"train": {"expert_bias_rate": 0.001}}
    after = reference.bias_update(config, jnp.zeros(4), jnp.asarray([10, 2, 6, 6]))
    np.testing.assert_allclose(after, [-0.001, 0.001, 0.0, 0.0], atol=1e-9)


def test_the_references_recurrence_is_the_equation_a_step():
    """Two steps by hand: the state decays a channel, loses what it holds along
    the key, gains the key times the value."""
    q = jnp.asarray([[[[1.0, 0.0]], [[0.0, 1.0]]]])         # [1, 2, 1, 2]
    k = jnp.asarray([[[[1.0, 0.0]], [[1.0, 0.0]]]])
    v = jnp.asarray([[[[2.0]], [[4.0]]]])
    g = jnp.log(jnp.asarray([[[[1.0, 1.0]], [[0.5, 0.25]]]]))
    beta = jnp.asarray([[[1.0], [0.5]]])
    o, state = reference.recurrence(q, k, v, g, beta)
    # step 1: S = k v^T = [[2], [0]], o = S^T q = 2
    # step 2: S' = diag(.5, .25) S = [[1], [0]]; u = .5 (4 - 1) = 1.5; S = [[2.5], [0]]
    np.testing.assert_allclose(o[0, :, 0, 0], [2.0, 0.0], atol=1e-6)
    np.testing.assert_allclose(state[0, 0, :, 0], [2.5, 0.0], atol=1e-6)


# -- the readers on a hand-made trace ---------------------------------------

def reader(name):
    import importlib.util

    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


TRACE = {
    "steps": 2, "step_busy_s_total": 1.0,
    "op_seconds": {"fusion.1": 0.10, "fusion.2": 0.20, "fusion.3": 0.04, "fusion.4": 0.06,
                   "attn_mla.1": 0.03, "attn_mla.2": 0.05, "fusion.5": 0.02, "fusion.6": 0.5},
    "op_text": {
        "fusion.1": "%fusion.1 = fusion(...)", "fusion.2": "%fusion.2 = fusion(...)",
        "fusion.3": "%fusion.3 = fusion(...)", "fusion.4": "%fusion.4 = fusion(...)",
        "attn_mla.1": "%attn_mla.1 = custom-call(...) tpu_custom_call",
        "attn_mla.2": "%attn_mla.2 = custom-call(...) tpu_custom_call",
        "fusion.5": "%fusion.5 = fusion(...)", "fusion.6": "%fusion.6 = fusion(...)",
    },
}
TABLE = {"fusion.1": "kda_proj", "fusion.2": "kda_scan", "fusion.3": "kda_conv",
         "fusion.4": "kda_gate", "attn_mla.1": "attn_mla", "attn_mla.2": "attn_mla",
         "fusion.5": "attn_mla"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def make_run(trace=TRACE, family=kda_lm, config=CONFIG):
    return types.SimpleNamespace(
        trace=trace, family=family, config=config, peaks=PEAKS, chips=1,
        items_per_step=8192, at_close={"registry": {}},
    )


@pytest.mark.parametrize("name,want", [
    ("kda_share", 100 * 0.40 / 1.0), ("kda_proj_ms", 50.0), ("kda_scan_ms", 100.0),
    ("kda_conv_ms", 20.0), ("kda_gate_ms", 30.0),
    ("attn_mla_ms", 40.0),                                  # the custom calls alone
])
def test_scope_readers_join_the_trace_to_the_programs_table(monkeypatch, name, want):
    from edl_tpu.obs import profile

    monkeypatch.setattr(profile, "step_scopes", lambda scopes: dict(TABLE))
    assert reader(name).read(make_run()) == pytest.approx(want)
    assert reader(name).read(make_run(trace=None)) is None
    monkeypatch.setattr(profile, "step_scopes", lambda scopes: {"fusion.6": "moe_route"})
    assert reader(name).read(make_run()) is None            # a model without the scopes


def test_the_rooflines_divide_the_familys_work_by_the_scopes_time(monkeypatch):
    from edl_tpu.obs import profile

    monkeypatch.setattr(profile, "step_scopes", lambda scopes: dict(TABLE))
    tokens = 8192 * 2
    least = max(kda_lm.kda_scan_flops(CONFIG, tokens) / 197e12,
                kda_lm.kda_scan_bytes(CONFIG, tokens) / 819e9)
    assert reader("kda_scan_roofline").read(make_run()) == pytest.approx(100 * least / 0.20)
    other = types.SimpleNamespace()                         # a family without the counts
    assert reader("kda_scan_roofline").read(make_run(family=other)) is None
    # the latent layer's kernels: the accepted readers find the custom calls
    # under ``attn_mla`` by the family's TRACE_KERNELS and count kernel_flops
    least = kda_lm.kernel_flops(CONFIG, 2) / 197e12
    assert reader("attn_kernel_roofline").read(make_run()) == pytest.approx(100 * least / 0.08)
    assert reader("attn_kernel_share").read(make_run()) == pytest.approx(100 * 0.08 / 1.0)


@pytest.mark.parametrize("name,gauge", [
    ("kda_decay_mean", "edl_train_kda_decay_mean"),
    ("expert_groups_live", "edl_train_moe_groups_live"),
])
def test_the_gauge_readers_read_the_registry(name, gauge):
    run = make_run()
    assert reader(name).read(run) is None                   # a program without the gauge
    run.at_close = {"registry": {gauge: {"": 3.5}}}
    assert reader(name).read(run) == 3.5


def test_the_timeline_file_lists_the_readers_for_the_familys_cell():
    extended = kda_timeline.with_kda(BENCH)
    by_name = {m["name"]: m for m in extended["per_layer"]}
    for name in kda_timeline.DEVICE_READERS:
        assert by_name[name]["workloads"] == [CELL]
        module = reader(name)
        assert (module.NAME, module.UNIT) == (name, by_name[name]["unit"])
        assert by_name[name]["source"] == module.SOURCE == "device_trace"
        assert by_name[name]["moves"] == module.MOVES == "throughput"
    for name in ("moe_share", "moe_kernel_roofline"):
        assert CELL in by_name[name]["workloads"]
    listed = {m["name"] for m in BENCH["per_layer"]}
    assert {"kda_decay_mean", "expert_groups_live"} <= listed
    for name in ("attn_kernel_share", "attn_kernel_roofline"):  # the latent layer's kernels
        assert CELL in by_name[name]["workloads"]
    assert not listed & set(kda_timeline.DEVICE_READERS)
    # what was there is there still, in its order
    assert [m["name"] for m in extended["per_layer"]][:len(BENCH["per_layer"])] == [
        m["name"] for m in BENCH["per_layer"]
    ]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(trace):
    proc, lines = run_cell(CELL, 1, "--trace", str(trace), "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["metrics"] == {}
    detail = json.loads(lines[-2])["detail"]
    result = detail["checks"]["reference"]
    assert result["ok"] and result["tokens_misrouted"] == 0
    if trace:
        assert {"kda_decay_mean", "expert_groups_live", "expert_bias_absmax",
                "expert_load_max", "expert_held_load_max"} <= set(detail["judged"])
        from benchmark import run as bench_run
        cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
        listed = {m["name"] for m in BENCH["per_layer"] if bench_run.applies(m, cell["name"])}
        assert listed - set(detail["judged"]) <= NEEDS_A_DEVICE
