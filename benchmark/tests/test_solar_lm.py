"""The ``solar_lm`` family: its operation and byte counts against arithmetic done
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

from benchmark import solar_timeline
from benchmark.families import solar_lm
from benchmark.reference import solar_lm as reference
from benchmark.tests.test_rehearse import NEEDS_A_DEVICE, run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "solar_open2_250b.json")
TOY = load("rehearsal", "configs", "solar_open2_250b.json")
BENCH = load("..", "BENCHMARK.json")
CELL = "solar_open2_250b.steady"


def test_solar_open2_250b_by_hand():
    d, t, h, hd = 4096, 8192, 8, 128
    # q, k, v, out; the decay's and the gate's pairs through 128; beta
    kda = 4 * d * h * hd + 2 * (d * hd + hd * h * hd) + d * h
    assert solar_lm.kda_mixer_params(CONFIG) == kda == 18_120_704
    gqa = 3 * d * 8 * hd + 2 * d * 1 * hd              # q, gate, out; k and v on one KV head
    assert solar_lm.gqa_mixer_params(CONFIG) == gqa == 13_631_488
    # the router at its published width, the shared expert, and 8 x 8 / 320 =
    # 1/5 routed expert a token, expected
    assert solar_lm.routed_experts_a_token(CONFIG) == 0.2
    expert_layer = d * 320 + 3 * d * 1280 + 0.2 * 3 * d * 1280
    assert expert_layer == pytest.approx(20_185_088)
    head = d * 24_576
    params = 3 * kda + gqa + 4 * expert_layer + head
    assert (solar_lm.layers(CONFIG, "linear"), solar_lm.layers(CONFIG, "softmax")) == (3, 1)
    assert solar_lm.matmul_params(CONFIG) == pytest.approx(params) == pytest.approx(249_397_248)
    attention = 2 * 8 * (t * t / 2) * (128 + 128)       # scores and values at 128
    assert solar_lm.attention_forward_flops(CONFIG, 3) == 3 * attention
    rule = h * (64 * 5 * hd + 6 * hd * hd + 64 * 64 / 3)
    assert solar_lm.rule_forward_flops_per_token(CONFIG) == pytest.approx(rule)
    want = 6 * params + 3 * attention / t + 3 * 3 * rule
    assert solar_lm.flops_per_item(CONFIG) == pytest.approx(want)
    assert solar_lm.flops_per_item(CONFIG) == pytest.approx(1.5568e9, rel=0.001)


def test_the_kernels_work_by_hand():
    t, h = 8192, 8
    pairs = 8 * t * t / 2
    assert solar_lm.kernel_flops(CONFIG, 2) == 2 * 2 * pairs * 7 * 128
    tokens = 3 * t
    assert solar_lm.kda_scan_flops(CONFIG, tokens) == pytest.approx(
        3 * solar_lm.rule_forward_flops_per_token(CONFIG) * tokens * 3
    )
    a_token = 2 * 3 * h * 128 + 4 * h * 128 + 4 * h      # q, k, v; g; beta
    assert solar_lm.kda_scan_bytes(CONFIG, tokens) == (
        (a_token + 2 * h * 128) + (a_token + 2 * h * 128 + a_token)
    ) * tokens * 3
    rows = tokens * 0.2
    assert solar_lm.moe_kernel_flops(CONFIG, tokens) == pytest.approx(
        6 * 3 * rows * 4096 * 1280 * 4
    )
    assert solar_lm.moe_kernel_bytes(CONFIG, tokens) == pytest.approx(
        9 * (rows * 4096 * 2 + rows * 1280 * 2 + 8 * 4096 * 1280 * 2) * 4
    )


def test_the_configuration_keeps_every_published_width():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Solar-Open2-250B")
    entry = next(c for c in BENCH["configs"] if c["name"] == "solar_open2_250b")
    assert entry["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k, "absent") != v}
    assert differs == set(entry["reduced"]) - {"linear_attn_config.num_heads"}
    for key in entry["reduced"]:
        assert key in CONFIG["published"]
        if key in row["config"]:
            assert CONFIG["published"][key] == row["config"][key] != CONFIG[key]
    # inside the changed group only the count of heads moved
    linear, published = CONFIG["linear_attn_config"], row["config"]["linear_attn_config"]
    assert {k for k in published if linear[k] != published[k]} == {"num_heads"}
    assert CONFIG["published"]["linear_attn_config.num_heads"] == published["num_heads"] == 64
    for key, width in (
        ("hidden_size", 4096), ("intermediate_size", 10240), ("moe_intermediate_size", 1280),
        ("head_dim", 128), ("num_experts_per_tok", 8), ("routed_scaling_factor", 1),
        ("n_shared_experts", 1), ("gqa_interval", 3), ("rms_norm_eps", 1e-5),
        ("first_k_dense_replace", 0),
    ):
        assert CONFIG[key] == width
    assert (linear["head_dim"], linear["short_conv_kernel_size"]) == (128, 4)
    share = CONFIG["share"]
    assert share["router_experts"] == 320 == CONFIG["published"]["n_routed_experts"]
    assert (share["chips_a_layer"], share["chips_a_heads"], share["chips_a_vocabulary"]) == (40, 8, 8)
    assert CONFIG["n_routed_experts"] * share["chips_a_layer"] == 320
    assert CONFIG["vocab_size"] * share["chips_a_vocabulary"] == 196_608
    assert linear["num_heads"] * share["chips_a_heads"] == 64
    assert CONFIG["num_attention_heads"] // CONFIG["num_key_value_heads"] == 64 // 8
    # the published rule written out, and the layers run its first period
    rule = [i for i in range(48) if i % (CONFIG["gqa_interval"] + 1) == 0]
    assert CONFIG["published"]["gqa_layers"] == rule == row["config"]["gqa_layers"]
    assert reference.layer_kinds(CONFIG) == ["softmax", "linear", "linear", "linear"]
    for section in ("published", "share", "deployment", "departures", "assumed", "not_run", "plan"):
        assert CONFIG[section], section
    spec = solar_lm.moe_spec(CONFIG)
    assert (spec.num_experts, spec.held, spec.top_k, spec.shared_d_ff) == (320, (0, 8), 8, 1280)
    arch = solar_lm.arch_spec(CONFIG)
    assert arch.layer_types == ("attention", "kda", "kda", "kda")
    assert (arch.rope, arch.attn_gate, arch.dense_layers, arch.head_dim) == (False, True, 0, 128)
    kda = arch.kda
    assert (kda.num_heads, kda.key_dim, kda.value_dim) == (8, 128, 128)
    assert (kda.lower_bound, kda.neg_eigval, kda.gate_rank, kda.chunk) == (None, True, 128, 64)


def test_the_parameters_are_the_issues_count():
    """840.9 M: a linear layer 18.14 M, the softmax layer 13.63 M, an expert
    layer 142.87 M (8 x 15.73 held, 15.73 shared, 1.31 router), embedding and
    head 201.33 M, the norms."""
    model = solar_lm.build(CONFIG, 1, 0)["model"]
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), np.zeros((1, 128), np.int32))
    )["params"]
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))  # noqa: E731
    assert count(shapes) == 840_874_392 == CONFIG["plan"]["tried"][0]["parameters"]
    assert count(shapes["layer_1"]["kda"]) == pytest.approx(18.14e6, rel=2e-3)
    assert count(shapes["layer_0"]["attn"]) == 13_631_488
    assert count(shapes["layer_2"]["moe"]) == pytest.approx(142.87e6, rel=1e-3)
    assert count(shapes["embed"]) + count(shapes["lm_head"]) == 2 * 4096 * 24_576
    kda = shapes["layer_1"]["kda"]
    assert kda["f_down"]["kernel"].shape == (4096, 128) and kda["f_up"]["kernel"].shape == (128, 1024)
    assert kda["g_up"]["bias"].shape == (1024,) and kda["b_proj"]["kernel"].shape == (4096, 8)


def test_the_toy_twin_has_every_mechanism():
    assert reference.layer_kinds(TOY) == ["softmax", "linear", "linear", "linear"]
    assert TOY["n_routed_experts"] < TOY["share"]["router_experts"]
    assert TOY["share"]["router_experts"] & (TOY["share"]["router_experts"] - 1)  # no power of two
    assert TOY["num_attention_heads"] > TOY["num_key_value_heads"] == 1
    assert TOY["train"]["seq_len"] > TOY["train"]["rule_chunk"]     # a carried state
    assert not TOY["use_rope"] and TOY["use_gqa_gate"] and TOY["kda_allow_neg_eigval"]
    assert not TOY["kda_use_full_proj"]


def test_no_batch_comes_twice_in_a_run_and_the_table_starts_at_rms_one():
    """What the issue's traffic leaves open, and what holds the routers near
    balance at its AdamW 4e-4 (PERF.md section 6, PR 51): more distinct
    batches than a run dispatches, and Keye's start for the embedding table."""
    pool = solar_lm.host_batches(TOY, 1, 3000005801)
    assert len(pool) == TOY["train"]["distinct_batches"] == CONFIG["train"]["distinct_batches"]
    assert len({tokens.tobytes() for tokens, _ in pool}) == len(pool)
    tokens, targets = pool[0]
    np.testing.assert_array_equal(tokens[:, 1:], targets[:, :-1])
    assert len(solar_lm.host_batches(TOY, 1, 0, n_batches=1)) == 1   # the tools' one batch
    with open(os.path.join(ROOT, "benchmark", "traffic", "steady.json")) as f:
        mix = json.load(f)
    # warm-up, the traced epoch and a window of BENCHMARK.json's seconds at 200
    # ms a step (the cell's step is 234): fewer steps than batches
    steps = mix["warmup_steps"] + mix["trace_steps"] + BENCH["run_seconds"] / 0.2
    assert steps < CONFIG["train"]["distinct_batches"]
    assert CONFIG["train"]["optimizer"] == {"name": "adamw", "lr": 0.0004}  # the issue's
    for config in (TOY, CONFIG):
        assert config["train"]["start"] == {"embedding_rms": 1.0, "head_rms": 0.0}
    rms = lambda a: float(np.sqrt(np.mean(np.square(a))))  # noqa: E731
    started = solar_lm.build(TOY, 1, 0)["model"].init(jax.random.PRNGKey(0), tokens)["params"]
    drawn = solar_lm.build(solar_lm.as_drawn(TOY), 1, 0)["model"].init(
        jax.random.PRNGKey(0), tokens
    )["params"]
    assert rms(started["embed"]["embedding"]) == pytest.approx(1.0, rel=0.05)
    assert rms(started["lm_head"]["kernel"]) == 0.0        # the optimum of uniform ids
    assert rms(drawn["lm_head"]["kernel"]) == pytest.approx(TOY["hidden_size"] ** -0.5, rel=0.05)
    # nothing else differs, and the head is trained like every other leaf
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(started), jax.tree.leaves(drawn)):
        if "lm_head" not in jax.tree_util.keystr(path):
            np.testing.assert_array_equal(a, b)
    logits = solar_lm.build(TOY, 1, 0)["model"].apply(
        {"params": started, "batch_stats": solar_lm.build(TOY, 1, 0)["model"].init(
            jax.random.PRNGKey(0), tokens)["batch_stats"]}, tokens
    )
    assert not np.any(np.asarray(logits))                   # uniform: a loss of ln vocab_size


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        source = f.read().split('"""', 2)[2]                # after the docstring
    assert "edl_tpu" not in source
    from benchmark.reference import kda_lm

    assert reference.recurrence is kda_lm.recurrence        # the rule a step at a time
    with open(kda_lm.__file__) as f:
        assert "jax.lax.scan" in f.read()


@pytest.fixture(scope="module")
def toy_state():
    job = solar_lm.build(solar_lm.as_drawn(TOY), 1, 0)   # a head that is not zero
    model = job["model"]
    tokens = solar_lm.host_batches(TOY, 1, 0, n_batches=1)[0][0]
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
    for i in range(TOY["num_hidden_layers"]):
        seen = left["intermediates"]["layer_%d" % i]["moe"]
        np.testing.assert_allclose(
            seen["router_logits"][0], info["router_logits"][i], rtol=1e-4, atol=1e-5
        )
        np.testing.assert_array_equal(
            np.sort(seen["top_idx"][0], axis=-1), np.sort(info["experts"][i], axis=-1)
        )


def _state(model, params, stats, apply_fn=None):
    return types.SimpleNamespace(
        params=params, batch_stats=stats, apply_fn=apply_fn or model.apply
    )


def test_check_passes_the_program_at_toy_widths(toy_state):
    model, params, stats, _ = toy_state
    result = solar_lm.check(TOY, _state(model, params, stats), 0)
    assert result["ok"], result
    assert result["rows_dropped"] == 0 and result["tokens_misrouted"] == 0
    assert result["router_arithmetic_rel_err"] <= 1e-6
    assert result["router_arithmetic_rel_err_of_a_bfloat16_router"] > (
        10 * solar_lm.ROUTER_ARITHMETIC_REL_TOL
    )
    assert result["rule"]["shape"][0] == [1, 128, 4, 16] and result["rule"]["chunk"] == 32
    assert set(result["rule"]["inputs"]) == set(solar_lm.RULE_ARGS)
    # the drawn inputs reach -30 a step and beta past 1; the trained ones need not
    assert result["rule_drawn"]["log_decay_min"] <= -30.0
    assert result["rule_drawn"]["beta_max"] > 1.5
    assert result["kernel"]["shape"] == [1, 4, 1, 128, 16]   # one whole group of the GQA
    assert result["grouped_matmul"]["groups"] == 4           # the held experts'


def _changed(tree, path, change):
    tree = jax.tree.map(lambda a: a, tree)
    node = tree
    for key in path[:-1]:
        node[key] = dict(node[key])
        node = node[key]
    node[path[-1]] = change(node[path[-1]])
    return tree


@pytest.mark.parametrize("fault", [
    "a_dropped_tap", "a_dropped_attention_gate", "a_dropped_gate_bias",
    "a_dropped_bias", "a_bias_not_moved", "a_bfloat16_router",
])
def test_check_fails_a_program_that_leaves_part_of_the_model_out(toy_state, fault):
    """Each mechanism's absence fails one of the check's limits: the program is
    given other parameters than the reference (a tap of zeros, the softmax
    layer's gate of zeros' sigmoid, the linear layers' gate without its bias),
    a routing bias of zeros or one it does not move, a router rounded to
    bfloat16."""
    model, params, stats, _ = toy_state

    def apply_with(change_params=None, change_stats=None, freeze_bias=False,
                   coarse_router=False):
        def apply_fn(variables, tokens, **kwargs):
            variables = dict(variables)
            if change_params:
                variables["params"] = change_params(variables["params"])
            if change_stats:
                variables["batch_stats"] = change_stats(variables["batch_stats"])
            out = model.apply(variables, tokens, **kwargs)
            if not kwargs.get("mutable"):
                return out
            logits, left = out
            left = jax.tree.map(lambda a: a, dict(left))
            if freeze_bias:
                left["batch_stats"] = variables["batch_stats"]
            if coarse_router:
                for layer in left["intermediates"].values():
                    moe = dict(layer["moe"])
                    moe["router_logits"] = tuple(
                        a.astype(jnp.bfloat16).astype(jnp.float32)
                        for a in moe["router_logits"]
                    )
                    layer["moe"] = moe
            return logits, left
        return apply_fn

    zero = jnp.zeros_like

    def no_gate_bias(p):
        for i in (1, 2, 3):
            p = _changed(p, ("layer_%d" % i, "kda", "g_up", "bias"), lambda b: b + 4.0)
        return p

    apply_fn, failed_by = {
        "a_dropped_tap": (apply_with(lambda p: _changed(
            p, ("layer_1", "kda", "k_conv"), lambda w: w.at[0].set(0.0))), "logits_rel_err"),
        "a_dropped_attention_gate": (apply_with(lambda p: _changed(
            p, ("layer_0", "attn", "g", "kernel"), zero)), "logits_rel_err"),
        "a_dropped_gate_bias": (apply_with(no_gate_bias), "logits_rel_err"),
        "a_dropped_bias": (apply_with(change_stats=lambda s: _changed(
            s, ("layer_1", "moe", "router_bias"), zero)), "tokens_misrouted"),
        "a_bias_not_moved": (apply_with(freeze_bias=True), "bias_abs_err"),
        "a_bfloat16_router": (apply_with(coarse_router=True), "router_arithmetic_rel_err"),
    }[fault]
    result = solar_lm.check(TOY, _state(model, params, stats, apply_fn), 0)
    assert not result["ok"]
    limit = {"logits_rel_err": solar_lm.LOGITS_REL_TOL, "tokens_misrouted": 0,
             "bias_abs_err": solar_lm.BIAS_ABS_TOL,
             "router_arithmetic_rel_err": solar_lm.ROUTER_ARITHMETIC_REL_TOL}[failed_by]
    assert result[failed_by] > limit, (failed_by, result[failed_by])


def kda_of_layer_1(toy_state):
    model, params, _, tokens = toy_state
    x = jnp.asarray(params["embed"]["embedding"])[tokens[:1]].astype(jnp.bfloat16)
    return params["layer_1"]["kda"], x


MIXER_FAULTS = ["no_silu", "no_l2_norm", "the_safe_gate", "beta_to_one", "full_rank_pairs"]


@pytest.mark.parametrize("fault", MIXER_FAULTS)
def test_a_wrong_mixer_fails_the_rules_inputs_check(toy_state, fault, monkeypatch):
    """What the program's first linear layer hands its rule is held to the
    reference's forms: a dropped SiLU or L2 norm and a beta left in (0, 1) read
    far over ``RULE_INPUTS_REL_TOL``; the safe gate in the softplus gate's
    place and full-rank matrices in the pairs' place (Ling's published forms)
    far over ``RULE_DECAY_REL_TOL``; the program as it is reads under both."""
    from edl_tpu.models import KimiDeltaMixer, KimiDeltaSpec
    from edl_tpu.models import gated_delta as mixer_module

    p, x = kda_of_layer_1(toy_state)
    good = solar_lm.rule_vs_reference(TOY, p, x)
    assert good["inputs_rel_err"] <= solar_lm.RULE_INPUTS_REL_TOL
    assert good["decay_rel_err"] <= solar_lm.RULE_DECAY_REL_TOL
    spec = solar_lm.kda_spec(TOY)
    as_dict = dict(num_heads=spec.num_heads, key_dim=spec.key_dim, value_dim=spec.value_dim,
                   d_conv=spec.d_conv, chunk=spec.chunk, lower_bound=None, neg_eigval=True,
                   gate_rank=spec.gate_rank)
    if fault == "no_silu":
        monkeypatch.setattr(
            mixer_module, "causal_conv_silu",
            lambda m, taps, bias: reference.causal_conv(
                m.astype(jnp.float32), taps, 0.0).astype(m.dtype),
        )
    elif fault == "no_l2_norm":
        monkeypatch.setattr(mixer_module, "_unit", lambda m: m)
    elif fault == "the_safe_gate":
        spec = KimiDeltaSpec(**dict(as_dict, lower_bound=-5.0))
    elif fault == "beta_to_one":
        spec = KimiDeltaSpec(**dict(as_dict, neg_eigval=False))
    elif fault == "full_rank_pairs":
        # one full matrix each, with the product of the pair's two as its start
        spec = KimiDeltaSpec(**dict(as_dict, gate_rank=None))
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        p = dict(p, f_proj={"kernel": f32(p["f_up"]["kernel"])[:1].repeat(x.shape[-1], 0)},
                 g_proj={"kernel": f32(p["g_up"]["kernel"])[:1].repeat(x.shape[-1], 0)})
    mixer = KimiDeltaMixer(spec, jnp.bfloat16, TOY["rms_norm_eps"]).apply
    bad = solar_lm.rule_vs_reference(TOY, p, x, mixer=mixer)
    if fault in ("the_safe_gate", "full_rank_pairs"):
        assert bad["decay_rel_err"] > 5 * solar_lm.RULE_DECAY_REL_TOL, bad["inputs"]
    else:
        assert bad["inputs_rel_err"] > 10 * solar_lm.RULE_INPUTS_REL_TOL, bad["inputs"]


def test_the_parents_form_fails_the_rules_own_limits_on_the_drawn_inputs(monkeypatch):
    """The form this PR adds is what the comparison on the chip holds: under
    the parent's pairs (a sub-block of 16 steps under one reference at its
    middle step, written out here) the rule is not finite on the drawn inputs
    (-30 a step for half a chunk), and the rule as it stands passes with room."""
    from edl_tpu.ops import gated_delta as rule_module

    good = solar_lm.rule_vs_reference(TOY, None, None, seed=5)
    assert good["log_decay_min"] <= -30.0 and good["beta_max"] > 1.5
    assert good["rel_err"] <= solar_lm.RULE_REL_TOL / 2
    assert good["state_rms_err"] <= solar_lm.STATE_RMS_TOL / 2
    assert good["exact_rel_err"] <= solar_lm.EXACT_REL_TOL / 10
    assert good["exact_state_rms_err"] <= solar_lm.EXACT_STATE_RMS_TOL / 10

    def parents_pairs(q32, k32, gamma, dtype, sub=16):
        b, n, c, h, d = gamma.shape
        in_blocks = lambda a: a.reshape(b, n, c // sub, sub, h, d)  # noqa: E731
        ref = in_blocks(gamma)[:, :, :, (sub - 1) // 2]
        rows = jnp.exp(in_blocks(gamma) - ref[:, :, :, None])
        reach = jnp.arange(c)[None, :] // sub <= jnp.arange(c // sub)[:, None]
        cols = jnp.exp(jnp.where(
            reach[:, :, None, None], ref[:, :, :, None] - gamma[:, :, None], -jnp.inf
        ))
        k_cols = (k32[:, :, None] * cols).astype(dtype)
        against = lambda a: jnp.einsum(  # noqa: E731
            "bnichk,bnishk->bnhics", (in_blocks(a) * rows).astype(dtype), k_cols,
            preferred_element_type=jnp.float32,
        ).reshape(b, n, h, c, c)
        return against(k32), against(q32)

    monkeypatch.setattr(rule_module, "_plain_pairs", parents_pairs)
    wide = dict(TOY, train=dict(TOY["train"], rule_chunk=64, seq_len=256))
    bad = solar_lm.rule_vs_reference(wide, None, None, seed=5)
    assert not (bad["rel_err"] <= solar_lm.RULE_REL_TOL)      # nan or far off


def test_a_bfloat16_carried_state_fails_the_float32_runs_check(monkeypatch):
    from edl_tpu.ops import gated_delta as rule_module

    carry = rule_module._carry

    def coarse(state, inputs):
        after, new = carry(state, inputs)
        return after.astype(jnp.bfloat16).astype(jnp.float32), new

    monkeypatch.setattr(rule_module, "_carry", coarse)
    bad = solar_lm.rule_vs_reference(TOY, None, None, seed=5)
    assert bad["exact_rel_err"] > 3 * solar_lm.EXACT_REL_TOL
    assert bad["exact_state_rms_err"] > 3 * solar_lm.EXACT_STATE_RMS_TOL


def test_an_8_bit_mixer_fails_the_rules_inputs_limit(toy_state):
    """What an 8-bit mixer hands its rule (projections, convolutions and norms
    in ``float8_e4m3fn``) is over the limit for q, k and v by three times and
    more; the decay's own, wider limit is for the faults of form."""
    from edl_tpu.models import KimiDeltaMixer

    p, x = kda_of_layer_1(toy_state)
    coarse = KimiDeltaMixer(
        solar_lm.kda_spec(TOY), jnp.float8_e4m3fn, TOY["rms_norm_eps"]
    ).apply

    def mixer(variables, x, mutable):
        out, sown = coarse(variables, x.astype(jnp.float8_e4m3fn), mutable=mutable)
        q, k, v, g, beta = sown["intermediates"]["rule_inputs"][0]
        q, k, v = (m.astype(jnp.bfloat16) for m in (q, k, v))   # what the rule can hold
        return out, dict(sown, intermediates={"rule_inputs": ((q, k, v, g, beta),)})

    bad = solar_lm.rule_vs_reference(TOY, p, x, mixer=mixer)
    assert min(bad["inputs"][name] for name in "qkv") > 3 * solar_lm.RULE_INPUTS_REL_TOL


# the cell's four layers and its routing (8 of 40 experts held, top-8) at a
# width where bfloat16 reads what it reads at the published widths
WIDE = dict(
    TOY, hidden_size=256, intermediate_size=512, moe_intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=1, head_dim=32, vocab_size=512,
    linear_attn_config=dict(TOY["linear_attn_config"], head_dim=32, num_heads=4),
    n_routed_experts=8, num_experts_per_tok=8,
    share=dict(TOY["share"], router_experts=40, experts_first=0),
    train=dict(TOY["train"], seq_len=512, rule_chunk=64),
)


@pytest.mark.parametrize("dtype, passes", [("bfloat16", True), ("float8_e4m3fn", False)])
def test_the_precision_below_fails_the_streams_limits(monkeypatch, dtype, passes):
    """The stated precision (bfloat16 compute) passes ``check``; an 8-bit float
    under the same program, the nearest precision below, fails at least one of
    the stream's limits with room (the 8-bit program hands its rule bfloat16
    operands, as ``test_kda_lm.py``'s does: the kernels and the carry take no
    8-bit float)."""
    from edl_tpu.models import gated_delta as mixer_module

    rule = mixer_module.kda_rule

    def rule_in_bfloat16(q, k, v, g, beta, **kwargs):
        if q.dtype.itemsize > 1:
            return rule(q, k, v, g, beta, **kwargs)
        o, state = rule(*(m.astype(jnp.bfloat16) for m in (q, k, v)), g, beta, **kwargs)
        return o.astype(q.dtype), state

    monkeypatch.setattr(mixer_module, "kda_rule", rule_in_bfloat16)
    model = solar_lm.build(solar_lm.as_drawn(WIDE), 1, 0)["model"]
    tokens = solar_lm.host_batches(WIDE, 1, 0, n_batches=1)[0][0]
    variables = model.init(jax.random.PRNGKey(0), tokens)
    coarse = model.clone(dtype=getattr(jnp, dtype), remat=False)
    result = solar_lm.check(
        WIDE, _state(coarse, variables["params"], variables["batch_stats"]), 0
    )
    readings = {
        name: result[name] / limit for name, limit in (
            ("logits_rel_err", solar_lm.LOGITS_REL_TOL),
            ("router_logits_rel_err", solar_lm.ROUTER_LOGITS_REL_TOL),
            ("flipped_share", solar_lm.ROUTE_FLIP_LIMIT),
        )
    }
    if passes:
        assert result["ok"], result
        assert all(r < 0.7 for r in readings.values()), readings
    else:
        assert not result["ok"]
        assert max(readings.values()) > 1.5, readings


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
                   "attn.1": 0.03, "attn.2": 0.05, "fusion.6": 0.5},
    "op_text": {
        "fusion.1": "%fusion.1 = fusion(...)", "fusion.2": "%fusion.2 = fusion(...)",
        "fusion.3": "%fusion.3 = fusion(...)", "fusion.4": "%fusion.4 = fusion(...)",
        "attn.1": "%attn.1 = custom-call(...) tpu_custom_call",
        "attn.2": "%attn.2 = custom-call(...) tpu_custom_call",
        "fusion.6": "%fusion.6 = fusion(...)",
    },
}
TABLE = {"fusion.1": "kda_proj", "fusion.2": "kda_scan", "fusion.3": "kda_conv",
         "fusion.4": "kda_gate"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def make_run(trace=TRACE, family=solar_lm, config=CONFIG):
    return types.SimpleNamespace(
        trace=trace, family=family, config=config, peaks=PEAKS, chips=1,
        items_per_step=8192, at_close={"registry": {}},
    )


def test_the_rooflines_divide_the_familys_work_by_the_time(monkeypatch):
    from edl_tpu.obs import profile

    monkeypatch.setattr(profile, "step_scopes", lambda scopes: dict(TABLE))
    tokens = 8192 * 2
    least = max(solar_lm.kda_scan_flops(CONFIG, tokens) / 197e12,
                solar_lm.kda_scan_bytes(CONFIG, tokens) / 819e9)
    assert reader("kda_scan_roofline").read(make_run()) == pytest.approx(100 * least / 0.20)
    assert reader("kda_scan_ms").read(make_run()) == pytest.approx(100.0)
    assert reader("kda_share").read(make_run()) == pytest.approx(40.0)
    # the softmax layer's kernels: the accepted readers find the custom calls
    # by the family's TRACE_KERNELS and count kernel_flops
    least = solar_lm.kernel_flops(CONFIG, 2) / 197e12
    assert reader("attn_kernel_roofline").read(make_run()) == pytest.approx(100 * least / 0.08)
    assert reader("attn_kernel_share").read(make_run()) == pytest.approx(100 * 0.08 / 1.0)


def test_the_new_gauge_reader_reads_the_registry():
    module = reader("kda_log_decay_min")
    run = make_run()
    assert module.read(run) is None                         # a program without the gauge
    run.at_close = {"registry": {"edl_train_kda_log_decay_min": {"": -7.25}}}
    assert module.read(run) == -7.25
    entry = next(m for m in BENCH["per_layer"] if m["name"] == "kda_log_decay_min")
    assert entry["workloads"] == [CELL, "ling_3_0_flash_vl.steady"]
    assert (module.NAME, module.UNIT, module.BETTER, module.SOURCE, module.LAYER, module.MOVES) == (
        entry["name"], entry["unit"], entry["better"], entry["source"], entry["layer"],
        entry["moves"])


def test_the_benchmark_gained_one_configuration_one_cell_and_the_cells_name_on_twelve_lists():
    """By name and not by place: a later PR appends its own after these."""
    assert [c["file"] for c in BENCH["configs"] if c["name"] == "solar_open2_250b"] == [
        "benchmark/configs/solar_open2_250b.json"
    ]
    cells = [w for w in BENCH["workloads"] if w["config"] == "solar_open2_250b"]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [(CELL, "steady", 1)]
    lists = [m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])]
    assert lists == [
        "attn_kernel_share", "attn_kernel_roofline", "expert_load_max", "expert_held_load_max",
        "expert_bias_absmax", "kda_decay_mean", "step_plain_fallbacks", "step_kernel_calls",
        "step_loops", "step_unplaced_share", "step_time_drift", "expert_rows_held",
        "kda_log_decay_min",
    ]
    gauge = next(m for m in BENCH["per_layer"] if m["name"] == "kda_log_decay_min")
    assert set(gauge["workloads"]) >= {CELL, "ling_3_0_flash_vl.steady"}


def test_the_timeline_file_lists_the_readers_for_the_familys_cell():
    extended = solar_timeline.with_solar(BENCH)
    by_name = {m["name"]: m for m in extended["per_layer"]}
    for name in solar_timeline.SHARED_READERS:
        assert CELL in by_name[name]["workloads"], name
        module = reader(name)
        assert by_name[name]["source"] == module.SOURCE == "device_trace"
    listed = {m["name"] for m in BENCH["per_layer"]}
    assert not listed & set(solar_timeline.SHARED_READERS)
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
        assert {"kda_log_decay_min", "kda_decay_mean", "expert_bias_absmax", "expert_rows_held",
                "expert_load_max", "expert_held_load_max"} <= set(detail["judged"])
        from benchmark import run as bench_run
        cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
        listed = {m["name"] for m in BENCH["per_layer"] if bench_run.applies(m, cell["name"])}
        assert listed - set(detail["judged"]) <= NEEDS_A_DEVICE
