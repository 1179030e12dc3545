"""The ``nemotron_h_lm`` family: its operation and byte counts against arithmetic
done by hand, its reference against the program at toy widths (and what each
limit of ``check`` is for), the configuration file against the published one, its
readers on a hand-made trace and a hand-made registry, and the rehearsal of its
cell."""

import dataclasses
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import latent_moe_timeline
from benchmark.families import nemotron_h_lm as family
from benchmark.reference import nemotron_h_lm as reference
from benchmark.reference import ssm_lm as ssm_reference
from benchmark.tests.test_rehearse import NEEDS_A_DEVICE, run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "nemotron_3_super_120b_a12b.json")
TOY = load("rehearsal", "configs", "nemotron_3_super_120b_a12b.json")
BENCH = load("..", "BENCHMARK.json")
CELL = "nemotron_3_super_120b_a12b.steady"


def test_nemotron_3_super_by_hand():
    d, t = 4096, 8192
    d_inner = 64 * 64                               # the 64 heads held, of 64
    mamba = d * (2 * d_inner + 2 * 4 * 128 + 64) + d_inner * d     # in (z, x, B, C, dt), out
    assert family.mamba_params(CONFIG) == mamba == 54_788_096
    attention = 2 * d * 16 * 128 + 2 * d * 1 * 128  # q and o at 16 heads, k and v at 1
    assert family.attention_params(CONFIG) == attention == 17_825_792
    # 22 choices fall on the 8 held of 512 with 22 x 8 / 512 = 0.34 a token
    assert family.routed_experts_a_token(CONFIG) == 0.34375
    expert_block = (
        d * 512 + 2 * d * 1024 + 2 * d * 5376 + 0.34375 * 2 * 1024 * 2688
    )                                               # router, latent pair, shared, held
    assert family.expert_block_params(CONFIG) == expert_block == 56_418_304
    head = d * 16_384
    params = 4 * mamba + attention + 4 * expert_block + head
    assert [family.letters(CONFIG, c) for c in "M*E"] == [4, 1, 4]
    assert family.matmul_params(CONFIG) == params == 529_760_256
    attention_forward = 2 * 16 * t * t * 128        # two products over T^2 / 2 pairs
    assert family.attention_forward_flops(CONFIG, 3) == 3 * attention_forward
    # C B^T a group, the mixing, the chunk's state, what it inherits
    scan = 128 * 128 * 4 + 128 * d_inner + 2 * 128 * d_inner + 2 * 128 * d_inner
    assert family.scan_forward_flops_per_token(CONFIG) == scan == 2_686_976
    want = 6 * params + 3 * attention_forward / t + 3 * 4 * scan
    assert family.flops_per_item(CONFIG) == pytest.approx(want)
    assert family.flops_per_item(CONFIG) == pytest.approx(3.3115e9, rel=0.001)
    # the Mamba-2 projections and the shared experts are most of the counted work
    assert 6 * 4 * mamba / want == pytest.approx(0.397, abs=0.002)
    assert 6 * 4 * 2 * d * 5376 / want == pytest.approx(0.319, abs=0.002)
    assert 6 * 4 * 0.34375 * 2 * 1024 * 2688 / want == pytest.approx(0.0137, abs=0.0005)


def test_the_kernels_work_by_hand():
    t = 8192
    assert family.kernel_flops(CONFIG, 2) == 3.5 * 2 * (2 * 16 * t * t * 128)
    tokens = 3 * t
    assert family.ssm_scan_flops(CONFIG, tokens) == 3 * 2_686_976 * tokens * 4
    d_inner, heads, bc = 4096, 64, 2 * 4 * 128
    a_token = 2 * d_inner + 4 * heads + 2 * bc      # x; dt in float32; B and C
    assert family.ssm_scan_bytes(CONFIG, tokens) == (
        (a_token + 2 * d_inner) + (a_token + 2 * d_inner + a_token)
    ) * tokens * 4
    rows = tokens * 0.34375
    # TWO matrices an expert, at the latent's width: forward and both gradients
    assert family.moe_kernel_flops(CONFIG, tokens) == 6 * 2 * rows * 1024 * 2688 * 4
    assert family.moe_kernel_bytes(CONFIG, tokens) == 6 * (
        rows * 1024 * 2 + rows * 2688 * 2 + 8 * 1024 * 2688 * 2
    ) * 4
    assert family.TRACE_KERNELS == ("%attn.", " custom-call(")     # unscoped, as Granite's
    assert family.MOE_TRACE_KERNELS == ("gmm", " custom-call(")


def test_the_configuration_keeps_every_published_width():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    entry = next(c for c in BENCH["configs"] if c["name"] == "nemotron_3_super_120b_a12b")
    assert entry["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k, "absent") != v}
    assert differs == set(entry["reduced"])
    for key in entry["reduced"]:
        assert CONFIG["published"][key] == row["config"][key] != CONFIG[key]
    for key, width in (
        ("hidden_size", 4096), ("mamba_head_dim", 64), ("ssm_state_size", 128),
        ("conv_kernel", 4), ("chunk_size", 128), ("expand", 2), ("head_dim", 128),
        ("num_experts_per_tok", 22), ("routed_scaling_factor", 5),
        ("moe_latent_size", 1024), ("moe_intermediate_size", 2688),
        ("moe_shared_expert_intermediate_size", 5376), ("n_group", 1), ("topk_group", 1),
        ("layer_norm_epsilon", 1e-5),
    ):
        assert CONFIG[key] == width
    share = CONFIG["share"]
    assert share["router_experts"] == 512 == CONFIG["published"]["n_routed_experts"]
    assert share["chips_a_layer"] == 64 and share["chips_a_heads"] == 2
    assert CONFIG["n_routed_experts"] * share["chips_a_layer"] == 512
    assert CONFIG["vocab_size"] * share["chips_a_vocabulary"] == 131_072
    assert CONFIG["mamba_num_heads"] * share["chips_a_heads"] == 128
    assert CONFIG["n_groups"] * share["chips_a_heads"] == 8
    # sixteen heads a group and sixteen queries a KV head, as published
    assert CONFIG["mamba_num_heads"] // CONFIG["n_groups"] == 128 // 8
    assert CONFIG["num_attention_heads"] // CONFIG["num_key_value_heads"] == 32 // 2
    # the published pattern written out, and the blocks run its first period
    pattern = "MEMEMEM*E" * 3 + "MEMEMEMEM*E" * 4 + "MEMEMEM*E" + "MEMEMEME"
    assert CONFIG["published"]["hybrid_override_pattern"] == pattern and len(pattern) == 88
    assert [pattern.count(c) for c in "M*E-"] == [40, 8, 40, 0]
    assert CONFIG["hybrid_override_pattern"] == pattern[:9] == "MEMEMEM*E"
    spec = family.moe_spec(CONFIG)
    assert (spec.num_experts, spec.held, spec.top_k, spec.route_scale) == (512, (0, 8), 22, 5)
    assert (spec.gated, spec.activation, spec.latent, spec.d_ff) == (False, "relu2", 1024, 2688)
    assert spec.shared_d_ff == 5376 and spec.n_group == 1
    arch = family.arch_spec(CONFIG)
    assert arch.one_branch and arch.rope is False and not arch.tie_embeddings
    assert arch.layer_types.count("mamba") == 4 and arch.layer_types[7] == "attention"
    assert (arch.mamba.num_heads, arch.mamba.n_groups, arch.mamba.chunk) == (64, 4, 128)
    for said in ("assumed", "not_run", "departures", "deployment", "plan"):
        assert CONFIG[said]
    said = " ".join(CONFIG["assumed"] + CONFIG["not_run"])
    for form in ("no position term", "each group", "before the dispatch", "full width",
                 "expert_bias_rate", "multi-token", "decode"):
        assert form in said, form


def test_the_toy_twin_has_every_mechanism():
    assert set(TOY["hybrid_override_pattern"]) == {"M", "E", "*"}
    assert TOY["n_groups"] > 1 and TOY["mamba_num_heads"] % TOY["n_groups"] == 0
    assert TOY["n_routed_experts"] < TOY["share"]["router_experts"]
    assert 0 < TOY["moe_latent_size"] < TOY["hidden_size"]
    assert TOY["mlp_hidden_act"] == "relu2"
    assert TOY["train"]["seq_len"] > TOY["chunk_size"]      # a carried state


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        source = f.read().split('"""', 2)[2]                # after the docstring
    assert "edl_tpu" not in source
    assert "from benchmark.reference.ssm_lm import causal_conv, recurrence" in source
    with open(ssm_reference.__file__) as f:                 # the scan a step at a time
        assert "jax.lax.scan" in f.read().split('"""', 2)[2]


@pytest.fixture(scope="module")
def toy_state():
    job = family.build(TOY, 1, 0)
    model = job["model"]
    tokens = family.host_batches(TOY, 1, 0, n_batches=1)[0][0]
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
    for j, i in enumerate(family.expert_blocks(TOY)):
        seen = left["intermediates"]["layer_%d" % i]["moe"]
        np.testing.assert_allclose(
            seen["router_logits"][0], info["router_logits"][j], rtol=1e-4, atol=1e-5
        )
        np.testing.assert_array_equal(
            np.sort(seen["top_idx"][0], axis=-1), np.sort(info["experts"][j], axis=-1)
        )
        sown = left["metrics"]["layer_%d" % i]["moe"]
        assert float(sown["moe_rows_held"][0]) == pytest.approx(
            float(info["rows_held"][j]), abs=1e-6
        )


def _state(model, params, stats, apply_fn=None):
    return types.SimpleNamespace(
        params=params, batch_stats=stats, apply_fn=apply_fn or model.apply
    )


def test_check_passes_the_program_at_toy_widths(toy_state):
    model, params, stats, _ = toy_state
    result = family.check(TOY, _state(model, params, stats), 0)
    assert result["ok"], result
    assert result["rows_dropped"] == 0 and result["tokens_misrouted"] == 0
    assert result["router_arithmetic_rel_err"] <= 1e-6
    assert result["router_arithmetic_rel_err_of_a_bfloat16_router"] > (
        10 * family.ROUTER_ARITHMETIC_REL_TOL
    )
    assert result["mixer"]["groups"] == 2 and result["scan"]["shape"] == [1, 128, 8, 16]
    assert result["kernel"]["shape"] == [1, 4, 1, 128, 32]
    assert result["grouped_matmul"]["groups"] == 4           # the held experts'
    assert len(result["flipped_share_by_layer"]) == 2


def _changed(tree, path, change):
    tree = jax.tree.map(lambda a: a, tree)
    node = tree
    for key in path[:-1]:
        node[key] = dict(node[key])
        node = node[key]
    node[path[-1]] = change(node[path[-1]])
    return tree


@pytest.mark.parametrize("fault", [
    "silu_for_relu2", "no_route_scale", "a_dropped_shared_expert", "a_position_term_too_many",
    "a_dropped_tap", "a_dropped_bias", "a_bias_not_moved", "a_bfloat16_router",
])
def test_check_fails_a_program_that_leaves_part_of_the_model_out(toy_state, fault):
    """Each mechanism's absence fails one of the check's limits: the program is
    another model on the same parameters (SiLU where the experts square a ReLU,
    weights not times ``routed_scaling_factor``, rotated queries and keys) or is
    given other parameters than the reference (a shared expert of zeros, a tap
    of zeros), a bias of zeros or one it does not move, or a router rounded to
    bfloat16."""
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
    moe, arch = family.moe_spec(TOY), family.arch_spec(TOY)
    apply_fn, failed_by = {
        "silu_for_relu2": (apply_with(other=model.clone(
            moe=dataclasses.replace(moe, activation="silu"))), "logits_rel_err"),
        "no_route_scale": (apply_with(other=model.clone(
            moe=dataclasses.replace(moe, route_scale=1.0))), "logits_rel_err"),
        "a_dropped_shared_expert": (apply_with(lambda p: _changed(
            p, ("layer_1", "moe", "shared", "down", "kernel"), zero)), "logits_rel_err"),
        "a_position_term_too_many": (apply_with(other=model.clone(
            arch=dataclasses.replace(arch, rope=True))), "logits_rel_err"),
        "a_dropped_tap": (apply_with(lambda p: _changed(
            p, ("layer_0", "mamba", "conv_kernel"), lambda w: w.at[0].set(0.0))),
            "logits_rel_err"),
        "a_dropped_bias": (apply_with(change_stats=lambda s: _changed(
            s, ("layer_1", "moe", "router_bias"), zero)), "tokens_misrouted"),
        "a_bias_not_moved": (apply_with(freeze_bias=True), "bias_abs_err"),
        "a_bfloat16_router": (apply_with(coarse_router=True), "router_arithmetic_rel_err"),
    }[fault]
    result = family.check(TOY, _state(model, params, stats, apply_fn), 0)
    assert not result["ok"]
    limit = {"logits_rel_err": family.LOGITS_REL_TOL, "tokens_misrouted": 0,
             "bias_abs_err": family.BIAS_ABS_TOL,
             "router_arithmetic_rel_err": family.ROUTER_ARITHMETIC_REL_TOL}[failed_by]
    assert result[failed_by] > limit, (failed_by, result[failed_by])


def test_a_norm_over_all_the_channels_fails_the_mixers_limit(toy_state):
    """The gated norm over all ``d_inner`` where the layer normalises each
    group's channels among themselves (``reference/ssm_lm.py``'s mixer standing
    in for the program's) is over ``MIXER_REL_TOL`` by ten times; the program's
    own mixer is under it by three."""
    _, params, _, _ = toy_state
    as_granite = {
        "mamba_n_heads": TOY["mamba_num_heads"], "mamba_d_head": TOY["mamba_head_dim"],
        "mamba_n_groups": TOY["n_groups"], "mamba_d_state": TOY["ssm_state_size"],
        "mamba_conv_bias": True, "rms_norm_eps": TOY["layer_norm_epsilon"],
    }
    # scales that tell the groups apart, as a trained layer's do
    mamba = dict(params["layer_0"]["mamba"])
    width = mamba["norm"].shape[0] // TOY["n_groups"]
    mamba["norm"] = mamba["norm"] * jnp.repeat(jnp.asarray([1.0, 3.0]), width)
    x_scale = jnp.repeat(jnp.asarray([1.0, 4.0]), width)   # the groups' sizes differ too
    d_inner = x_scale.shape[0]
    kernel = mamba["in_proj"]["kernel"]
    mamba["in_proj"] = {"kernel": kernel.at[:, :d_inner].multiply(x_scale)}
    over_all = lambda v, x: ssm_reference.mamba_mixer(as_granite, v["params"], x)  # noqa: E731
    steps = TOY["train"]["seq_len"]
    wrong = family.mixer_vs_reference(TOY, mamba, 0, steps, mixer=over_all)
    right = family.mixer_vs_reference(TOY, mamba, 0, steps)
    assert wrong["rel_err"] > 10 * family.MIXER_REL_TOL
    assert right["rel_err"] < family.MIXER_REL_TOL / 3


WIDE = dict(
    TOY, hidden_size=256, vocab_size=512, num_hidden_layers=9,
    hybrid_override_pattern="MEMEMEM*E", mamba_num_heads=16, n_groups=4,
    num_attention_heads=8, num_key_value_heads=1, moe_latent_size=64,
    moe_intermediate_size=96, moe_shared_expert_intermediate_size=192,
    n_routed_experts=8, num_experts_per_tok=6,
    share={"router_experts": 64, "experts_first": 0}, chunk_size=64,
    train=dict(TOY["train"], seq_len=512),
)


@pytest.mark.parametrize("dtype, passes", [("bfloat16", True), ("float8_e4m3fn", False)])
def test_the_precision_below_fails_the_streams_limits(monkeypatch, dtype, passes):
    """The stated precision (bfloat16 compute) passes ``check`` on the cell's
    nine blocks at a width of 256; an 8-bit float under the same program, the
    nearest precision below, fails the logits', the routers' and the flips'
    limits, each with room. jax promotes no 8-bit float and XLA's CPU backend
    multiplies none, so the 8-bit program rounds every matmul operand and
    result to ``float8_e4m3fn`` and computes between in bfloat16."""
    from edl_tpu.models import mamba as mamba_module

    scan, conv = mamba_module.ssd_scan, mamba_module.causal_conv_silu
    wide = lambda a: a.astype(jnp.bfloat16) if a.dtype.itemsize == 1 else a  # noqa: E731
    monkeypatch.setattr(
        mamba_module, "ssd_scan",
        lambda x, dt, a, b, c, d, **kw: scan(wide(x), dt, a, wide(b), wide(c), d, **kw).astype(x.dtype),
    )
    monkeypatch.setattr(
        mamba_module, "causal_conv_silu",
        lambda z, *a, **kw: conv(wide(z), *a, **kw).astype(z.dtype),
    )
    model = family.build(WIDE, 1, 0)["model"]
    tokens = family.host_batches(WIDE, 1, 0, n_batches=1)[0][0]
    variables = model.init(jax.random.PRNGKey(0), tokens)
    coarse = model.clone(dtype=getattr(jnp, dtype), remat=False)
    result = family.check(
        WIDE, _state(coarse, variables["params"], variables["batch_stats"]), 0
    )
    assert result["tokens_misrouted"] == 0                  # a flip is still the rounding's
    readings = {
        name: result[name] / limit for name, limit in (
            ("logits_rel_err", family.LOGITS_REL_TOL),
            ("router_logits_rel_err", family.ROUTER_LOGITS_REL_TOL),
            ("flipped_share", family.ROUTE_FLIP_LIMIT),
        )
    }
    print(dtype, {name: result[name] for name in readings})
    if passes:
        assert result["ok"], result
        assert all(r < 0.7 for r in readings.values()), readings
    else:
        assert not result["ok"]
        assert all(r > 1.2 for r in readings.values()), readings


def test_the_references_route_weighs_by_the_scores_and_chooses_by_the_bias():
    config = dict(TOY, num_experts_per_tok=2)
    logits = jnp.log(jnp.asarray([[0.6, 0.5, 0.4, 0.3]]) / (1 - jnp.asarray([[0.6, 0.5, 0.4, 0.3]])))
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.25])              # lifts the last over the second
    weights, experts, margin, scores = reference.route(config, logits, bias)
    assert sorted(np.asarray(experts[0])) == [0, 3]
    np.testing.assert_allclose(scores[0], [0.6, 0.5, 0.4, 0.3], atol=1e-6)
    np.testing.assert_allclose(                            # s / sum(s) * 5, the bias nowhere
        sorted(np.asarray(weights[0])), [5 * 0.3 / 0.9, 5 * 0.6 / 0.9], atol=1e-5
    )
    assert float(margin[0]) == pytest.approx(0.55 - 0.5, abs=1e-6)


def test_the_references_rule_moves_the_bias_against_the_load():
    after = reference.bias_update(TOY, jnp.zeros((4,)), jnp.asarray([9, 1, 5, 5]))
    np.testing.assert_allclose(after, [-0.001, 0.001, 0.0, 0.0], atol=1e-9)


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
    "op_seconds": {"fusion.1": 0.10, "fusion.2": 0.20, "fusion.3": 0.04, "gmm.1": 0.06,
                   "attn.1": 0.03, "attn.2": 0.05, "fusion.5": 0.02, "fusion.6": 0.5},
    "op_text": {
        "fusion.1": "%fusion.1 = fusion(...)", "fusion.2": "%fusion.2 = fusion(...)",
        "fusion.3": "%fusion.3 = fusion(...)",
        "gmm.1": "%gmm.1 = custom-call(...) tpu_custom_call",
        "attn.1": "%attn.1 = custom-call(...) tpu_custom_call",
        "attn.2": "%attn.2 = custom-call(...) tpu_custom_call",
        "fusion.5": "%fusion.5 = fusion(...)", "fusion.6": "%fusion.6 = fusion(...)",
    },
}
TABLE = {"fusion.1": "moe_latent", "fusion.2": "ssm_scan", "fusion.3": "moe_shared",
         "gmm.1": "moe_experts", "fusion.5": "moe_latent"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def make_run(trace=TRACE, config=CONFIG):
    return types.SimpleNamespace(
        trace=trace, family=family, config=config, peaks=PEAKS, chips=1,
        items_per_step=8192, at_close={"registry": {}},
    )


def test_moe_latent_ms_joins_the_trace_to_the_programs_table(monkeypatch):
    from edl_tpu.obs import profile

    monkeypatch.setattr(profile, "step_scopes", lambda scopes: dict(TABLE))
    assert reader("moe_latent_ms").read(make_run()) == pytest.approx(1e3 * 0.12 / 2)
    assert reader("moe_latent_ms").read(make_run(trace=None)) is None
    # a program without the scope (the parent, every other family): nothing to read
    monkeypatch.setattr(profile, "step_scopes", lambda scopes: {})
    assert reader("moe_latent_ms").read(make_run()) is None
    monkeypatch.setattr(profile, "step_scopes", lambda scopes: {"fusion.6": "moe_route"})
    assert reader("moe_latent_ms").read(make_run()) is None


def test_the_rooflines_divide_the_familys_work_by_the_trace(monkeypatch):
    from edl_tpu.obs import profile

    monkeypatch.setattr(profile, "step_scopes", lambda scopes: dict(TABLE))
    tokens = 8192 * 2
    least = max(family.ssm_scan_flops(CONFIG, tokens) / 197e12,
                family.ssm_scan_bytes(CONFIG, tokens) / 819e9)
    assert reader("ssm_scan_roofline").read(make_run()) == pytest.approx(100 * least / 0.20)
    least = max(family.moe_kernel_flops(CONFIG, tokens) / 197e12,
                family.moe_kernel_bytes(CONFIG, tokens) / 819e9)
    assert reader("moe_kernel_roofline").read(make_run()) == pytest.approx(100 * least / 0.06)
    # the attention block's kernels, unscoped: the accepted readers find them
    least = family.kernel_flops(CONFIG, 2) / 197e12
    assert reader("attn_kernel_roofline").read(make_run()) == pytest.approx(100 * least / 0.08)
    assert reader("attn_kernel_share").read(make_run()) == pytest.approx(100 * 0.08 / 1.0)


@pytest.mark.parametrize("name,gauge", [
    ("ssm_decay_mean", "edl_train_ssm_decay_mean"),
    ("expert_rows_held", "edl_train_moe_rows_held"),
])
def test_the_gauge_readers_read_the_registry(name, gauge):
    run = make_run()
    assert reader(name).read(run) is None                   # a program without the gauge
    run.at_close = {"registry": {gauge: {"": 0.0156}}}
    assert reader(name).read(run) == 0.0156
    module = reader(name)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert (module.NAME, module.UNIT, module.SOURCE, module.LAYER, module.MOVES) == (
        name, entry["unit"], entry["source"], entry["layer"], entry["moves"])
    assert entry["source"] == "program_counter" and CELL in entry["workloads"]


def test_the_benchmark_lists_the_cell_where_the_issue_says():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert by_name["ssm_decay_mean"]["workloads"] == ["granite_4_0_h_micro.steady", CELL]
    assert by_name["expert_rows_held"]["workloads"] == [CELL]
    for name in ("attn_kernel_share", "attn_kernel_roofline", "expert_load_max",
                 "expert_held_load_max", "expert_bias_absmax", "step_plain_fallbacks",
                 "step_kernel_calls", "step_loops", "step_unplaced_share", "step_time_drift"):
        assert by_name[name]["workloads"][-1] == CELL
    cell = BENCH["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "nemotron_3_super_120b_a12b", "steady", 1)
    assert len(BENCH["workloads"]) == 11 and len(BENCH["configs"]) == 10


def test_the_timeline_file_lists_the_readers_for_the_familys_cell():
    extended = latent_moe_timeline.with_latent_moe(BENCH)
    by_name = {m["name"]: m for m in extended["per_layer"]}
    assert by_name["moe_latent_ms"]["workloads"] == [CELL]
    module = reader("moe_latent_ms")
    assert (module.NAME, module.UNIT) == ("moe_latent_ms", by_name["moe_latent_ms"]["unit"])
    assert by_name["moe_latent_ms"]["source"] == module.SOURCE == "device_trace"
    for name in latent_moe_timeline.SHARED_READERS:
        assert CELL in by_name[name]["workloads"], name
    assert {"moe_kernel_roofline", "ssm_scan_roofline", "moe_shared_ms", "moe_share",
            "ssm_share"} <= set(latent_moe_timeline.SHARED_READERS)
    listed = {m["name"] for m in BENCH["per_layer"]}
    assert not listed & {"moe_latent_ms", *latent_moe_timeline.SHARED_READERS}
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
        assert {"ssm_decay_mean", "expert_rows_held", "expert_bias_absmax", "expert_load_max",
                "expert_held_load_max", "step_unplaced_share"} <= set(detail["judged"])
        from benchmark import run as bench_run
        cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
        listed = {m["name"] for m in BENCH["per_layer"] if bench_run.applies(m, cell["name"])}
        assert listed - set(detail["judged"]) <= NEEDS_A_DEVICE
