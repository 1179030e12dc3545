"""The ``lfm2_lm`` family: its operation and byte counts against arithmetic done
by hand, its reference against the program at toy widths (and what each limit
of ``check`` is for), the configuration file against the published one, its
readers on a hand-made trace, and the rehearsal of its cell."""

import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import lfm2_timeline
from benchmark.families import lfm2_lm
from benchmark.reference import lfm2_lm as reference
from benchmark.tests.test_rehearse import NEEDS_A_DEVICE, run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "lfm2_24b_a2b.json")
TOY = load("rehearsal", "configs", "lfm2_24b_a2b.json")
BENCH = load("..", "BENCHMARK.json")
CELL = "lfm2_24b_a2b.steady"


def test_lfm2_24b_a2b_by_hand():
    d, t, h, hd = 2048, 8192, 32, 64
    conv_mixer = d * 3 * d + d * d                          # in and out projections
    assert conv_mixer == 16_777_216
    attention = 2 * d * h * hd + 2 * d * 8 * hd             # q, o; k, v
    assert attention == 10_485_760
    dense = 3 * d * 11_776
    assert dense == 72_351_744
    # the router at its published width and 4 x 8 / 64 = 0.5 routed experts a
    # token, expected; no shared expert
    assert lfm2_lm.routed_experts_a_token(CONFIG) == 0.5
    expert_layer = d * 64 + 0.5 * 3 * d * 1536
    assert expert_layer == 4_849_664
    head = d * 8192
    params = 7 * conv_mixer + 2 * attention + dense + 8 * expert_layer + head
    assert lfm2_lm.layers(CONFIG, "conv") == 7 and lfm2_lm.layers(CONFIG, "full_attention") == 2
    assert lfm2_lm.matmul_params(CONFIG) == params == 266_338_304
    full = 2 * h * t * t * hd                               # 4 * H * (T^2 / 2) * D a layer
    assert lfm2_lm.attention_forward_flops(CONFIG, 3) == 3 * 2 * full
    want = 6 * params + 3 * 2 * full / t
    assert lfm2_lm.flops_per_item(CONFIG) == pytest.approx(want)
    assert lfm2_lm.flops_per_item(CONFIG) == pytest.approx(1.7994e9, rel=0.001)
    # the issue's shares of the counted work
    assert 6 * 7 * conv_mixer / want == pytest.approx(0.392, abs=0.001)
    assert 6 * dense / want == pytest.approx(0.241, abs=0.001)
    assert 6 * 8 * 0.5 * 3 * d * 1536 / want == pytest.approx(0.126, abs=0.001)
    assert 3 * 2 * full / t / want == pytest.approx(0.112, abs=0.001)
    assert 6 * head / want == pytest.approx(0.056, abs=0.001)
    assert lfm2_lm.kernel_flops(CONFIG, 2) == 3.5 * 2 * 2 * full
    # the held experts' grouped matmuls: 4096 rows a layer, nine of them
    assert lfm2_lm.moe_kernel_flops(CONFIG, t) == 6 * 3 * 4096 * d * 1536 * 8
    assert lfm2_lm.moe_kernel_bytes(CONFIG, t) == 9 * (
        4096 * d * 2 + 4096 * 1536 * 2 + 8 * d * 1536 * 2
    ) * 8
    # compute-bound by a hair at 512 rows a group: operations a byte against 240
    assert 240 < lfm2_lm.moe_kernel_flops(CONFIG, t) / lfm2_lm.moe_kernel_bytes(CONFIG, t) < 400


def test_the_gated_convolutions_work_by_hand():
    t, d = 8192, 2048
    # a channel a token: two gate products, three tap products, two sums; the
    # backward twice that
    assert lfm2_lm.sconv_conv_flops(CONFIG, t) == 3 * 7 * t * d * 7
    # bfloat16: three reads and a write forward (134 MB a layer, the issue's),
    # four reads and three writes backward
    assert 4 * t * d * 2 == 134_217_728
    assert lfm2_lm.sconv_conv_bytes(CONFIG, t) == (4 + 7) * t * d * 2 * 7
    # bound by memory: under one operation a byte against the v5e's 240
    assert lfm2_lm.sconv_conv_flops(CONFIG, t) / lfm2_lm.sconv_conv_bytes(CONFIG, t) < 1
    least = lfm2_lm.sconv_conv_bytes(CONFIG, t) / 819e9
    assert least == pytest.approx(3.15e-3, rel=0.01)         # seconds a step


def test_the_configuration_keeps_every_published_width():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        catalog = next(
            row for row in map(json.loads, f) if row["name"] == "LFM2-24B-A2B"
        )
    entry = next(c for c in BENCH["configs"] if c["name"] == "lfm2_24b_a2b")
    assert entry["source"] == catalog["source_url"]
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types", "num_dense_layers",
                       "num_experts", "vocab_size"}
    for key, value in catalog["config"].items():
        if key in reduced:
            assert CONFIG["published"][key] == value and CONFIG[key] != value
        else:
            assert CONFIG[key] == value, key
    # the cut: layers 1-9 of the published pattern, one dense layer and two
    # whole periods of ``full_attention, conv, conv, conv``
    assert CONFIG["layer_types"] == catalog["config"]["layer_types"][1:10]
    assert CONFIG["layer_types"][1:5] == CONFIG["layer_types"][5:9] == [
        "full_attention", "conv", "conv", "conv"
    ]
    assert len(CONFIG["layer_types"]) == CONFIG["num_hidden_layers"] == 9
    # the floors: a whole period, four expert layers, 8 experts, an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] - CONFIG["num_dense_layers"] >= 4
    share = CONFIG["share"]
    assert share["router_experts"] == catalog["config"]["num_experts"] == 64
    assert CONFIG["num_experts"] == 8 and CONFIG["num_experts"] * share["chips_a_layer"] == 64
    assert CONFIG["vocab_size"] * share["chips_a_layer"] == catalog["config"]["vocab_size"]
    # every width as published
    assert (CONFIG["hidden_size"], CONFIG["intermediate_size"], CONFIG["moe_intermediate_size"],
            CONFIG["conv_L_cache"], CONFIG["num_experts_per_tok"]) == (2048, 11776, 1536, 3, 4)
    assert lfm2_lm.head_dim(CONFIG) == 64
    spec = lfm2_lm.moe_spec(CONFIG)
    assert (spec.num_experts, spec.top_k, spec.d_ff, spec.held) == (64, 4, 1536, (0, 8))
    assert spec.norm_topk_eps == 1e-6 and spec.shared_d_ff == 0 and spec.bias_rate == 1e-3
    arch = lfm2_lm.arch_spec(CONFIG)
    assert arch.rope_theta == 1e6 and arch.tie_embeddings and arch.dense_layers == 1
    assert arch.layer_types.count("conv") == 7 and arch.short_conv.taps == 3


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        source = f.read().split('"""', 2)[2]                # after the docstring
    assert "edl_tpu" not in source
    assert "shifted(u, length - 1 - k)" in source           # the taps as shifted products
    assert "lax.scan" in source                             # a loop over the held experts


@pytest.fixture(scope="module")
def toy_state():
    job = lfm2_lm.build(TOY, 1, 0)
    model = job["model"]
    tokens = lfm2_lm.host_batches(TOY, 1, 0, n_batches=1)[0][0]
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
            {"params": params, "batch_stats": stats}, tokens, mutable=["intermediates"]
        )
        want, info = reference.forward(TOY, params, stats, tokens)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    for j, i in enumerate(range(TOY["num_dense_layers"], TOY["num_hidden_layers"])):
        seen = left["intermediates"]["layer_%d" % i]["moe"]
        np.testing.assert_allclose(
            seen["router_logits"][0], info["router_logits"][j], rtol=1e-4, atol=1e-5
        )
        np.testing.assert_array_equal(
            np.sort(seen["top_idx"][0], axis=-1), np.sort(info["experts"][j], axis=-1)
        )


def _state(model, params, stats, apply_fn=None):
    return types.SimpleNamespace(
        params=params, batch_stats=stats, apply_fn=apply_fn or model.apply
    )


def test_check_passes_the_program_at_toy_widths(toy_state):
    model, params, stats, _ = toy_state
    result = lfm2_lm.check(TOY, _state(model, params, stats), 0)
    assert result["ok"], result
    assert result["rows_dropped"] == 0 and result["tokens_misrouted"] == 0
    assert result["router_arithmetic_rel_err"] <= 1e-6
    assert result["router_arithmetic_rel_err_of_a_bfloat16_router"] > (
        10 * lfm2_lm.ROUTER_ARITHMETIC_REL_TOL
    )
    assert result["gated_conv"]["shape"] == [1, 128, 192]
    assert result["rotation"]["theta"] == 1e6
    assert result["grouped_matmul"]["groups"] == 4           # the held experts'
    assert result["kernel"]["window"] is None


def _changed(tree, path, change):
    tree = jax.tree.map(lambda a: a, tree)
    node = tree
    for key in path[:-1]:
        node[key] = dict(node[key])
        node = node[key]
    node[path[-1]] = change(node[path[-1]])
    return tree


@pytest.mark.parametrize("fault", [
    "a_dropped_tap", "a_dropped_gate", "a_base_of_ten_thousand", "a_dropped_bias",
    "a_bias_not_moved", "a_bfloat16_router", "an_epsilon_of_1e_20",
])
def test_check_fails_a_program_that_leaves_part_of_the_model_out(toy_state, fault):
    """Each mechanism's absence fails one of the check's limits: the program
    is given other parameters than the reference (a tap of zeros; an in
    projection whose ``C_g`` third is all ones' worth of nothing), another base,
    a bias of zeros or one it does not move, a router rounded to bfloat16, or
    the other renormalisation (told apart where the scores are small)."""
    model, params, stats, _ = toy_state
    d = TOY["hidden_size"]

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
                    moe = dict(layer["moe"])
                    moe["router_logits"] = tuple(
                        a.astype(jnp.bfloat16).astype(jnp.float32)
                        for a in moe["router_logits"]
                    )
                    layer["moe"] = moe
            return logits, left
        return apply_fn

    zero = jnp.zeros_like
    # scores near 1e-3 in every expert layer: where w / (sum + 1e-6) and
    # w / (sum + 1e-20) differ by a thousandth... too little for the logits'
    # limit at toy depth, so this fault is shown on the layer itself below
    apply_fn, failed_by = {
        "a_dropped_tap": (apply_with(lambda p: _changed(
            p, ("layer_2", "sconv", "conv_kernel"), lambda w: w.at[0].set(0.0))),
            "logits_rel_err"),
        "a_dropped_gate": (apply_with(lambda p: _changed(
            p, ("layer_3", "sconv", "in_proj", "kernel"),
            lambda w: w.at[:, d:2 * d].set(zero(w[:, d:2 * d])))), "logits_rel_err"),
        "a_base_of_ten_thousand": (apply_with(other=model.clone(
            arch=lfm2_lm.arch_spec(dict(TOY, rope_parameters={
                "rope_theta": 10000, "rope_type": "default"})))), "logits_rel_err"),
        "a_dropped_bias": (apply_with(change_stats=lambda s: _changed(
            s, ("layer_1", "moe", "router_bias"), zero)), "tokens_misrouted"),
        "a_bias_not_moved": (apply_with(freeze_bias=True), "bias_abs_err"),
        "a_bfloat16_router": (apply_with(coarse_router=True), "router_arithmetic_rel_err"),
        "an_epsilon_of_1e_20": (None, None),
    }[fault]
    if fault == "an_epsilon_of_1e_20":
        logits = jnp.full((4, 8), -12.0).at[:, :2].set(-11.0)
        weights, _, _, scores = reference.route(TOY, logits, jnp.zeros(8))
        total = 2 * float(scores[0, 0])
        assert float(jnp.sum(weights[0])) == pytest.approx(total / (total + 1e-6), rel=1e-5)
        assert float(jnp.sum(weights[0])) < 0.98            # 1e-20 would read 1
        return
    result = lfm2_lm.check(TOY, _state(model, params, stats, apply_fn), 0)
    assert not result["ok"]
    limit = {"logits_rel_err": lfm2_lm.LOGITS_REL_TOL, "tokens_misrouted": 0,
             "bias_abs_err": lfm2_lm.BIAS_ABS_TOL,
             "router_arithmetic_rel_err": lfm2_lm.ROUTER_ARITHMETIC_REL_TOL}[failed_by]
    assert result[failed_by] > limit, (failed_by, result[failed_by])


def test_a_bfloat16_convolution_fails_the_convolutions_own_limits():
    """The precision below the stated one: the same gates, taps and sums on
    bfloat16 values round five times where the program rounds once, and the
    taps' gradient is a sum of bfloat16 products. At a shape of several
    thousand steps both limits tell the two apart."""
    def coarse(x, w):
        c = w.shape[1]
        b_gate, c_gate, inner = (x[..., i * c:(i + 1) * c] for i in range(3))
        u = b_gate * inner
        taps = w.astype(x.dtype)
        t = u.shape[1]
        padded = jnp.pad(u, ((0, 0), (2, 0), (0, 0)))
        conv = sum(taps[k] * padded[:, k:k + t] for k in range(3))
        return c_gate * conv

    good = lfm2_lm.gated_conv_vs_reference(3, 1, 2048, 256, 3)
    assert good["max_rel_err"] <= 2.0 ** -8 < lfm2_lm.CONV_REL_TOL   # one rounding's bound
    assert good["d_taps"] <= lfm2_lm.CONV_TAPS_REL_TOL / 10
    bad = lfm2_lm.gated_conv_vs_reference(3, 1, 2048, 256, 3, conv=coarse)
    assert bad["max_rel_err"] > 1.3 * lfm2_lm.CONV_REL_TOL
    assert bad["d_taps"] > 50 * lfm2_lm.CONV_TAPS_REL_TOL


# the cell's nine layers and its routing (8 of 64 experts held, top-4) at a
# width where bfloat16 reads what it reads at the published widths on the chip
WIDE = dict(
    TOY, hidden_size=256, intermediate_size=512, moe_intermediate_size=192,
    num_attention_heads=8, num_key_value_heads=2, vocab_size=512, num_experts=8,
    num_experts_per_tok=4, num_hidden_layers=9, layer_types=CONFIG["layer_types"],
    share=dict(TOY["share"], chips_a_layer=8, chip=0, router_experts=64, experts_first=0),
    train=dict(TOY["train"], seq_len=1024),
)


@pytest.mark.parametrize("dtype, passes", [("bfloat16", True), ("float8_e4m3fn", False)])
def test_the_precision_below_fails_the_streams_three_limits(monkeypatch, dtype, passes):
    """The stated precision (bfloat16 compute) passes ``check`` and reads what
    the chip reads; an 8-bit float under the same program, the nearest
    precision below, fails the logits', the routers' and the flips' limits,
    each by a factor of three and more, and misroutes no token all the same
    (a flip is still the rounding's)."""
    from edl_tpu.models import transformer

    rope = transformer.rope  # jax promotes no 8-bit float: rotate it as float32
    monkeypatch.setattr(
        transformer, "rope",
        lambda x, positions, theta: rope(x.astype(jnp.float32), positions, theta).astype(x.dtype)
        if x.dtype.itemsize == 1 else rope(x, positions, theta),
    )
    model = lfm2_lm.build(WIDE, 1, 0)["model"]
    tokens = lfm2_lm.host_batches(WIDE, 1, 0, n_batches=1)[0][0]
    variables = model.init(jax.random.PRNGKey(0), tokens)
    coarse = model.clone(dtype=getattr(jnp, dtype), remat=False)
    result = lfm2_lm.check(
        WIDE, _state(coarse, variables["params"], variables["batch_stats"]), 0
    )
    assert result["tokens_misrouted"] == 0
    readings = [
        result[name] / limit for name, limit in (
            ("logits_rel_err", lfm2_lm.LOGITS_REL_TOL),
            ("router_logits_rel_err", lfm2_lm.ROUTER_LOGITS_REL_TOL),
            ("flipped_share", lfm2_lm.ROUTE_FLIP_LIMIT),
        )
    ]
    if passes:
        assert result["ok"], result
        assert all(0.2 < r < 0.6 for r in readings), readings   # room above, and a reading
    else:
        assert not result["ok"]
        assert all(r > 3.0 for r in readings), readings


def test_the_references_rule_moves_the_bias_against_the_load():
    config = {"train": {"expert_bias_rate": 0.001}}
    after = reference.bias_update(config, jnp.zeros(4), jnp.asarray([10, 2, 6, 6]))
    np.testing.assert_allclose(after, [-0.001, 0.001, 0.0, 0.0], atol=1e-9)
    after = reference.bias_update(config, jnp.zeros(4), jnp.asarray([9, 1, 1, 1]))
    np.testing.assert_allclose(after, [-0.0015, 0.0005, 0.0005, 0.0005], atol=1e-9)


def test_the_references_convolution_is_causal_and_starts_from_zeros():
    u = jnp.arange(1.0, 6.0).reshape(1, 5, 1)
    ones = jnp.ones_like(u)
    taps = jnp.asarray([[100.0], [10.0], [1.0]])            # oldest step first
    got = reference.gated_conv(ones, ones, u, taps)[0, :, 0]
    # c_t = 100 u_{t-2} + 10 u_{t-1} + u_t
    np.testing.assert_allclose(got, [1, 12, 123, 234, 345])


# -- the readers ---------------------------------------------------------------


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


TRACE = {
    "steps": 2, "step_busy_s_total": 0.5,
    "op_seconds": {"fusion.1": 0.060, "fusion.2": 0.040, "fusion.3": 0.010,
                   "fusion.4": 0.006, "fusion.5": 0.200},
    "op_text": {name: "%%%s = bf16[8192,2048] fusion(%%a)" % name
                for name in ("fusion.1", "fusion.2", "fusion.3", "fusion.4", "fusion.5")},
}
TABLE = {"fusion.1": "sconv_proj", "fusion.2": "sconv_proj", "fusion.3": "sconv_conv",
         "fusion.4": "sconv_conv"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def make_run(trace=TRACE, family=lfm2_lm, config=CONFIG):
    return types.SimpleNamespace(
        trace=trace, family=family, config=config, peaks=PEAKS, chips=1,
        items_per_step=8192,
    )


@pytest.mark.parametrize("name,want", [
    ("sconv_proj_ms", 50.0), ("sconv_conv_ms", 8.0), ("sconv_share", 100 * 0.116 / 0.5),
    # the bytes of two traced steps at the chip's bandwidth over the scope's 16 ms
    ("sconv_conv_roofline", 100 * lfm2_lm.sconv_conv_bytes(CONFIG, 2 * 8192) / 819e9 / 0.016),
])
def test_scope_readers_join_the_trace_to_the_programs_table(monkeypatch, name, want):
    from edl_tpu.obs import profile

    read = reader(name).read
    monkeypatch.setattr(profile, "step_scopes", lambda scopes: dict(TABLE))
    assert read(make_run()) == pytest.approx(want)
    assert read(make_run(trace=None)) is None            # no device trace
    assert read(make_run(trace=dict(TRACE, steps=0))) is None
    monkeypatch.setattr(profile, "step_scopes", lambda scopes: {})
    assert read(make_run()) is None                      # a model without the scopes
    monkeypatch.delattr(profile, "step_scopes")
    assert read(make_run()) is None                      # a program before the join


def test_the_roofline_finds_nothing_in_another_family(monkeypatch):
    from benchmark.families import transformer_lm
    from edl_tpu.obs import profile

    monkeypatch.setattr(profile, "step_scopes", lambda scopes: dict(TABLE))
    assert reader("sconv_conv_roofline").read(make_run(family=transformer_lm)) is None


def test_the_bias_reader_reads_the_gauge():
    read = reader("expert_bias_absmax").read
    run = types.SimpleNamespace(
        at_close={"registry": {"edl_train_moe_bias_absmax": {"": 0.0125}}}
    )
    assert read(run) == 0.0125
    assert read(types.SimpleNamespace(at_close={"registry": {}})) is None  # the parent's


def test_the_timeline_file_lists_the_readers_for_the_familys_cell():
    listed = {m["name"] for m in BENCH["per_layer"]}
    assert not set(lfm2_timeline.DEVICE_READERS) & listed
    assert "expert_bias_absmax" in listed
    extended = lfm2_timeline.with_lfm2(BENCH)
    by_name = {m["name"]: m for m in extended["per_layer"]}
    for name in lfm2_timeline.DEVICE_READERS:
        assert by_name[name]["workloads"] == [CELL]
    for name in ("moe_share", "moe_kernel_roofline", "moe_route_ms",
                 "moe_experts_ms", "moe_combine_ms"):
        assert by_name[name]["workloads"] == [
            "olmoe_1b_7b.steady", "trinity_mini.steady", CELL
        ]
    assert by_name["gdn_share"]["workloads"] == ["olmo_hybrid_7b.steady"]
    assert lfm2_timeline.with_lfm2(extended) == extended
    # everything the accepted file lists is there, unchanged and first
    assert extended["per_layer"][:len(BENCH["per_layer"])] == BENCH["per_layer"]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(trace):
    proc, lines = run_cell(CELL, 1, "--trace", str(trace), "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    detail = json.loads(lines[-2])["detail"]
    assert detail["checks"]["reference"]["ok"]
    assert detail["checks"]["compiles_in_window"] == 0
    if trace:
        # the listed gauge reads on the CPU too: it is no device metric
        assert "expert_bias_absmax" in detail["judged"]
        assert "expert_bias_absmax" not in NEEDS_A_DEVICE
