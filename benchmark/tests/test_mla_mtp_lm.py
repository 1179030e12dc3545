"""The ``mla_mtp_lm`` family: its operation counts against arithmetic done by
hand, its reference against the program at toy widths (and what each limit of
``check`` is for), the configuration file against the published one, its
readers on a hand-made trace, and the rehearsal of its cell."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import mtp_timeline
from benchmark.families import mla_mtp_lm
from benchmark.reference import mla_mtp_lm as reference
from benchmark.tests.test_rehearse import NEEDS_A_DEVICE, run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "glm_4_7_flash.json")
TOY = load("rehearsal", "configs", "glm_4_7_flash.json")
BENCH = load("..", "BENCHMARK.json")
CELL = "glm_4_7_flash.steady"
# the catalog row's ``config`` (model-configs guide, GLM-4.7-Flash), key for key
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536, "topk_method": "noaux_tc",
    "norm_topk_prob": True, "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1, "routed_scaling_factor": 1.8,
    "num_experts_per_tok": 4, "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000,
    "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880,
}


def test_glm_4_7_flash_by_hand():
    d, t, h = 2048, 8192, 20
    # the query pair through 768, the latent with the rotated key, its up
    # projection to keys of 192 and values of 256, the out projection
    mla = d * 768 + 768 * h * 256 + d * 576 + 512 * h * 448 + h * 256 * d
    assert mla_mtp_lm.mla_mixer_params(CONFIG) == mla == 21_757_952
    # the router at its published width, the shared expert, and 4 x 8 / 64 =
    # 1/2 routed expert a token, expected
    assert mla_mtp_lm.routed_experts_a_token(CONFIG) == 0.5
    expert_layer = d * 64 + 3 * d * 1536 + 0.5 * 3 * d * 1536
    assert expert_layer == 14_286_848
    # six attention calls and five expert layers (the module's block among
    # both), one dense SwiGLU, the joined projection, the head twice
    params = 6 * mla + 3 * d * 10240 + 5 * expert_layer + 2 * d * d + 2 * d * 19360
    assert (mla_mtp_lm.attention_calls(CONFIG), mla_mtp_lm.expert_layers(CONFIG)) == (6, 5)
    assert mla_mtp_lm.matmul_params(CONFIG) == params == 352_583_680
    attention = 2 * h * (t * t / 2) * (256 + 256)           # scores at 256, values at 256
    assert mla_mtp_lm.attention_forward_flops(CONFIG, 1) == 6 * attention
    want = 6 * params + 3 * 6 * attention / t
    assert mla_mtp_lm.flops_per_item(CONFIG) == pytest.approx(want)
    # the issue's reckoning: 1208 MFLOP a token forward, three times that a step
    assert mla_mtp_lm.flops_per_item(CONFIG) == pytest.approx(3 * 1.2085e9, rel=0.001)
    module = 6 * (mla + expert_layer + 2 * d * d + d * 19360) + 3 * attention / t
    assert module / want == pytest.approx(0.209, abs=0.002)  # the module's share of the work
    assert (6 * 6 * mla + 3 * 6 * attention / t) / want == pytest.approx(0.632, abs=0.002)


def test_the_kernels_work_by_hand():
    t, h = 8192, 20
    pairs = h * t * t / 2
    assert mla_mtp_lm.kernel_flops(CONFIG, 1) == 6 * 2 * pairs * (4 * 256 + 3 * 256)
    assert mla_mtp_lm.TRACE_KERNELS == ("%attn_mla", " custom-call(")
    rows = 8192 * 0.5                                        # 512 a held expert
    assert mla_mtp_lm.moe_kernel_flops(CONFIG, 8192) == 6 * 3 * rows * 2048 * 1536 * 5
    assert mla_mtp_lm.moe_kernel_bytes(CONFIG, 8192) == (
        9 * (rows * 2048 * 2 + rows * 1536 * 2 + 8 * 2048 * 1536 * 2) * 5
    )


def test_the_configuration_keeps_every_published_key_but_the_three_it_lists():
    entry = next(c for c in BENCH["configs"] if c["name"] == "glm_4_7_flash")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value and type(CONFIG[key]) is type(value), key
    share = CONFIG["share"]
    assert CONFIG["num_hidden_layers"] == 5                  # the dense layer and four that follow
    assert CONFIG["n_routed_experts"] * share["chips_a_layer"] == 64 == share["router_experts"]
    assert CONFIG["vocab_size"] * share["chips_a_vocabulary"] == 154880
    for key in ("assumed", "departures", "not_run", "deployment", "published", "share", "plan"):
        assert CONFIG[key], key
    train = CONFIG["train"]
    assert (train["seq_len"], train["batch_per_chip"], train["distinct_batches"]) == (8192, 1, 256)
    assert train["mtp_loss_weight"] == 0.3 and train["remat_policy"] == "save_flash"
    assert train["start"] == load("configs", "solar_open2_250b.json")["train"]["start"]


def test_the_parameters_are_the_issues_count():
    model = mla_mtp_lm.build(CONFIG, 1, 0)["model"]
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8192), jnp.int32)
    )["params"]
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))  # noqa: E731
    assert count(shapes["layer_0"]["attn"]) == 21_757_952 + 768 + 512    # and the two norms
    assert count(shapes) == 706_518_528 == CONFIG["plan"]["tried"][0]["parameters"]
    assert shapes["mtp_eh_proj"]["kernel"].shape == (4096, 2048)
    assert shapes["mtp_block"]["moe"]["gate"].shape == (8, 2048, 1536)
    assert shapes["mtp_block"]["moe"]["router"]["kernel"].shape == (2048, 64)
    assert set(shapes["mtp_block"]["attn"]) == set(shapes["layer_1"]["attn"])


def test_the_plan_fits_the_chip():
    plan = CONFIG["plan"]
    tried = plan["tried"][0]
    assert tried["seq_len"] == CONFIG["train"]["seq_len"] == plan["chosen"]["seq_len"]
    assert tried["total_gb"] < plan["chip_gb"] == 15.75
    assert tried["left_gb"] == pytest.approx(plan["chip_gb"] - tried["total_gb"], abs=2e-3)
    assert tried["on_chip"]["ran"] and tried["on_chip"]["correct"]
    assert 4.0 <= tried["on_chip"]["hbm_peak_gb"] < 15.75


def test_the_toy_twin_has_every_mechanism():
    assert set(TOY) >= set(PUBLISHED) - {"max_position_embeddings", "model_type"}
    assert TOY["q_lora_rank"] and TOY["num_nextn_predict_layers"] == 1
    assert TOY["first_k_dense_replace"] == 1 < TOY["num_hidden_layers"]
    assert TOY["n_routed_experts"] < TOY["share"]["router_experts"]
    assert TOY["v_head_dim"] > TOY["qk_nope_head_dim"]       # values wider than the keys' plain part
    assert TOY["train"]["start"] == CONFIG["train"]["start"]


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        source = f.read().split('"""', 2)[2]                # after the docstring
    assert "edl_tpu" not in source


@pytest.fixture(scope="module")
def toy_state():
    job = mla_mtp_lm.build(mla_mtp_lm.as_drawn(TOY), 1, 0)   # a head that is not zero
    model = job["model"]
    tokens = mla_mtp_lm.host_batches(TOY, 1, 0, n_batches=1)[0][0]
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
            mutable=["intermediates", "metrics", "losses"],
        )
        want, ahead, info = reference.forward(TOY, params, stats, tokens)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        left["intermediates"]["mtp_logits"][0][:, :-2], ahead[:, :-2], rtol=2e-4, atol=2e-4
    )
    _, mtp = reference.losses(want, ahead, tokens, tokens)
    assert float(left["metrics"]["mtp_loss"][0]) == pytest.approx(float(mtp), rel=1e-5)
    assert float(left["losses"]["mtp_loss"][0]) == pytest.approx(0.3 * float(mtp), rel=1e-5)
    for j, name in enumerate(reference.expert_blocks(TOY)):
        seen = left["intermediates"][name]["moe"]
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
    result = mla_mtp_lm.check(TOY, _state(model, params, stats), 0)
    assert result["ok"], result
    assert result["rows_dropped"] == 0 and result["tokens_misrouted"] == 0
    assert result["expert_blocks"] == ["layer_1", "layer_2", "mtp_block"]
    assert len(result["flipped_share_by_layer"]) == len(result["rows_held"]) == 3
    assert result["router_arithmetic_rel_err"] <= 1e-6
    assert result["router_arithmetic_rel_err_of_a_bfloat16_router"] > (
        10 * mla_mtp_lm.ROUTER_ARITHMETIC_REL_TOL
    )
    assert result["kernel"]["shape"] == [1, 4, 128, 32, 32]  # keys of 24 + 8, values of 32
    assert result["grouped_matmul"]["groups"] == 4           # the held experts'
    assert result["mtp_loss"] == pytest.approx(result["reference_mtp_loss"], rel=1e-3)


def _changed(tree, path, change):
    tree = jax.tree.map(lambda a: a, tree)
    node = tree
    for key in path[:-1]:
        node[key] = dict(node[key])
        node = node[key]
    node[path[-1]] = change(node[path[-1]])
    return tree


FAULTS = {
    # name: (the path of a parameter, what the program does to it, the reading that fails)
    "the_query_latent_is_not_normed": (
        ("layer_0", "attn", "q_norm", "scale"), jnp.ones_like, "query_rel_err"),
    "the_stream_joins_the_module_without_its_norm": (
        ("mtp_hnorm", "scale"), jnp.ones_like, "mtp_logits_rel_err"),
    "the_joined_halves_are_the_other_way_round": (
        ("mtp_eh_proj", "kernel"), lambda w: jnp.roll(w, w.shape[0] // 2, axis=0),
        "mtp_logits_rel_err"),
    "the_module_has_no_last_norm_of_its_own": (
        ("mtp_norm", "scale"), jnp.ones_like, "mtp_logits_rel_err"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_check_fails_a_program_that_leaves_part_of_the_model_out(toy_state, fault):
    model, params, stats, _ = toy_state
    path, change, reading = FAULTS[fault]

    def apply_fn(variables, tokens, **kwargs):
        changed = _changed(dict(variables["params"]), path, change)
        return model.apply({**variables, "params": changed}, tokens, **kwargs)

    result = mla_mtp_lm.check(TOY, _state(model, params, stats, apply_fn), 0)
    limit = result["query_rel_tol"] if reading == "query_rel_err" else result["logits_rel_tol"]
    assert not result["ok"] and result[reading] > 2 * limit, (reading, result[reading])
    if reading != "query_rel_err":   # the trunk is the model's own: only the module is off
        assert result["logits_rel_err"] <= result["logits_rel_tol"]


def test_check_fails_a_module_scored_against_the_wrong_token(toy_state):
    """A module that scores position i against token i+1 (the main head's
    target) sows another loss: the logits agree and the sown value does not."""
    model, params, stats, _ = toy_state

    def apply_fn(variables, tokens, **kwargs):
        logits, left = model.apply(variables, tokens, **kwargs)
        ahead = left["intermediates"]["mtp_logits"][0]
        logp = jax.nn.log_softmax(ahead[:, :-1], axis=-1)
        wrong = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
        left = _changed(dict(left), ("metrics", "mtp_loss"), lambda _: (wrong,))
        return logits, left

    result = mla_mtp_lm.check(TOY, _state(model, params, stats, apply_fn), 0)
    assert not result["ok"] and result["mtp_loss_rel_err"] > result["loss_rel_tol"]
    assert result["mtp_logits_rel_err"] <= result["logits_rel_tol"]


def test_check_fails_a_head_at_zero():
    """The start's zero head on fresh parameters compares 0 with 0: the check
    says so instead of passing."""
    model = mla_mtp_lm.build(TOY, 1, 0)["model"]
    tokens = mla_mtp_lm.host_batches(TOY, 1, 0, n_batches=1)[0][0]
    variables = model.init(jax.random.PRNGKey(0), tokens)
    result = mla_mtp_lm.check(
        TOY, _state(model, variables["params"], variables["batch_stats"]), 0
    )
    assert not result["logits_nonzero"] and not result["ok"]
    assert result["mtp_loss"] == pytest.approx(np.log(TOY["vocab_size"]), rel=1e-4)


WIDE = dict(
    TOY, hidden_size=256, intermediate_size=512, moe_intermediate_size=128,
    q_lora_rank=96, kv_lora_rank=64, qk_nope_head_dim=48, qk_rope_head_dim=16, v_head_dim=64,
    train=dict(TOY["train"], seq_len=512),
)


@pytest.mark.parametrize("dtype, passes", [("bfloat16", True), ("float8_e4m3fn", False)])
def test_the_precision_below_fails_the_limits(monkeypatch, dtype, passes):
    """The stated precision (bfloat16 compute) passes ``check``; an 8-bit float
    under the same program, the nearest precision below, fails at least one of
    the limits with room (the 8-bit program hands its kernels bfloat16 operands
    and rotates in float32: no kernel and no promotion rule takes an 8-bit
    float)."""
    from bench_results.solar_precision_below import in_bfloat16
    from edl_tpu.models import moe, transformer

    rope = transformer.rope
    monkeypatch.setattr(
        transformer, "rope",
        lambda x, positions, theta: rope(x.astype(jnp.float32), positions, theta).astype(x.dtype)
        if x.dtype.itemsize == 1 else rope(x, positions, theta),
    )
    monkeypatch.setattr(moe, "grouped_matmul", in_bfloat16(moe.grouped_matmul))
    monkeypatch.setattr(transformer, "attention", in_bfloat16(transformer.attention))
    model = mla_mtp_lm.build(mla_mtp_lm.as_drawn(WIDE), 1, 0)["model"]
    tokens = mla_mtp_lm.host_batches(WIDE, 1, 0, n_batches=1)[0][0]
    variables = model.init(jax.random.PRNGKey(0), tokens)
    coarse = model.clone(dtype=getattr(jnp, dtype), remat=False)
    result = mla_mtp_lm.check(
        WIDE, _state(coarse, variables["params"], variables["batch_stats"]), 0
    )
    readings = {
        name: result[name] / limit for name, limit in (
            ("logits_rel_err", mla_mtp_lm.LOGITS_REL_TOL),
            ("mtp_logits_rel_err", mla_mtp_lm.LOGITS_REL_TOL),
            ("router_logits_rel_err", mla_mtp_lm.ROUTER_LOGITS_REL_TOL),
            ("flipped_share", mla_mtp_lm.ROUTE_FLIP_LIMIT),
            ("query_rel_err", mla_mtp_lm.QUERY_REL_TOL),
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
    "op_seconds": {"fusion.1": 0.02, "fusion.2": 0.10, "fusion.3": 0.06, "fusion.4": 0.20,
                   "attn_mla.1": 0.03, "attn_mla.2": 0.05, "attn_mla.3": 0.04, "while.1": 0.5},
    "op_text": {
        "fusion.1": "%fusion.1 = fusion(...)", "fusion.2": "%fusion.2 = fusion(...)",
        "fusion.3": "%fusion.3 = fusion(...)", "fusion.4": "%fusion.4 = fusion(...)",
        "attn_mla.1": "%attn_mla.1 = custom-call(...) tpu_custom_call",
        "attn_mla.2": "%attn_mla.2 = custom-call(...) tpu_custom_call",
        "attn_mla.3": "%attn_mla.3 = custom-call(...) tpu_custom_call",
        "while.1": "%while.1 = while(...)",
    },
}
# what ``step_scopes`` gives for each set of scopes asked: the module's (the
# block's attention call under ``mtp`` when only the module's are asked) and
# the latent layer's (the same call under ``attn_mla``)
TABLES = {
    mtp_timeline.SCOPES: {"fusion.1": "mtp_join", "fusion.2": "mtp", "fusion.3": "mtp_head",
                          "attn_mla.3": "mtp", "while.1": "mtp"},
    "other": {"fusion.2": "mla_proj", "fusion.4": "mla_proj", "attn_mla.1": "attn_mla",
              "attn_mla.2": "attn_mla", "attn_mla.3": "attn_mla"},
}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def make_run(trace=TRACE, family=mla_mtp_lm, config=CONFIG):
    return types.SimpleNamespace(
        trace=trace, family=family, config=config, peaks=PEAKS, chips=1,
        items_per_step=8192, at_close={"registry": {}},
    )


def test_the_readers_sum_the_modules_scopes_and_the_kernels(monkeypatch):
    from benchmark import kda_timeline
    from edl_tpu.obs import profile

    monkeypatch.setattr(
        profile, "step_scopes", lambda scopes: dict(TABLES.get(tuple(scopes), TABLES["other"]))
    )
    kept = kda_timeline.SCOPES
    # everything under the module, the loop's own event left out: 0.02 + 0.10 + 0.06 + 0.04
    assert reader("mtp_share").read(make_run()) == pytest.approx(22.0)
    assert reader("mtp_join_ms").read(make_run()) == pytest.approx(10.0)
    assert reader("mtp_head_ms").read(make_run()) == pytest.approx(30.0)
    assert kda_timeline.SCOPES == kept                       # lent for the call, and given back
    assert reader("mla_proj_ms").read(make_run()) == pytest.approx(150.0)
    assert reader("attn_mla_ms").read(make_run()) == pytest.approx(60.0)
    # the six calls' kernels: the accepted readers find the custom calls by the
    # family's TRACE_KERNELS and count kernel_flops at 2 (4 x 256 + 3 x 256) a pair
    least = mla_mtp_lm.kernel_flops(CONFIG, 2) / 197e12
    assert reader("attn_kernel_roofline").read(make_run()) == pytest.approx(100 * least / 0.12)
    assert reader("attn_kernel_share").read(make_run()) == pytest.approx(100 * 0.12 / 1.0)
    # a program without the scopes, as the parent: nothing to read, and no raise
    monkeypatch.setattr(profile, "step_scopes", lambda scopes: {})
    for name in mtp_timeline.DEVICE_READERS:
        assert reader(name).read(make_run()) is None
    assert reader("mtp_share").read(make_run(trace=None)) is None


def test_the_new_gauge_reader_reads_the_registry():
    module = reader("mtp_loss")
    run = make_run()
    assert module.read(run) is None                         # a program without the module
    run.at_close = {"registry": {"edl_train_mtp_loss": {"": 9.871}}}
    assert module.read(run) == 9.871
    entry = next(m for m in BENCH["per_layer"] if m["name"] == "mtp_loss")
    assert entry["workloads"] == [CELL]
    assert (module.NAME, module.UNIT, module.BETTER, module.SOURCE, module.LAYER, module.MOVES) == (
        entry["name"], entry["unit"], entry["better"], entry["source"], entry["layer"],
        entry["moves"])


def test_the_benchmark_gained_one_configuration_one_cell_and_the_cells_name_on_twelve_lists():
    """By name and not by place: a later PR appends its own after these."""
    assert [c["file"] for c in BENCH["configs"] if c["name"] == "glm_4_7_flash"] == [
        "benchmark/configs/glm_4_7_flash.json"
    ]
    cells = [w for w in BENCH["workloads"] if w["config"] == "glm_4_7_flash"]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [(CELL, "steady", 1)]
    assert len(cells[0]["why"]) <= 200
    lists = [m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])]
    assert lists == [
        "attn_kernel_share", "attn_kernel_roofline", "expert_load_max", "expert_held_load_max",
        "expert_bias_absmax", "step_plain_fallbacks", "step_kernel_calls", "step_loops",
        "step_unplaced_share", "step_time_drift", "expert_rows_held", "mtp_loss",
    ]


def test_the_timeline_file_lists_the_readers_for_the_familys_cell():
    extended = mtp_timeline.with_mtp(BENCH)
    by_name = {m["name"]: m for m in extended["per_layer"]}
    for name in mtp_timeline.DEVICE_READERS + mtp_timeline.SHARED_READERS:
        assert CELL in by_name[name]["workloads"], name
        module = reader(name)
        assert by_name[name]["source"] == module.SOURCE == "device_trace"
    for name in mtp_timeline.DEVICE_READERS:
        assert by_name[name]["workloads"] == [CELL]
    listed = {m["name"] for m in BENCH["per_layer"]}
    assert not listed & set(mtp_timeline.DEVICE_READERS + mtp_timeline.SHARED_READERS)
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
        assert {"mtp_loss", "expert_bias_absmax", "expert_rows_held", "expert_load_max",
                "expert_held_load_max", "step_unplaced_share"} <= set(detail["judged"])
        from benchmark import run as bench_run
        cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
        listed = {m["name"] for m in BENCH["per_layer"] if bench_run.applies(m, cell["name"])}
        assert listed - set(detail["judged"]) <= NEEDS_A_DEVICE
