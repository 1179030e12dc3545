"""The ``smallthinker_lm`` family: its operation counts against arithmetic done
by hand, its reference against the program at toy widths (and what each limit
of ``check`` is for: the precision below, a router handed another tensor than
the block's input), the configuration file against the published one, its
gauge's reader, and the rehearsal of its cell."""

import json
import os
import types
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import smallthinker_timeline
from benchmark.families import smallthinker_lm as family
from benchmark.reference import smallthinker_lm as reference
from benchmark.tests.test_mla_mtp_lm import reader
from benchmark.tests.test_rehearse import NEEDS_A_DEVICE, run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "smallthinker_21b_a3b.json")
TOY = load("rehearsal", "configs", "smallthinker_21b_a3b.json")
BENCH = load("..", "BENCHMARK.json")
CELL = "smallthinker_21b_a3b.steady"
PERIOD = [0, 1, 1, 1]
# the catalog row's ``config`` (model-configs guide, SmallThinker-21BA3B-Instruct), key for key
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_layout": PERIOD * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": PERIOD * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False, "vocab_size": 151936,
}
REDUCED = ["num_hidden_layers", "rope_layout", "sliding_window_layout",
           "moe_num_primary_experts", "vocab_size"]


def test_smallthinker_by_hand():
    """Depth 8 at T = 16,384, a token: attention's four projections 2 x 2560 x
    128 x (28 + 4), the router 2560 x 64, three quarters of an expert of 3 x
    2560 x 768, the head 2560 x 18,992; the full layer's T / 2 visible keys a
    query and a window's (T W - W^2 / 2) / T, 4 x 28 x 128 operations each."""
    layer = 20_971_520 + 163_840 + 4_423_680
    assert family.attention_params(CONFIG) == 20_971_520
    assert family.routed_experts_a_token(CONFIG) == 0.75
    assert family.matmul_params(CONFIG) == 8 * layer + 48_619_520 == 253_091_840
    full, window = 14336 * 8192, 14336 * 3584
    period = full + 3 * window
    assert family.layer_attention_forward_flops(CONFIG, 1, False) / 16384 == full
    assert family.layer_attention_forward_flops(CONFIG, 1, True) / 16384 == window
    assert family.attention_forward_flops(CONFIG, 1) / 16384 == 2 * period
    assert family.flops_per_item(CONFIG) == 6 * 253_091_840 + 3 * 2 * period == 3_148_038_144
    # the shares of the counted work, against the issue's 47 / 29 / 17 / 6 % at depth 4
    assert 6 * period / 3_148_038_144 == pytest.approx(0.518, abs=1e-3)
    assert 6 * 8 * 20_971_520 / 3_148_038_144 == pytest.approx(0.320, abs=1e-3)
    assert 6 * 48_619_520 / 3_148_038_144 == pytest.approx(0.093, abs=1e-3)
    assert 6 * 8 * 4_423_680 / 3_148_038_144 == pytest.approx(0.067, abs=1e-3)
    # and at the issue's depth 4 the issue's count
    one_period = dict(
        CONFIG, num_hidden_layers=4, rope_layout=PERIOD, sliding_window_layout=PERIOD
    )
    assert family.flops_per_item(one_period) == 6 * 150_855_680 + 3 * period == 1_719_877_632
    assert 3 * period / 1_719_877_632 == pytest.approx(0.474, abs=1e-3)


def test_the_kernels_work_by_hand():
    one = 16384
    period = 14336 * 8192 + 3 * 14336 * 3584
    assert family.kernel_flops(CONFIG, 1) == 3.5 * one * 2 * period
    assert family.kind_kernel_flops(CONFIG, 1, False) + family.kind_kernel_flops(
        CONFIG, 1, True
    ) == family.kernel_flops(CONFIG, 1)
    wide, narrow = one * 28 * 128 * 2, one * 4 * 128 * 2
    assert family.kind_kernel_bytes(CONFIG, 1, True) == 6 * (9 * wide + 6 * narrow)
    assert family.kind_kernel_bytes(CONFIG, 1, False) == 2 * (9 * wide + 6 * narrow)
    rows = one * 0.75                                       # 12,288: 1536 a held expert
    assert family.moe_kernel_flops(CONFIG, one) == 6 * 3 * rows * 2560 * 768 * 8
    assert family.moe_kernel_bytes(CONFIG, one) == 9 * (
        rows * 2560 * 2 + rows * 768 * 2 + 8 * 2560 * 768 * 2
    ) * 8


def test_the_configuration_keeps_every_published_key_but_the_five_it_lists():
    entry = next(c for c in BENCH["configs"] if c["name"] == "smallthinker_21b_a3b")
    assert entry["reduced"] == REDUCED
    assert entry["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json"
    )
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value and type(CONFIG[key]) is type(value), key
    share, depth = CONFIG["share"], CONFIG["num_hidden_layers"]
    assert depth % 4 == 0 and depth >= 4                    # whole periods, the guide's floor
    assert CONFIG["rope_layout"] == CONFIG["sliding_window_layout"] == PERIOD * (depth // 4)
    assert CONFIG["moe_num_primary_experts"] * share["chips_a_layer"] == 64 == share["router_experts"]
    assert CONFIG["vocab_size"] * share["chips_a_vocabulary"] == 151936
    for key in ("assumed", "departures", "not_run", "deployment", "published", "share", "plan"):
        assert CONFIG[key], key
    assert "block input" in CONFIG["assumed"][0] or "block's input" in CONFIG["assumed"][0]
    train = CONFIG["train"]
    assert (train["seq_len"], train["distinct_batches"]) == (16384, 256)
    assert (train["load_balance_coef"], train["router_z_coef"]) == (0.01, 0.001)
    assert train["remat"] and train["remat_policy"] == "save_flash"


def test_the_parameters_are_the_issues_count():
    model = family.build(CONFIG, 1, 0)["model"]
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 16384), jnp.int32)
    )["params"]
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))  # noqa: E731
    assert count(shapes["layer_0"]["attn"]) == 20_971_520
    assert count(shapes["layer_0"]) == 68_326_400           # + the router, two norms, 8 experts
    assert count(shapes) == 8 * 68_326_400 + 2 * 48_619_520 + 2560 == 643_852_800
    assert count(shapes) == CONFIG["plan"]["chosen"]["parameters"]
    assert shapes["layer_1"]["moe"]["gate"].shape == (8, 2560, 768)
    assert shapes["layer_1"]["moe"]["router"]["kernel"].shape == (2560, 64)
    assert set(shapes["layer_0"]) == {"ln1", "attn", "ln2", "moe"}
    assert set(shapes["layer_0"]["attn"]) == {"q", "k", "v", "o"}
    spec = model.moe
    assert (spec.route_from, spec.activation, spec.gated, spec.shared_d_ff) == (
        "block_input", "relu", True, 0
    )
    assert model.arch.layer_types == (("attention",) + ("sliding_attention",) * 3) * 2
    assert model.arch.rope == "sliding" and model.arch.rope_theta == 1.5e6


def test_the_plan_fits_the_chip():
    plan = CONFIG["plan"]
    chosen = plan["chosen"]
    assert (chosen["num_hidden_layers"], chosen["batch_per_chip"]) == (
        CONFIG["num_hidden_layers"], CONFIG["train"]["batch_per_chip"]
    )
    by_rung = {(t["num_hidden_layers"], t["batch_per_chip"]): t for t in plan["tried"]}
    tried = by_rung[chosen["num_hidden_layers"], chosen["batch_per_chip"]]
    assert tried["total_gb"] + 1.0 <= plan["chip_gb"] == 15.75
    assert tried["left_gb"] == pytest.approx(plan["chip_gb"] - tried["total_gb"], abs=2e-3)
    assert tried["on_chip"]["ran"] and tried["on_chip"]["correct"]
    assert 4.0 <= tried["on_chip"]["hbm_peak_gb"] < 15.75
    # the ladder's order: every rung before the chosen one was tried and fell
    order = [(8, 1), (4, 2), (4, 1)]
    for rung in order[:order.index((chosen["num_hidden_layers"], chosen["batch_per_chip"]))]:
        assert by_rung[rung]["left_gb"] < 1.0 or not by_rung[rung]["on_chip"]["ran"], rung


def test_the_toy_twin_has_every_mechanism():
    assert set(TOY) >= set(PUBLISHED)
    assert TOY["num_attention_heads"] == 7 * TOY["num_key_value_heads"]   # the group of seven
    assert TOY["rope_layout"] == TOY["sliding_window_layout"] == PERIOD
    assert TOY["sliding_window_size"] < TOY["train"]["seq_len"]
    assert TOY["moe_num_primary_experts"] < TOY["share"]["router_experts"]
    assert TOY["train"]["start"] == CONFIG["train"]["start"]
    assert TOY["train"]["optimizer"] == CONFIG["train"]["optimizer"]


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        source = f.read().split('"""', 2)[2]                # after the docstring
    assert "edl_tpu" not in source


def _state(model, params, apply_fn=None):
    return types.SimpleNamespace(params=params, apply_fn=apply_fn or model.apply)


@pytest.fixture(scope="module")
def toy_state():
    model = family.build(family.as_drawn(TOY), 1, 0)["model"]  # a head that is not zero
    tokens = family.host_batches(TOY, 1, 0, n_batches=1)[0][0]
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 200))
    params = jax.tree.map(
        lambda a: a * (1 + 0.2 * jax.random.normal(next(keys), a.shape)) if a.ndim == 1 else a,
        params,
    )
    return model, params


def test_check_passes_the_program_at_toy_widths(toy_state):
    model, params = toy_state
    result = family.check(TOY, _state(model, params), 0)
    assert result["ok"], result
    assert result["rows_dropped"] == 0 and result["tokens_misrouted"] == 0
    assert len(result["router_logits_rel_err_by_layer"]) == len(result["rows_held"]) == 4
    assert result["router_arithmetic_rel_err"] <= 1e-6
    assert result["router_arithmetic_rel_err_of_a_bfloat16_router"] > (
        10 * family.ROUTER_ARITHMETIC_REL_TOL
    )
    assert result["kernel"]["window"]["shape"] == [1, 7, 1, 128, 16]
    assert result["kernel"]["window"]["window"] == 32 and result["kernel"]["full"]["window"] is None
    assert result["grouped_matmul"]["groups"] == 4           # the held experts'
    assert all(0.3 < dead < 0.7 for dead in result["gate_dead"])


def _unit_rms(x):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-6)).astype(x.dtype)


# what a block hands its router in the block input's place
FAULTS = {
    "the_normed_input": lambda h, block_input: _unit_rms(block_input),
    "the_stream_after_attention": lambda h, block_input: h,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_check_fails_a_router_handed_another_tensor(toy_state, fault):
    """The routers' logits are compared with ``x W_r`` on the reference's BLOCK
    INPUT: a program whose router reads the input after its norm, or what the
    experts read, computes every later layer with other experts and fails by
    the routers' logits and by the share of the choices that differ (a flip is
    then no tie's: the logits themselves are other numbers)."""
    from edl_tpu.models import transformer

    model, params = toy_state
    feed_forward = transformer.Block._feed_forward

    def faulty(self, h, dense=False, route_x=None):
        return feed_forward(self, h, dense, FAULTS[fault](h, route_x))

    def apply_fn(variables, tokens, **kwargs):
        with mock.patch.object(transformer.Block, "_feed_forward", faulty):
            return model.apply(variables, tokens, **kwargs)

    result = family.check(TOY, _state(model, params, apply_fn), 0)
    assert not result["ok"]
    assert result["router_logits_rel_err"] > 5 * family.ROUTER_LOGITS_REL_TOL
    assert result["router_arithmetic_rel_err"] <= family.ROUTER_ARITHMETIC_REL_TOL
    if fault == "the_stream_after_attention":
        assert result["flipped_share"] > 2 * family.ROUTE_FLIP_LIMIT
    else:  # a token's normed input is its input times a positive number: the same
        # order of experts, under another temperature of the weights' softmax
        assert result["flipped_share"] <= family.ROUTE_FLIP_LIMIT


def test_check_fails_a_silu_gate(toy_state):
    """A SiLU in the ReLU's place sows no dead share and moves the logits."""
    import dataclasses

    model, params = toy_state
    silu = model.clone(moe=dataclasses.replace(model.moe, activation="silu"))

    def apply_fn(variables, tokens, mutable=(), **kwargs):
        logits, left = silu.apply(variables, tokens, mutable=mutable, **kwargs)
        metrics = jax.tree.map(lambda a: a, left["metrics"])
        for name in metrics:
            metrics[name]["moe"]["moe_gate_dead"] = (jnp.float32(0.0),)
        return logits, dict(left, metrics=metrics)

    result = family.check(TOY, _state(model, params, apply_fn), 0)
    assert not result["ok"]
    assert result["logits_rel_err"] > family.LOGITS_REL_TOL
    assert result["gate_dead_abs_err"] > 10 * family.GATE_DEAD_ABS_TOL


def test_check_fails_a_head_at_zero():
    """The start's zero head on fresh parameters compares 0 with 0: the check
    says so instead of passing."""
    model = family.build(TOY, 1, 0)["model"]
    tokens = family.host_batches(TOY, 1, 0, n_batches=1)[0][0]
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    result = family.check(TOY, _state(model, params), 0)
    assert not result["logits_nonzero"] and not result["ok"]
    assert result["loss"] == pytest.approx(np.log(TOY["vocab_size"]), rel=1e-4)


WIDE = dict(
    TOY, hidden_size=256, moe_ffn_hidden_size=128, head_dim=32,
    train=dict(TOY["train"], seq_len=512), sliding_window_size=128,
)


@pytest.mark.parametrize("dtype, passes", [("bfloat16", True), ("float8_e4m3fn", False)])
def test_the_precision_below_fails_the_limits(monkeypatch, dtype, passes):
    """The stated precision (bfloat16 compute) passes ``check``; an 8-bit float
    under the same program, the nearest precision below, fails at least one of
    the limits with room (the 8-bit program hands its kernels bfloat16 operands
    and rotates in float32: no kernel and no promotion rule takes an 8-bit
    float)."""
    from bench_results.smallthinker_precision_below import rotating_in_float32
    from bench_results.solar_precision_below import in_bfloat16
    from edl_tpu.models import moe, transformer

    monkeypatch.setattr(transformer, "rope", rotating_in_float32())
    monkeypatch.setattr(moe, "grouped_matmul", in_bfloat16(moe.grouped_matmul))
    monkeypatch.setattr(transformer, "attention", in_bfloat16(transformer.attention))
    model = family.build(family.as_drawn(WIDE), 1, 0)["model"]
    tokens = family.host_batches(WIDE, 1, 0, n_batches=1)[0][0]
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    coarse = model.clone(dtype=getattr(jnp, dtype), remat=False)
    result = family.check(WIDE, _state(coarse, params), 0)
    readings = {
        name: result[name] / limit for name, limit in (
            ("logits_rel_err", family.LOGITS_REL_TOL),
            ("router_logits_rel_err", family.ROUTER_LOGITS_REL_TOL),
            ("flipped_share", family.ROUTE_FLIP_LIMIT),
        )
    }
    if passes:
        assert result["ok"], result
        assert all(r < 0.7 for r in readings.values()), readings
    else:
        assert not result["ok"]
        assert max(readings.values()) > 1.5, readings


# -- the readers --------------------------------------------------------------


def test_the_new_gauge_reader_reads_the_registry():
    module = reader("expert_gate_dead")
    run = types.SimpleNamespace(at_close={"registry": {}})
    assert module.read(run) is None                         # a program without the gauge
    run.at_close = {"registry": {"edl_train_moe_gate_dead": {"": 0.4987}}}
    assert module.read(run) == 0.4987
    entry = next(m for m in BENCH["per_layer"] if m["name"] == "expert_gate_dead")
    assert entry["workloads"] == [CELL]
    assert (module.NAME, module.UNIT, module.BETTER, module.SOURCE, module.LAYER, module.MOVES) == (
        entry["name"], entry["unit"], entry["better"], entry["source"], entry["layer"],
        entry["moves"])


def test_the_benchmark_gained_one_configuration_one_cell_and_the_cells_name_on_eleven_lists():
    """By name and not by place: a later PR appends its own after these."""
    assert [c["file"] for c in BENCH["configs"] if c["name"] == "smallthinker_21b_a3b"] == [
        "benchmark/configs/smallthinker_21b_a3b.json"
    ]
    cells = [w for w in BENCH["workloads"] if w["config"] == "smallthinker_21b_a3b"]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [(CELL, "steady", 1)]
    assert len(cells[0]["why"]) <= 200
    lists = [m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])]
    assert lists == [
        "attn_kernel_share", "attn_kernel_roofline", "expert_load_max", "expert_held_load_max",
        "step_plain_fallbacks", "step_kernel_calls", "step_loops", "step_unplaced_share",
        "step_time_drift", "expert_rows_held", "expert_gate_dead",
    ]


def test_the_timeline_file_lists_the_readers_for_the_familys_cell():
    extended = smallthinker_timeline.with_smallthinker(BENCH)
    by_name = {m["name"]: m for m in extended["per_layer"]}
    assert len(smallthinker_timeline.SHARED_READERS) == 9
    for name in smallthinker_timeline.SHARED_READERS:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["source"] == reader(name).SOURCE == "device_trace"
    assert "trinity_mini.steady" in by_name["attn_window_roofline"]["workloads"]
    listed = {m["name"] for m in BENCH["per_layer"]}
    assert not listed & set(smallthinker_timeline.SHARED_READERS)
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
        assert {"expert_gate_dead", "expert_rows_held", "expert_load_max",
                "expert_held_load_max", "step_unplaced_share"} <= set(detail["judged"])
        from benchmark import run as bench_run
        cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
        listed = {m["name"] for m in BENCH["per_layer"] if bench_run.applies(m, cell["name"])}
        assert listed - set(detail["judged"]) <= NEEDS_A_DEVICE
