"""The ``block_diffusion_lm`` family: its operation counts and the mask's pair
count against arithmetic done by hand, the exact counts ``kernel_membership``
expects against a count over the mask, its reference against the program at toy
widths (and what each limit of ``check`` is for: the precision below, a program
whose noised rows read their own block's answers, whose mask is plain causal,
whose loss lacks its ``1 / t``), the configuration file against the published
one, its two readers, and the rehearsal of its cell."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import sdar_timeline
from benchmark.families import block_diffusion_lm as family
from benchmark.reference import block_diffusion_lm as reference
from benchmark.tests.test_mla_mtp_lm import reader
from benchmark.tests.test_rehearse import NEEDS_A_DEVICE, run_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "sdar_30b_a3b.json")
TOY = load("rehearsal", "configs", "sdar_30b_a3b.json")
BENCH = load("..", "BENCHMARK.json")
CELL = "sdar_30b_a3b.steady"

# the catalog row's config (/opt/skills/guides/model-configs/architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
DEPTH = CONFIG["num_hidden_layers"]


def test_sdar_by_hand():
    """A DATA token at L = 8192: its clean and its noised position each meet
    attention's four projections 2 x 2048 x 128 x (32 + 4), the router 2048 x
    128 and one expert of 3 x 2048 x 768 (8 choices x 16 held / 128) a layer; the
    noised one the head 2048 x 18,992; a head's visible pairs are L (L + B), so
    (L + B) a data token, 4 x 32 x 128 operations each."""
    layer = 18_874_368 + 262_144 + 4_718_592
    assert family.attention_params(CONFIG) == 18_874_368
    assert family.routed_experts_a_token(CONFIG) == 1.0
    assert family.layer_matmul_params(CONFIG) == layer == 23_855_104
    assert family.visible_pairs(CONFIG) == 8192 * 8196 == 67_141_632
    a_layer = 4 * 32 * 128 * 8196                            # attention forward, a data token
    assert family.attention_forward_flops(CONFIG, 1) / 8192 == DEPTH * a_layer
    assert family.flops_per_item(CONFIG) == (
        6 * (2 * DEPTH * layer + 38_895_616) + 3 * DEPTH * a_layer
    )
    at = lambda depth: family.flops_per_item(dict(CONFIG, num_hidden_layers=depth))  # noqa: E731
    assert at(5) == 3_678_928_896 and at(6) == 4_368_039_936
    # the issue's shares of a step's counted work at depth 6, the kernels at
    # their executed 3.5 forwards: 59 / 28 / 7 / 5 %
    step = 6 * (2 * 6 * layer + 38_895_616) + 3.5 * 6 * a_layer
    assert 3.5 * 6 * a_layer / step == pytest.approx(0.59, abs=0.005)
    assert 6 * 2 * 6 * 18_874_368 / step == pytest.approx(0.28, abs=0.005)
    assert 6 * 2 * 6 * 4_718_592 / step == pytest.approx(0.07, abs=0.005)
    assert 6 * 38_895_616 / step == pytest.approx(0.05, abs=0.005)
    assert step * 8192 == pytest.approx(39e12, rel=0.01)     # the issue's 39 TFLOP a step


def test_the_pair_count_is_the_masks():
    for length, block in ((8, 2), (16, 4), (24, 4), (12, 3)):
        t = np.arange(2 * length)
        seen = np.asarray(reference.visible(t[:, None], t[None, :], length, block))
        config = {"train": {"seq_len": length, "block_diffusion": {"block": block}}}
        assert family.visible_pairs(config) == seen.sum()
        # each half sees half of them
        assert seen[:length].sum() == seen[length:].sum()


def test_the_kernels_work_by_hand():
    a_layer = 4 * 32 * 128 * 8192 * 8196
    assert family.kernel_flops(CONFIG, 1) == 3.5 * DEPTH * a_layer
    wide, narrow = 16384 * 32 * 128 * 2, 16384 * 4 * 128 * 2
    assert family.kernel_bytes(CONFIG, 1) == DEPTH * (9 * wide + 6 * narrow)
    # compute-bound: operations a byte above the v5e's 240
    assert family.kernel_flops(CONFIG, 1) / family.kernel_bytes(CONFIG, 1) > 240
    rows = 2 * 8192 * 1.0                                   # 16,384: 1024 a held expert
    assert family.moe_kernel_flops(CONFIG, 8192) == 6 * 3 * rows * 2048 * 768 * DEPTH
    assert family.moe_kernel_bytes(CONFIG, 8192) == 9 * (
        rows * 2048 * 2 + rows * 768 * 2 + 16 * 2048 * 768 * 2
    ) * DEPTH


@pytest.mark.parametrize("length,block,d,group", [(16, 4, 8, 2), (24, 4, 16, 1), (12, 3, 8, 4)])
def test_the_membership_counts_are_a_count_over_the_mask(length, block, d, group):
    t = 2 * length
    pos = np.arange(t)
    seen = np.asarray(reference.visible(pos[:, None], pos[None, :], length, block), np.float64)
    one_hot = np.eye(d)[pos % d]
    visible = seen.sum(axis=1)
    want_out = seen @ one_hot / visible[:, None]
    want_dv = group * (seen.T @ (one_hot / visible[:, None]))
    out, dv = family.membership_counts(length, block, d, group)
    np.testing.assert_allclose(out, want_out, atol=1e-12)
    np.testing.assert_allclose(dv, want_dv, atol=1e-12)


def test_the_configuration_keeps_every_published_key_but_the_three_it_lists():
    entry = next(c for c in BENCH["configs"] if c["name"] == "sdar_30b_a3b")
    assert entry["reduced"] == REDUCED
    assert entry["source"] == "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r["name"] == "SDAR-30B-A3B-Chat"]
    assert row["config"] == PUBLISHED and row["source_url"] == entry["source"]
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value and type(CONFIG[key]) is type(value), key
    share = CONFIG["share"]
    assert DEPTH >= 4                                        # the guide's floors
    assert CONFIG["num_experts"] * share["chips_a_layer"] == 128 == share["router_experts"]
    assert CONFIG["num_experts"] >= 8
    assert CONFIG["vocab_size"] * share["chips_a_vocabulary"] == 151936
    for key in ("assumed", "departures", "not_run", "deployment", "published", "share", "plan"):
        assert CONFIG[key], key
    train = CONFIG["train"]
    assert (train["seq_len"], train["batch_per_chip"], train["distinct_batches"]) == (8192, 1, 256)
    assert train["block_diffusion"] == {"block": 4, "mask_id": 18991, "t_min": 0.001}
    assert train["load_balance_coef"] == 0.001 and "router_z_coef" not in train
    assert train["remat"] and train["remat_policy"] == "save_flash"
    assert train["start"] == {"embedding_rms": 1.0, "head_rms": 0.0}
    assert CONFIG["item"] == "token" and CONFIG["family"] == "block_diffusion_lm"


def test_the_parameters_are_the_issues_count():
    job = family.build(CONFIG, 1, 0)
    model = job["model"]
    assert job["sample_input"].shape == (1, 16384) and job["items_per_step"] == 8192
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 16384), jnp.int32)
    )["params"]
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))  # noqa: E731
    assert count(shapes["layer_0"]["attn"]) == 18_874_368 + 2 * 128     # and two head norms
    assert count(shapes["layer_0"]) == 94_638_336           # the issue's 94.64 M a layer
    assert count(shapes) == DEPTH * 94_638_336 + 2 * 38_895_616 + 2048
    assert count(shapes) == CONFIG["plan"]["chosen"]["parameters"]
    assert 6 * 94_638_336 + 2 * 38_895_616 + 2048 == 645_623_296        # the issue's 645.6 M
    assert shapes["layer_1"]["moe"]["gate"].shape == (16, 2048, 768)
    assert shapes["layer_1"]["moe"]["router"]["kernel"].shape == (2048, 128)
    assert set(shapes["layer_0"]) == {"ln1", "attn", "ln2", "moe"}
    assert set(shapes["layer_0"]["attn"]) == {"q", "k", "v", "o", "q_norm", "k_norm"}
    assert shapes["layer_0"]["attn"]["q_norm"]["scale"].shape == (128,)
    assert shapes["lm_head"]["kernel"].shape == (2048, 18992)
    spec = model.moe
    assert (spec.score_func, spec.activation, spec.gated, spec.shared_d_ff, spec.held) == (
        "softmax", "silu", True, 0, (0, 16)
    )
    assert spec.norm_topk_prob and spec.z_weight == 0.0
    assert spec.aux_weight == pytest.approx(0.001 / DEPTH)
    assert model.arch.block_diffusion.block == 4 and model.arch.block_diffusion.mask_id == 18991
    assert model.arch.rope_theta == 1e6 and model.qk_norm == "head"


def test_the_plan_fits_the_chip():
    plan = CONFIG["plan"]
    chosen = plan["chosen"]
    assert chosen["num_hidden_layers"] == DEPTH and chosen["seq_len"] == 8192
    by_depth = {t["num_hidden_layers"]: t for t in plan["tried"]}
    tried = by_depth[DEPTH]
    assert tried["total_gb"] + 1.0 <= plan["chip_gb"] == 15.75
    assert tried["left_gb"] == pytest.approx(plan["chip_gb"] - tried["total_gb"], abs=2e-3)
    assert tried["on_chip"]["ran"] and tried["on_chip"]["correct"]
    # above the driver's floor of a quarter of the chip, by the state alone
    assert tried["on_chip"]["memory_peak_bytes"] > 0.25 * tried["on_chip"]["bytes_limit"]
    # the ladder's order: every rung before the chosen one was tried and fell
    for depth in [d for d in (6, 5, 4) if d > DEPTH]:
        assert by_depth[depth]["left_gb"] < 1.0 or not by_depth[depth]["on_chip"]["ran"], depth


def test_the_toy_twin_has_every_mechanism():
    assert set(TOY) >= set(PUBLISHED)
    assert TOY["num_attention_heads"] == 8 * TOY["num_key_value_heads"]   # the group of eight
    assert TOY["num_experts"] < TOY["share"]["router_experts"]
    assert TOY["train"]["seq_len"] % TOY["train"]["block_diffusion"]["block"] == 0
    assert TOY["train"]["block_diffusion"]["mask_id"] == TOY["vocab_size"] - 1
    assert TOY["train"]["start"] == CONFIG["train"]["start"]
    assert TOY["train"]["optimizer"] == CONFIG["train"]["optimizer"]


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        source = f.read().split('"""', 2)[2]                # after the docstring
    assert "edl_tpu" not in source


def test_the_batches_are_noised_by_the_program_and_none_comes_twice():
    from edl_tpu.data.block_diffusion import noised_batch

    pool = family.host_batches(TOY, 2, 5, n_batches=3)
    length, bd = TOY["train"]["seq_len"], TOY["train"]["block_diffusion"]
    for i, (tokens, (labels, weights)) in enumerate(pool):
        assert tokens.shape == (2, 2 * length) and labels.shape == weights.shape == (2, length)
        assert np.array_equal(tokens[:, :length], labels) and labels.max() < bd["mask_id"]
        again = noised_batch(labels, 5, i, bd["block"], bd["mask_id"], bd["t_min"])
        assert np.array_equal(again[0], tokens) and np.array_equal(again[1][1], weights)
        assert ((tokens[:, length:] == bd["mask_id"]) == (weights > 0)).all()
    assert not np.array_equal(pool[0][0], pool[1][0])
    assert len(family.host_batches(TOY, 1, 0)) == TOY["train"]["distinct_batches"] == 256


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
    assert result["kernel"]["shape"] == [1, 8, 1, 128, 16]
    assert result["kernel"]["block_diffusion"] == [64, 4]
    assert result["forward_process"]["tokens_differ"] == 0
    assert result["grouped_matmul"]["groups"] == 4           # the held experts'
    assert 0.2 < result["loss_head_metrics"]["bd_masked_share"] < 0.8


FAULTS = ["reads_its_own_blocks_answers", "plain_causal", "no_one_over_t", "positions_run_on"]


@pytest.mark.parametrize("fault", FAULTS)
def test_check_fails_a_program_that_is_not_the_step(toy_state, fault, monkeypatch):
    """What ``check``'s limits are for: a program whose noised rows also read
    their own block's clean tokens, one under a plain causal mask, a loss head
    without its ``1 / t`` and positions that run on through the noised half
    each fail ``check``, by the logits' or the loss' limit and with room."""
    import importlib

    from edl_tpu.models import transformer
    from edl_tpu.train import step as train_step

    model, params = toy_state
    ops = importlib.import_module("edl_tpu.ops.attention")
    apply_fn = model.apply
    if fault == "reads_its_own_blocks_answers":
        def leaky(q, k, v, causal=True, block_diffusion=None, **more):
            length, block = block_diffusion
            t = jnp.arange(2 * length)
            i, j = t[:, None], t[None, :]
            seen = ops._sees(i, j, None, block_diffusion) | (
                (i >= length) & (j < length) & ((i - length) // block == j // block)
            )
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, q.shape[1] // k.shape[1], 1))
            probs = jax.nn.softmax(
                jnp.where(seen, scores.astype(jnp.float32) * q.shape[-1] ** -0.5, -1e30), -1
            )
            return jnp.einsum(
                "bhqk,bhkd->bhqd", probs.astype(v.dtype), jnp.repeat(v, q.shape[1] // v.shape[1], 1)
            )
        monkeypatch.setattr(transformer, "attention", leaky)
    elif fault == "plain_causal":
        monkeypatch.setattr(
            transformer, "attention",
            lambda q, k, v, causal=True, block_diffusion=None, **more: ops.attention(
                q, k, v, causal=True, **more
            ),
        )
    elif fault == "positions_run_on":
        def apply_fn(variables, tokens, **kwargs):
            run_on = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None, :], tokens.shape)
            return model.apply(variables, tokens, run_on, **kwargs)
    else:
        def flat(logits, y):
            labels, weights = y
            return train_step.make_block_diffusion_loss()(
                logits, (labels, (weights > 0).astype(jnp.float32))
            )
        monkeypatch.setattr(
            "edl_tpu.train.make_block_diffusion_loss", lambda: flat, raising=False
        )
    result = family.check(TOY, _state(model, params, apply_fn), 0)
    assert not result["ok"]
    if fault == "no_one_over_t":
        assert result["loss_rel_err"] > 5 * family.LOSS_REL_TOL
        assert result["logits_rel_err"] <= family.LOGITS_REL_TOL
    else:
        assert result["logits_rel_err"] > 2 * family.LOGITS_REL_TOL


def test_check_fails_a_head_at_zero():
    """The start's zero head on fresh parameters compares 0 with 0: the check
    says so instead of passing."""
    model = family.build(TOY, 1, 0)["model"]
    tokens = family.host_batches(TOY, 1, 0, n_batches=1)[0][0]
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    result = family.check(TOY, _state(model, params), 0)
    assert not result["logits_nonzero"] and not result["ok"]
    assert result["loss_head_metrics"]["bd_masked_ce"] == pytest.approx(
        np.log(TOY["vocab_size"]), rel=1e-4
    )


WIDE = dict(
    TOY, hidden_size=256, moe_intermediate_size=128, head_dim=32,
    train=dict(TOY["train"], seq_len=256),
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


def test_the_gauges_reader_reads_the_registry():
    module = reader("bd_masked_share")
    run = types.SimpleNamespace(at_close={"registry": {}})
    assert module.read(run) is None                         # a program without the loss head
    run.at_close = {"registry": {"edl_train_bd_masked_share": {"": 0.4987}}}
    assert module.read(run) == 0.4987


def test_the_walks_reader_reads_the_ring():
    module = reader("attn_walked_over_live")
    run = types.SimpleNamespace(tracer_events=[])
    assert module.read(run) is None                         # a program without the mask
    causal = {"name": "attn_tiles", "args": {"kernel": "flash2_fwd", "interior": 0.4, "edge": 0.1}}
    run.tracer_events = [causal]
    assert module.read(run) is None                         # another mask's kernels

    def tiles(**route):
        return [
            {"name": "attn_tiles", "args": dict(
                route, kernel=kernel, mask="block_diffusion", interior=0.21875, edge=edge,
                visible=0.25)}
            for kernel, edge in (("flash2_fwd", 0.09375), ("flash2_bwd", 0.15625))
        ]

    kernels = tiles(path="kernel")
    run.tracer_events = [causal] + kernels + kernels        # a second stage notes them again
    assert module.read(run) == pytest.approx((1.25 + 1.5) / 2)
    run.tracer_events = tiles(path="plain", why="backend")  # a CPU's rehearsal: the plan
    assert module.read(run) == pytest.approx((1.25 + 1.5) / 2)
    run.tracer_events = kernels[:1]                         # the backward took the reference
    assert module.read(run) is None
    run.tracer_events = kernels[:1] + tiles(path="plain", why="blocks")[1:]
    assert module.read(run) is None                         # and said so: no plan for a kernel


@pytest.mark.parametrize("name", ["attn_walked_over_live", "bd_masked_share"])
def test_a_readers_constants_are_its_entry(name):
    module = reader(name)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert (module.NAME, module.UNIT, module.BETTER, module.SOURCE, module.LAYER, module.MOVES) == (
        entry["name"], entry["unit"], entry["better"], entry["source"], entry["layer"],
        entry["moves"])


def test_the_benchmark_gained_one_configuration_one_cell_and_the_cells_name_on_twelve_lists():
    """By name and not by place: a later PR appends its own after these."""
    assert [c["file"] for c in BENCH["configs"] if c["name"] == "sdar_30b_a3b"] == [
        "benchmark/configs/sdar_30b_a3b.json"
    ]
    cells = [w for w in BENCH["workloads"] if w["config"] == "sdar_30b_a3b"]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [(CELL, "steady", 1)]
    assert len(cells[0]["why"]) <= 200
    lists = [m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])]
    assert lists == [
        "attn_kernel_share", "attn_kernel_roofline", "expert_load_max", "expert_held_load_max",
        "step_plain_fallbacks", "step_kernel_calls", "step_loops", "step_unplaced_share",
        "step_time_drift", "expert_rows_held", "attn_walked_over_live", "bd_masked_share",
    ]


def test_the_timeline_file_lists_the_readers_for_the_familys_cell():
    extended = sdar_timeline.with_sdar(BENCH)
    by_name = {m["name"]: m for m in extended["per_layer"]}
    assert len(sdar_timeline.SHARED_READERS) == 6
    for name in sdar_timeline.SHARED_READERS:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["source"] == reader(name).SOURCE == "device_trace"
    listed = {m["name"] for m in BENCH["per_layer"]}
    assert not listed & set(sdar_timeline.SHARED_READERS)
    assert sdar_timeline.SCOPES[:2] == ("attn_block_diffusion", "attn_gate")
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
        assert {"attn_walked_over_live", "bd_masked_share", "expert_rows_held", "expert_load_max",
                "expert_held_load_max", "step_unplaced_share"} <= set(detail["judged"])
        from benchmark import run as bench_run
        cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
        listed = {m["name"] for m in BENCH["per_layer"] if bench_run.applies(m, cell["name"])}
        assert listed - set(detail["judged"]) <= NEEDS_A_DEVICE
