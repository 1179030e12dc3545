"""The ``sparse_lm`` family: its counts against hand arithmetic at the published
widths, the configuration file against the catalog's row, the kernels'
comparison on the program as it is and on four wrong programs (each has to fail
one limit), the timeline's listing, and the readers on a hand-made run."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import dsa_timeline
from benchmark.families import sparse_lm as family
from benchmark.layer_metrics import dsa_tile_live

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "configs", "keye_vl_2_0_30b_a3b.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def test_the_files_numbers_are_the_published_ones_but_the_three_cuts():
    entry = next(c for c in BENCH["configs"] if c["name"] == "keye_vl_2_0_30b_a3b")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert CONFIG["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936
    }
    published = {
        "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4,
        "head_dim": 128, "moe_intermediate_size": 768, "num_experts_per_tok": 8,
        "intermediate_size": 6144, "num_local_experts": 128, "rope_theta": 10000000,
        "rms_norm_eps": 1e-06, "max_position_embeddings": 262144,
        "norm_topk_prob": True, "tie_word_embeddings": False,
    }
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
        "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048,
    }
    assert CONFIG["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert CONFIG["share"]["router_experts"] == 128 and CONFIG["num_experts"] == 16
    assert CONFIG["vocab_size"] * 8 == 151936
    assert CONFIG["train"]["seq_len"] == 16384 and CONFIG["train"]["batch_per_chip"] == 1


def test_parameters_a_layer_and_of_the_cut_by_hand():
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512
    indexer = 2048 * 16 * 64 + 2048 * 64 + 2048 * 16
    assert family.attention_params(CONFIG) == attention == 18_874_368
    assert family.indexer_params(CONFIG) == indexer == 2_260_992
    layer = attention + indexer + 2048 * 128 + 16 * 3 * 2048 * 768
    assert round(layer / 1e6, 2) == 96.89          # the norms' 4,480 bring the issue's 96.90
    model = family.build(CONFIG, 1, 0)["model"]
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32)
    )["params"]
    counted = sum(int(jnp.prod(jnp.asarray(a.shape))) for a in jax.tree.leaves(shapes))
    assert counted == CONFIG["plan"]["tried"][1]["parameters"] == 562_290_560


def test_pairs_and_flops_a_token_by_hand():
    t, k = 16384, 2048
    picked = sum(min(s + 1, k) for s in range(t))
    assert family.selected_pairs(CONFIG) == picked == 31_458_304
    assert family.causal_pairs(t) == 134_225_920
    assert round(picked / family.causal_pairs(t), 3) == 0.234
    # a token meets: attention's four, the indexer's three, the router, one expert's
    # three matrices (8 of 128 x 16 held = 1 expected), and the head over the slice
    params = 5 * (18_874_368 + 2_260_992 + 2048 * 128 + 1.0 * 3 * 2048 * 768) + 2048 * 18992
    assert family.matmul_params(CONFIG) == params
    a_layer = (
        3 * 4 * 128 * 32 * picked                       # q k and p v, forward and twice backward
        + 2 * 16 * 64 * (134_225_920 + 2 * picked)      # the scores: every causal pair, then two gradients
        + 2 * 128 * 32 * picked                         # the target's q k
    ) / t
    assert family.flops_per_item(CONFIG) == pytest.approx(6 * params + 5 * a_layer)
    assert family.flops_per_item(CONFIG) == pytest.approx(1.691e9, rel=1e-3)
    # the issue's arithmetic for its six layers: about 1.98 GFLOP a token
    assert family.flops_per_item(dict(CONFIG, num_hidden_layers=6)) == pytest.approx(1.982e9, rel=1e-3)


def test_the_kernels_counts_by_hand():
    picked, causal = 31_458_304, 134_225_920
    assert family.sparse_kernel_flops(CONFIG, 2) == 2 * 5 * 7 * 2 * 128 * 32 * picked
    q, kv = 16384 * 32 * 128 * 2, 16384 * 4 * 128 * 2
    assert family.sparse_kernel_bytes(CONFIG, 1) == 5 * (6 * q + 6 * kv)
    assert family.index_kernel_flops(CONFIG, 1) == 5 * 2 * 16 * 64 * (causal + 3 * picked)
    assert family.index_kernel_bytes(CONFIG, 1) == 5 * (4 * causal + 2 * picked)
    assert family.select_bytes(CONFIG, 3) == 3 * 5 * 4 * causal
    assert family.routed_experts_a_token(CONFIG) == 1.0
    assert family.moe_kernel_flops(CONFIG, 16384) == 6 * 3 * 16384 * 2048 * 768 * 5


# -- the kernels' comparison, and programs that have to fail it ---------------

SHAPE = dict(h=4, h_kv=2, t=256, d=32, j=4, di=16, topk=48)


def compare(op=None, seed=5, near=False):
    return family.sparse_kernels_vs_reference(seed, *SHAPE.values(), op=op, near=near)


@pytest.mark.parametrize("near", [False, True])
def test_the_program_passes_the_kernels_comparison(near):
    found = compare(near=near)
    assert found["ok"], found
    assert found["pairs_selected_wrongly"] == 0
    assert found["index_arithmetic"] <= family.INDEX_ARITHMETIC_REL_TOL / 10
    assert found["max_rel_err"] <= family.KERNEL_REL_TOL / 2
    assert found["index_grad_rel_err"] <= found["index_grad_rel_tol"] / 2
    assert found["index_grad_rel_tol"] == (
        family.INDEX_GRAD_NEAR_REL_TOL if near else family.INDEX_GRAD_REL_TOL
    )


def test_an_indexer_that_looks_near_leaves_tiles_empty_and_scores_tied():
    """What the second comparison is for: at a length of 2048 and the kernels'
    512 x 512 tiles its selection of 256 keys a row leaves most tiles under
    the diagonal empty, and its bfloat16 cosines tie scores by the hundred,
    where a fresh indexer's selection lives in every tile."""
    import importlib

    S = importlib.import_module("edl_tpu.ops.sparse_attention")
    live = {}
    for near in (False, True):
        def op(q, k, v, iq, ik, iw, topk):
            scores = S.index_scores_reference(iq[0], ik[0], iw[0])
            mask = S.select_reference(scores, topk)
            return (
                jnp.zeros_like(q), jnp.ones(()), S._stats(mask, 512, 512),
                jax.tree.map(lambda a: a[None], S._detail(mask, scores)),
            )

        found = family.sparse_kernels_vs_reference(5, 2, 1, 2048, 16, 4, 16, 256, op=op, near=near)
        live[near] = found["tile_live"]
        assert found["pairs_selected_wrongly"] == 0
    assert live[False] == 1.0 and live[True] <= 0.75, live


@jax.custom_vjp
def _cotangent_in_8_bits(x):
    return x


_cotangent_in_8_bits.defvjp(
    lambda x: (x, None),
    # the nearest precision below bfloat16 for what crosses HBM backward: an
    # 8-bit float under one scale a tensor, as a program that kept dI so would
    lambda _, g: ((
        (g * (448.0 / jnp.max(jnp.abs(g)))).astype(jnp.float8_e4m3fn).astype(g.dtype)
        * (jnp.max(jnp.abs(g)) / 448.0)
    ),),
)


def _wrong(kind):
    """``ops.sparse_attention`` with one thing wrong."""
    import importlib

    S = importlib.import_module("edl_tpu.ops.sparse_attention")

    def op(q, k, v, iq, ik, iw, topk):
        scale = q.shape[-1] ** -0.5
        scores = S.index_scores_reference(iq[0], ik[0], iw[0])
        if kind == "scores_kept_in_bfloat16":
            scores = scores.astype(jnp.bfloat16).astype(jnp.float32)
        keep = topk - 1 if kind == "a_selection_short_of_k" else topk
        mask = S.select_reference(scores, keep)
        if kind == "ties_to_the_higher_index":
            mask = S.select_reference(scores[:, ::-1], keep)[:, ::-1] & S.select_reference(
                jnp.zeros_like(scores), scores.shape[0]
            )
        out, probs = S._masked_attention_reference(q[0], k[0], v[0], mask, scale)
        kl = S.index_kl_reference(scores, mask, jnp.mean(probs, axis=0))
        if kind == "the_indexers_loss_left_out":
            kl = 0.0 * kl
        if kind == "the_indexers_gradient_in_8_bits":
            kl = S.index_kl_reference(_cotangent_in_8_bits(scores), mask, jnp.mean(probs, axis=0))
        return out[None], kl, S._stats(mask, 64, 64), jax.tree.map(
            lambda a: a[None], S._detail(mask, scores)
        )

    return op


@pytest.mark.parametrize("kind, fails", [
    ("scores_kept_in_bfloat16", "index_arithmetic"),
    ("a_selection_short_of_k", "pairs_selected_wrongly"),
    ("the_indexers_loss_left_out", "index_kl_rel_err"),
    ("the_indexers_gradient_in_8_bits", "index_grad_rel_err"),
    ("the_indexers_gradient_in_8_bits.near", "index_grad_rel_err"),
])
def test_a_wrong_program_fails_one_limit(kind, fails):
    kind, _, near = kind.partition(".")
    found = compare(_wrong(kind), near=bool(near))
    assert not found["ok"]
    limit = {"index_arithmetic": family.INDEX_ARITHMETIC_REL_TOL,
             "pairs_selected_wrongly": 0, "index_kl_rel_err": family.INDEX_KL_REL_TOL,
             "index_grad_rel_err": 2 * found["index_grad_rel_tol"]}[fails]
    assert found[fails] > limit, found


def test_the_wrong_programs_unchanged_form_passes():
    assert compare(_wrong("nothing"))["ok"]


# -- the listing and the readers ---------------------------------------------


def test_the_timeline_lists_its_readers_for_the_familys_cells_only():
    extended = dsa_timeline.with_dsa(BENCH)
    by_name = {m["name"]: m for m in extended["per_layer"]}
    for name in dsa_timeline.DEVICE_READERS:
        assert by_name[name]["workloads"] == ["keye_vl_2_0_30b_a3b.steady"]
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["moves"] == "throughput"
    assert "keye_vl_2_0_30b_a3b.steady" in by_name["moe_share"]["workloads"]
    listed = next(m for m in BENCH["per_layer"] if m["name"] == "dsa_tile_live")
    assert listed["workloads"] == ["keye_vl_2_0_30b_a3b.steady"]
    assert not any(m["name"] in dsa_timeline.DEVICE_READERS for m in BENCH["per_layer"])
    assert len(extended["per_layer"]) == len({m["name"] for m in extended["per_layer"]})


def _run(trace=None, registry=None):
    return types.SimpleNamespace(
        trace=trace, family=family, config=CONFIG, peaks={
            "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        },
        at_close={"registry": registry or {}}, items_per_step=16384, chips=1,
    )


def test_the_readers_return_nothing_where_there_is_nothing_to_read(monkeypatch):
    import importlib

    assert dsa_tile_live.read(_run()) is None
    assert dsa_tile_live.read(_run(registry={"edl_train_dsa_tile_live": {"": 0.75}})) == 0.75
    for name in dsa_timeline.DEVICE_READERS:
        reader = importlib.import_module("benchmark.layer_metrics." + name)
        assert reader.read(_run()) is None                      # no trace
        assert reader.NAME == name and reader.LAYER == "Model + kernels"
    # a program that enters none of the scopes (the parent): a trace, and still nothing
    from edl_tpu.obs import profile as obs_profile

    monkeypatch.setattr(obs_profile, "step_scopes", lambda scopes: {})
    trace = {"steps": 2, "op_seconds": {"fusion.1": 0.5}, "op_text": {"fusion.1": ""},
             "step_busy_s_total": 1.0}
    for name in dsa_timeline.DEVICE_READERS:
        reader = importlib.import_module("benchmark.layer_metrics." + name)
        assert reader.read(_run(trace)) is None


def test_the_readers_on_a_hand_made_trace(monkeypatch):
    import importlib

    from edl_tpu.obs import profile as obs_profile

    table = {"call.1": "attn_sparse", "call.2": "dsa_select", "fusion.3": "dsa_select",
             "call.4": "dsa_index", "fusion.5": "dsa_target"}
    monkeypatch.setattr(obs_profile, "step_scopes", lambda scopes: dict(table))
    kernel = " custom-call("
    trace = {
        "steps": 2, "step_busy_s_total": 4.0,
        "op_seconds": {"call.1": 1.0, "call.2": 0.2, "fusion.3": 0.1, "call.4": 0.4,
                       "fusion.5": 0.3, "fusion.9": 2.0},
        "op_text": {"call.1": kernel, "call.2": kernel, "fusion.3": "fusion(", "call.4": kernel,
                    "fusion.5": "fusion(", "fusion.9": "fusion("},
    }
    read = lambda name: importlib.import_module(  # noqa: E731
        "benchmark.layer_metrics." + name
    ).read(_run(trace))
    assert read("dsa_share") == pytest.approx(100 * 2.0 / 4.0)
    assert read("dsa_select_ms") == pytest.approx(1e3 * 0.3 / 2)
    assert read("attn_sparse_ms") == pytest.approx(500.0)
    assert read("dsa_target_ms") == pytest.approx(150.0)
    assert read("dsa_index_ms") == pytest.approx(200.0)
    assert read("attn_sparse_roofline") == pytest.approx(
        100 * family.sparse_kernel_flops(CONFIG, 2) / 197e12 / 1.0
    )
    assert read("dsa_select_roofline") == pytest.approx(
        100 * family.select_bytes(CONFIG, 2) / 819e9 / 0.2     # the kernel alone, not the fusion
    )
    assert read("dsa_index_roofline") == pytest.approx(
        100 * max(family.index_kernel_flops(CONFIG, 2) / 197e12,
                  family.index_kernel_bytes(CONFIG, 2) / 819e9) / 0.4
    )


# -- the precision below the stated one has to fail ----------------------------

with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs", "keye_vl_2_0_30b_a3b.json")) as f:
    TOY = json.load(f)
# the cell's five layers at a width of 256: 8 heads of 64 over 2 key heads, an
# indexer of 8 heads of 32 keeping 128 of up to 1024 keys, 8 of 32 experts held
WIDE = dict(
    TOY, hidden_size=256, head_dim=64, num_attention_heads=8, num_key_value_heads=2,
    moe_intermediate_size=128, num_hidden_layers=5, num_experts=8, num_local_experts=32,
    num_experts_per_tok=4, vocab_size=512,
    sa_config=dict(TOY["sa_config"], indexer_head_dim=32, indexer_num_heads=8, topk=128),
    share=dict(TOY["share"], chips_a_layer=4, chip=0, router_experts=32, experts_first=0),
    train=dict(TOY["train"], seq_len=1024, compute_dtype="bfloat16"),
)
STREAM_LIMITS = (
    ("logits_rel_err", "LOGITS_REL_TOL"), ("index_scores_rel_err", "INDEX_SCORES_REL_TOL"),
    ("router_logits_rel_err", "ROUTER_LOGITS_REL_TOL"),
    ("selection_widest_flip_by_layer", "SELECT_MARGIN_REL"),
    ("selection_flipped_share_by_layer", "SELECT_FLIP_LIMIT"),
    ("index_kl_rel_err_by_layer", "INDEX_KL_REL_TOL"),
    ("route_flipped_share_by_layer", "ROUTE_FLIP_LIMIT"),
)


@pytest.mark.parametrize("dtype, passes", [("bfloat16", True), ("float8_e4m3fn", False)])
def test_the_precision_below_fails_the_streams_limits(monkeypatch, dtype, passes):
    """The stated precision (bfloat16 compute) passes ``check`` with room under
    every limit of the stream; an 8-bit float under the same program, the
    nearest precision below, fails each of them, and still selects exactly
    ``min(topk, t + 1)`` keys a row and misroutes no token (a flip is the
    rounding's)."""
    import flax.linen as nn

    from edl_tpu.models import transformer

    rope, norm = transformer.rope, nn.LayerNorm

    def layer_norm(epsilon, dtype, name):  # jax promotes no 8-bit float: norm it as float32
        if jnp.dtype(dtype).itemsize != 1:
            return norm(epsilon=epsilon, dtype=dtype, name=name)
        inner = norm(epsilon=epsilon, dtype=jnp.float32, name=name)
        return lambda x: inner(x.astype(jnp.float32)).astype(dtype)

    monkeypatch.setattr(nn, "LayerNorm", layer_norm)
    monkeypatch.setattr(
        transformer, "rope",
        lambda x, positions, theta: rope(x.astype(jnp.float32), positions, theta).astype(x.dtype)
        if x.dtype.itemsize == 1 else rope(x, positions, theta),
    )
    model = family.build(WIDE, 1, 0)["model"]
    tokens = family.host_batches(WIDE, 1, 0, n_batches=1)[0][0]
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    coarse = model.clone(dtype=getattr(jnp, dtype), remat=False)
    result = family.check(
        WIDE, types.SimpleNamespace(params=params, batch_stats={}, apply_fn=coarse.apply), 0
    )
    assert result["tokens_misrouted"] == 0 and result["rows_short_of_their_keys"] == 0
    readings = {
        name: np.max(result[name]) / getattr(family, limit) for name, limit in STREAM_LIMITS
    }
    if passes:
        assert result["ok"], result
        assert all(r < 0.6 for r in readings.values()), readings
    else:
        assert not result["ok"]
        assert all(r > 2.0 for r in readings.values()), readings
