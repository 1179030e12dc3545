"""The ``afmoe_lm`` family: its operation counts against arithmetic done by
hand, its reference against the program at toy widths (and what each limit of
``check`` is for), the configuration file against the published one, and its
readers on a hand-made trace."""

import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import afmoe_timeline
from benchmark.families import afmoe_lm
from benchmark.reference import afmoe_lm as reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


CONFIG = load("configs", "trinity_mini.json")
TOY = load("rehearsal", "configs", "trinity_mini.json")


def test_trinity_mini_by_hand():
    d, t, w, h, hd = 2048, 8192, 2048, 32, 128
    attention = 3 * d * h * hd + 2 * d * 4 * hd            # q, g, o; k, v
    assert attention == 27_262_976
    dense_layer = attention + 3 * d * 6144
    assert dense_layer == 65_011_712
    # router at its published width, the shared expert, and 8 x 16 / 128 = 1
    # routed expert a token, expected
    expert_layer = attention + d * 128 + 3 * d * 1024 + 1 * 3 * d * 1024
    assert expert_layer == 40_108_032
    head = d * 25_024
    assert afmoe_lm.routed_experts_a_token(CONFIG) == 1.0
    assert afmoe_lm.matmul_params(CONFIG) == dense_layer + 4 * expert_layer + head
    full = 2 * h * t * t * hd                              # 4 * H * (T^2 / 2) * D
    windowed = 2 * h * (2 * t * w - w * w) * hd
    assert afmoe_lm.layer_attention_forward_flops(CONFIG, 1, False) == full
    assert afmoe_lm.layer_attention_forward_flops(CONFIG, 1, True) == windowed
    assert windowed / full == pytest.approx(0.4375)
    assert afmoe_lm.attention_forward_flops(CONFIG, 3) == 3 * (full + 4 * windowed)
    want = 6 * (dense_layer + 4 * expert_layer + head) + 3 * (full + 4 * windowed) / t
    assert afmoe_lm.flops_per_item(CONFIG) == pytest.approx(want)
    assert afmoe_lm.flops_per_item(CONFIG) == pytest.approx(2.214e9, rel=0.001)
    # a window as long as the sequence is the causal layer
    assert afmoe_lm.layer_attention_forward_flops(
        dict(CONFIG, sliding_window=t), 1, True
    ) == full
    # the kernels' own work: forward and 2.5 times it backward, by kind
    assert afmoe_lm.kind_kernel_flops(CONFIG, 2, True) == 3.5 * 2 * 4 * windowed
    assert afmoe_lm.kind_kernel_flops(CONFIG, 2, False) == 3.5 * 2 * full
    assert afmoe_lm.kernel_flops(CONFIG, 2) == (
        afmoe_lm.kind_kernel_flops(CONFIG, 2, True) + afmoe_lm.kind_kernel_flops(CONFIG, 2, False)
    )
    wide, narrow = t * h * hd * 2, t * 4 * hd * 2
    assert afmoe_lm.kind_kernel_bytes(CONFIG, 1, False) == 9 * wide + 6 * narrow
    assert afmoe_lm.kind_kernel_bytes(CONFIG, 1, True) == 4 * (9 * wide + 6 * narrow)
    # compute-bound by far: operations a byte against the v5e's 240
    assert afmoe_lm.kind_kernel_flops(CONFIG, 1, True) / afmoe_lm.kind_kernel_bytes(CONFIG, 1, True) > 1000
    # the held experts' grouped matmuls: 8192 rows a layer, nine of them
    assert afmoe_lm.moe_kernel_flops(CONFIG, t) == 6 * 3 * t * d * 1024 * 4
    assert afmoe_lm.moe_kernel_bytes(CONFIG, t) == 9 * (
        t * d * 2 + t * 1024 * 2 + 16 * d * 1024 * 2
    ) * 4


def test_the_configuration_keeps_every_published_width():
    catalog = None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Trinity-Mini":
                catalog = row["config"]
    entry = next(c for c in load("..", "BENCHMARK.json")["configs"] if c["name"] == "trinity_mini")
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "num_dense_layers", "layer_types",
                       "num_experts", "vocab_size"}
    for key, value in catalog.items():
        if key in reduced:
            assert CONFIG["published"][key] == value and CONFIG[key] != value
        else:
            assert CONFIG[key] == value, key
    # the cut: layers 1-5 of the published pattern, one dense layer and a
    # whole period of four expert layers at 3 : 1
    assert CONFIG["layer_types"] == catalog["layer_types"][1:6]
    assert CONFIG["layer_types"].count("full_attention") == 1
    assert len(CONFIG["layer_types"]) == CONFIG["num_hidden_layers"] == 5
    share = CONFIG["share"]
    assert share["router_experts"] == catalog["num_experts"] == 128
    assert CONFIG["num_experts"] * share["chips_a_layer"] == 128
    assert CONFIG["vocab_size"] * share["chips_a_layer"] == catalog["vocab_size"]


@pytest.fixture(scope="module")
def toy_state():
    job = afmoe_lm.build(TOY, 1, 0)
    model = job["model"]
    tokens = afmoe_lm.host_batches(TOY, 1, 0, n_batches=1)[0][0]
    variables = model.init(jax.random.PRNGKey(0), tokens)
    # scales off 1 and a bias off 0, so that a misplaced one shows
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 200))
    params = jax.tree.map(
        lambda a: a * (1 + 0.2 * jax.random.normal(next(keys), a.shape)) if a.ndim == 1 else a,
        variables["params"],
    )
    def some_bias(a):  # as the rule leaves it: its mean at zero
        b = 0.02 * jax.random.normal(next(keys), a.shape)
        return b - jnp.mean(b)

    stats = jax.tree.map(some_bias, variables["batch_stats"])
    return model, params, stats, tokens


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
    result = afmoe_lm.check(TOY, _state(model, params, stats), 0)
    assert result["ok"], result
    assert result["rows_dropped"] == 0 and result["tokens_misrouted"] == 0
    assert result["router_arithmetic_rel_err"] <= 1e-6
    # the precision below the stated one fails its limit by a wide margin
    assert result["router_arithmetic_rel_err_of_a_bfloat16_router"] > (
        10 * afmoe_lm.ROUTER_ARITHMETIC_REL_TOL
    )
    assert result["kernel"]["window"]["window"] == TOY["sliding_window"]
    assert result["kernel"]["full"]["window"] is None


def _without(tree, *path):
    """``tree`` with the leaf at ``path`` replaced by a neutral value."""
    tree = jax.tree.map(lambda a: a, tree)
    node = tree
    for key in path[:-1]:
        node[key] = dict(node[key])
        node = node[key]
    node[path[-1]] = jnp.zeros_like(node[path[-1]])
    return tree


@pytest.mark.parametrize("fault", [
    "a_dropped_gate", "a_dropped_bias", "a_bias_not_moved", "a_bfloat16_router",
    "a_dropped_shared_expert",
])
def test_check_fails_a_program_that_leaves_part_of_the_model_out(toy_state, fault):
    """Each mechanism's absence fails one of the check's limits: the program
    is given other parameters than the reference (a gate projection of zeros is
    sigmoid = 1/2 everywhere, a dropped gate up to the factor; a bias of zeros;
    a shared expert of zeros), a bias it does not move, or a router rounded to
    bfloat16."""
    model, params, stats, _ = toy_state
    params = jax.tree.map(lambda a: a, dict(params))

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

    zero = lambda *path: (lambda tree: _without(tree, *path))  # noqa: E731
    apply_fn, failed_by = {
        "a_dropped_gate": (
            apply_with(zero("layer_2", "attn", "g", "kernel")), "logits_rel_err"),
        "a_dropped_bias": (
            apply_with(change_stats=zero("layer_1", "moe", "router_bias")),
            "tokens_misrouted"),
        "a_bias_not_moved": (apply_with(freeze_bias=True), "bias_abs_err"),
        "a_bfloat16_router": (apply_with(coarse_router=True), "router_arithmetic_rel_err"),
        "a_dropped_shared_expert": (
            apply_with(zero("layer_3", "moe", "shared", "down", "kernel")),
            "logits_rel_err"),
    }[fault]
    result = afmoe_lm.check(TOY, _state(model, params, stats, apply_fn), 0)
    assert not result["ok"]
    limit = {"logits_rel_err": afmoe_lm.LOGITS_REL_TOL, "tokens_misrouted": 0,
             "bias_abs_err": afmoe_lm.BIAS_ABS_TOL,
             "router_arithmetic_rel_err": afmoe_lm.ROUTER_ARITHMETIC_REL_TOL}[failed_by]
    assert result[failed_by] > limit, (failed_by, result[failed_by])


@pytest.mark.parametrize("off_by", [-1, 1])
def test_the_membership_count_fails_a_window_off_by_one(monkeypatch, off_by):
    """The exact count of visible keys, which the check runs on the kernels at
    the step's shape: a window one key longer or shorter than the stated one
    moves one residue of every full row by 1/16 of its value here too (head_dim
    16, window 32: two keys a residue), far over the limit; the right window
    reads the output's own rounding."""
    import importlib

    attention = importlib.import_module("edl_tpu.ops.attention")
    real = attention.attention_reference
    shape = (1, 4, 2, 128, 16)
    good = afmoe_lm.kernel_membership(*shape, 32)
    assert good["max_rel_err"] <= afmoe_lm.MEMBERSHIP_REL_TOL / 2
    assert afmoe_lm.kernel_membership(*shape, None)["max_rel_err"] <= (
        afmoe_lm.MEMBERSHIP_REL_TOL / 2
    )
    monkeypatch.setattr(
        attention, "attention_reference",
        lambda q, k, v, causal=False, scale=None, window=None: real(
            q, k, v, causal=causal, scale=scale, window=window + off_by
        ),
    )
    wrong = afmoe_lm.kernel_membership(*shape, 32)
    assert wrong["out"] > 2 * afmoe_lm.MEMBERSHIP_REL_TOL
    assert wrong["dv"] > 2 * afmoe_lm.MEMBERSHIP_REL_TOL


def test_the_references_attention_sees_exactly_the_window():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 12, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 12, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 12, 8))
    out = reference.masked_attention(q, k, v, window=3)
    # position 0 sees only itself: its output is v[0] of its own kv head
    np.testing.assert_allclose(out[0, 0, 0], v[0, 0, 0], rtol=1e-5)
    np.testing.assert_allclose(out[0, 3, 0], v[0, 1, 0], rtol=1e-5)
    # query 6 sees keys 4, 5, 6: key 3 moves nothing, key 4 does
    before = reference.masked_attention(q, k.at[:, :, 3].add(1.0), v, window=3)
    np.testing.assert_allclose(before[:, :, 6], out[:, :, 6], rtol=1e-5)
    moved = reference.masked_attention(q, k.at[:, :, 4].add(1.0), v, window=3)
    assert float(jnp.max(jnp.abs(moved[:, :, 6] - out[:, :, 6]))) > 1e-3
    # no window: causal over everything; a later key moves no earlier output
    full = reference.masked_attention(q, k, v)
    later = reference.masked_attention(q, k.at[:, :, 9].add(1.0), v)
    np.testing.assert_allclose(later[:, :, :9], full[:, :, :9], rtol=1e-5)
    assert float(jnp.max(jnp.abs(full[:, :, 11] - out[:, :, 11]))) > 1e-3


def test_the_references_rule_moves_the_bias_against_the_load():
    config = {"load_balance_coeff": 0.001}
    counts = jnp.asarray([10, 2, 6, 6])
    after = reference.bias_update(config, jnp.zeros(4), counts)
    # delta = (-1, +1, 0, 0) * 0.001, its mean 0
    np.testing.assert_allclose(after, [-0.001, 0.001, 0.0, 0.0], atol=1e-9)
    after = reference.bias_update(config, jnp.zeros(4), jnp.asarray([9, 1, 1, 1]))
    # delta = (-1, +1, +1, +1) * 0.001 less its mean 0.0005
    np.testing.assert_allclose(after, [-0.0015, 0.0005, 0.0005, 0.0005], atol=1e-9)


# -- the readers ---------------------------------------------------------------

def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


KERNEL = "%%%s = bf16[32,8192,128] custom-call(%%a), custom_call_target=\"tpu_custom_call\""
TRACE = {
    "steps": 2,
    "op_seconds": {"attn_window.1": 0.080, "attn_window.2": 0.040, "fusion.7": 0.004,
                   "attn_full.1": 0.050, "fusion.8": 0.010, "fusion.9": 0.006,
                   "fusion.10": 0.123},
    "op_text": {"attn_window.1": KERNEL % "attn_window.1",
                "attn_window.2": KERNEL % "attn_window.2",
                "fusion.7": "%fusion.7 = f32[32,8192] fusion(%b)",
                "attn_full.1": KERNEL % "attn_full.1",
                "fusion.8": "%fusion.8 = bf16[8192,4096] fusion(%c)",
                "fusion.9": "%fusion.9 = bf16[8192,2048] fusion(%d)",
                "fusion.10": "%fusion.10 = bf16[8192,2048] fusion(%e)"},
}
TABLE = {"attn_window.1": "attn_window", "attn_window.2": "attn_window",
         "fusion.7": "attn_window", "attn_full.1": "attn_full",
         "fusion.8": "attn_gate", "fusion.9": "moe_shared"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def make_run(trace=TRACE, family=afmoe_lm, config=CONFIG):
    return types.SimpleNamespace(
        trace=trace, family=family, config=config, peaks=PEAKS, chips=1,
        items_per_step=8192,
    )


@pytest.mark.parametrize("name,want", [
    ("attn_window_ms", 62.0),   # the kernels and the row sums of their backward
    ("attn_full_ms", 25.0), ("attn_gate_ms", 5.0), ("moe_shared_ms", 3.0),
    # the custom calls alone against the family's counts over 2 traced steps
    ("attn_window_roofline", 100 * afmoe_lm.kind_kernel_flops(CONFIG, 2, True) / 197e12 / 0.120),
    ("attn_full_roofline", 100 * afmoe_lm.kind_kernel_flops(CONFIG, 2, False) / 197e12 / 0.050),
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


def test_the_rooflines_find_nothing_in_another_family(monkeypatch):
    from benchmark.families import transformer_lm
    from edl_tpu.obs import profile

    monkeypatch.setattr(profile, "step_scopes", lambda scopes: dict(TABLE))
    other = make_run(family=transformer_lm)
    assert reader("attn_window_roofline").read(other) is None
    assert reader("attn_full_roofline").read(other) is None


def test_the_held_load_reader_reads_the_gauge():
    read = reader("expert_held_load_max").read
    run = types.SimpleNamespace(
        at_close={"registry": {"edl_train_moe_held_load_max": {"": 1.25}}}
    )
    assert read(run) == 1.25
    assert read(types.SimpleNamespace(at_close={"registry": {}})) is None


def test_the_timeline_file_lists_the_readers_for_the_familys_cells():
    bench = load("..", "BENCHMARK.json")
    listed = {m["name"] for m in bench["per_layer"]}
    assert not set(afmoe_timeline.DEVICE_READERS) & listed
    extended = afmoe_timeline.with_afmoe(bench)
    by_name = {m["name"]: m for m in extended["per_layer"]}
    for name in afmoe_timeline.DEVICE_READERS:
        assert by_name[name]["workloads"] == ["trinity_mini.steady"]
    for name in ("moe_share", "moe_kernel_roofline", "moe_route_ms",
                 "moe_experts_ms", "moe_combine_ms"):
        assert by_name[name]["workloads"] == ["olmoe_1b_7b.steady", "trinity_mini.steady"]
    assert by_name["ssm_share"]["workloads"] == ["granite_4_0_h_micro.steady"]
    assert afmoe_timeline.with_afmoe(extended) == extended
    # everything the accepted file lists is there, unchanged and first
    assert extended["per_layer"][:len(bench["per_layer"])] == bench["per_layer"]
