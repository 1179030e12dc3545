"""The ``gdn_lm`` family: its plain reference against the program at toy widths
on the CPU, its ``check`` passing on the program and failing on a wrong one (a
beta without its factor 2, a dropped decay, a dropped L2 norm, a dropped SiLU,
the gate before the norm, a bfloat16 carried state), its operation and byte
counts against arithmetic done by hand, and the mixer's readers on a hand-made
table of device operations."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import gdn_timeline
from benchmark.families import gdn_lm
from benchmark.layer_metrics import (
    gdn_conv_ms,
    gdn_gate_ms,
    gdn_proj_ms,
    gdn_scan_ms,
    gdn_scan_roofline,
    gdn_share,
)
from benchmark.reference import gdn_lm as reference
from edl_tpu.models import gated_delta as mixer_module
from edl_tpu.obs import profile as obs_profile
from edl_tpu.ops import gated_delta as rule_module

HERE = os.path.dirname(os.path.dirname(__file__))


def load(*parts, **overrides):
    with open(os.path.join(HERE, *parts)) as f:
        return dict(json.load(f), **overrides)


def toy(**overrides):
    return load("rehearsal", "configs", "olmo_hybrid_7b.json", **overrides)


def test_the_toy_twin_has_every_mechanism():
    config = toy()
    assert set(config["layer_types"]) == {"linear_attention", "full_attention"}
    assert config["linear_key_head_dim"] != config["linear_value_head_dim"]
    assert config["train"]["seq_len"] // config["train"]["rule_chunk"] >= 3
    assert config["linear_allow_neg_eigval"] is True


PATTERNS = [
    ["linear_attention", "linear_attention", "full_attention"],
    ["full_attention", "linear_attention"], ["linear_attention"],
]


@pytest.mark.parametrize("pattern", PATTERNS, ids=["-".join(p) for p in PATTERNS])
def test_reference_agrees_with_the_program_in_float32(pattern):
    config = toy(layer_types=pattern, num_hidden_layers=len(pattern))
    job = gdn_lm.build(config, 2, 0)
    model = job["model"].clone(dtype=jnp.float32, remat=False)
    tokens, targets = gdn_lm.host_batches(config, 2, 0, n_batches=1)[0]
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    got = model.apply({"params": params}, tokens)
    with jax.default_matmul_precision("highest"):
        want = reference.forward(config, params, tokens)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-4)
    head, _ = job["loss"](got, targets)
    np.testing.assert_allclose(head, reference.loss(want, targets), rtol=1e-5)


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        source = f.read()
    assert "edl_tpu" not in source.split('"""', 2)[2]        # after the docstring
    assert "lax.scan" in source                               # one step a token
    assert "tril" not in source and "chunk" not in source.split('"""', 2)[2]


class State:
    def __init__(self, config):
        job = gdn_lm.build(config, 1, 0)
        tokens, _ = gdn_lm.host_batches(config, 1, 0, n_batches=1)[0]
        self.model = job["model"]
        self.params = self.model.init(jax.random.PRNGKey(0), tokens)["params"]
        self.apply_fn = self.model.apply


def test_check_passes_on_the_program_and_names_what_it_compared():
    config = toy()
    result = gdn_lm.check(config, State(config), 0)
    assert result["ok"], result
    assert result["logits_rel_err"] <= gdn_lm.LOGITS_REL_TOL
    rule = result["rule"]
    assert rule["shape"] == [[1, 128, 4, 16], [1, 128, 4, 16], [1, 128, 4, 32]]
    assert set(rule["inputs"]) == {"q", "k", "v", "g", "beta"}
    assert rule["inputs_rel_err"] <= gdn_lm.RULE_INPUTS_REL_TOL
    assert rule["rel_err"] <= gdn_lm.RULE_REL_TOL
    assert rule["exact_rel_err"] <= gdn_lm.EXACT_REL_TOL / 10
    assert rule["exact_state_rms_err"] <= gdn_lm.EXACT_STATE_RMS_TOL / 10
    assert result["kernel"]["shape"] == [1, 4, 4, 128, 32]
    assert result["kernel"]["scale"] == 32 ** -0.5


def rule_of_layer_0(config, state, **replaced):
    tokens, _ = gdn_lm.host_batches(config, 1, 0, n_batches=1)[0]
    x = jnp.asarray(state.params["embed"]["embedding"])[tokens].astype(jnp.bfloat16)
    return gdn_lm.rule_vs_reference(config, state.params["layer_0"]["gdn"], x, **replaced)


FAULTS = ["beta_without_its_factor", "no_decay", "no_l2_norm", "no_silu"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_mixer_fails_the_rules_inputs_check(fault, monkeypatch):
    """Each of these leaves the logits of a toy within their limit or near it
    (a norm follows every branch): what the layer hands its rule shows them."""
    config = toy()
    state = State(config)
    sound = rule_of_layer_0(config, state)
    assert sound["inputs_rel_err"] <= gdn_lm.RULE_INPUTS_REL_TOL / 2
    spec = gdn_lm.gated_delta_spec(config)
    if fault == "beta_without_its_factor":
        spec = spec.__class__(**dict(spec.__dict__, neg_eigval=False))
    elif fault == "no_decay":
        monkeypatch.setattr(mixer_module, "jax", _Shim(
            jax, nn=_Shim(jax.nn, softplus=lambda x: 0.0 * x)
        ))
    elif fault == "no_l2_norm":
        monkeypatch.setattr(mixer_module, "_unit", lambda m: m)
    else:
        conv = mixer_module.causal_conv_silu
        from edl_tpu.ops.causal_conv import causal_conv

        monkeypatch.setattr(
            mixer_module, "causal_conv_silu",
            lambda x, kernel, bias, offset=0: causal_conv(
                x[..., offset:offset + kernel.shape[1]], kernel, bias
            ).astype(x.dtype),
        )
        assert conv is not mixer_module.causal_conv_silu
    wrong = mixer_module.GatedDeltaMixer(spec, jnp.bfloat16, config["rms_norm_eps"]).apply
    result = rule_of_layer_0(config, state, mixer=wrong)
    assert result["inputs_rel_err"] > 2 * gdn_lm.RULE_INPUTS_REL_TOL, result


def test_the_gate_before_the_norm_fails_the_logits():
    config = toy()
    state = State(config)
    assert gdn_lm.check(config, state, 0)["ok"]
    # the wrong program: RMSNorm(o * silu(gate)) * w, Mamba-2's order, on the
    # same parameters
    state.apply_fn = lambda variables, tokens: reference_with_gate_first(
        config, variables["params"], tokens
    )
    assert not gdn_lm.check(config, state, 0)["ok"]


def reference_with_gate_first(config, params, tokens):
    """The reference's forward with one line changed: the output gate applied
    before the per-head norm (Mamba-2's order), on the same parameters."""
    real = reference.linear_attention_mixer

    def mixer(config, p, x):
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        q, k, v, g, beta, gate = reference.rule_inputs(config, p, x)
        o, _ = reference.recurrence(q, k, v, g, beta)
        o = reference._rms_norm(o * jax.nn.silu(gate), f32(p["norm"]), config["rms_norm_eps"])
        return o.reshape(o.shape[:2] + (-1,)) @ f32(p["out_proj"]["kernel"])

    reference.linear_attention_mixer = mixer
    try:
        return reference.forward(config, params, tokens)
    finally:
        reference.linear_attention_mixer = real


class _Shim:
    """``base`` with some attributes replaced: a wrong ``jax`` for a module of
    the program to compute with."""

    def __init__(self, base, **over):
        self._base, self._over = base, over

    def __getattr__(self, name):
        return self._over[name] if name in self._over else getattr(self._base, name)


def _bfloat16_carried_state(monkeypatch):
    def scan(step, init, xs):
        def rounded(carry, x):
            carry, out = step(carry, x)
            return carry.astype(jnp.bfloat16).astype(jnp.float32), out
        return jax.lax.scan(rounded, init, xs)

    monkeypatch.setattr(rule_module, "jax", _Shim(jax, lax=_Shim(jax.lax, scan=scan)))


def test_a_bfloat16_carried_state_fails_the_float32_runs_check(monkeypatch):
    config = toy()
    state = State(config)
    sound = rule_of_layer_0(config, state)
    assert sound["exact_state_rms_err"] <= gdn_lm.EXACT_STATE_RMS_TOL / 10
    _bfloat16_carried_state(monkeypatch)
    wrong = rule_of_layer_0(config, state)
    assert wrong["exact_state_rms_err"] > 2 * gdn_lm.EXACT_STATE_RMS_TOL
    # by the step's own bfloat16 run it would pass: the reason for the second
    assert wrong["state_rms_err"] <= gdn_lm.STATE_RMS_TOL
    assert wrong["rel_err"] <= gdn_lm.RULE_REL_TOL


def test_a_solve_at_the_default_matmul_precision_is_still_float32():
    """The doubling's products ask for ``Precision.HIGHEST`` themselves: the
    chip's default (one bfloat16 pass) around them changes nothing."""
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (2, 64, 64)), -1)
    with jax.default_matmul_precision("bfloat16"):
        low = rule_module.unit_lower_inverse(a)
    np.testing.assert_array_equal(low, rule_module.unit_lower_inverse(a))


# -- operation and byte counts by hand -------------------------------------------


def test_olmo_hybrid_by_hand():
    """The numbers of the cell's file: 15 of the 30 heads of both kinds of
    layer (one of two chips' share), the SwiGLU whole, an eighth of the
    vocabulary."""
    config = load("configs", "olmo_hybrid_7b.json")
    d, f, v, t = 3840, 11008, 12544, 8192
    in_proj = d * (1440 + 1440 + 2880 + 2880 + 15 + 15)      # [q | k | v | gate | b | a]
    out_proj = 2880 * d
    attention = 4 * d * 15 * 128                             # q, k, v, o: 15 heads of 128
    swiglu = 3 * d * f
    assert (in_proj, out_proj, attention, swiglu, d * v) == (
        33_292_800, 11_059_200, 29_491_200, 126_812_160, 48_168_960
    )
    assert config["layer_types"] == ["linear_attention"] * 3 + ["full_attention"]
    assert gdn_lm.head_dim(config) == 128                    # 3840 / the published 30
    assert gdn_lm.mixer_params(config) == in_proj + out_proj
    matmul = 3 * (in_proj + out_proj) + attention + 4 * swiglu + d * v
    assert gdn_lm.matmul_params(config) == matmul == 717_964_800
    # the rule at the source's chunk of 64, a head a token a layer, forward:
    # K K^T and Q K^T 64*96 each (half of 2*64*96), W = T K~ 64*96 and U = T V~
    # 64*192 (T triangular), (QK^T o decay) V_new 64*192; W S, Q S and K^T V_new
    # 2*96*192 each; the solve 64*64/3
    head = 64 * (3 * 96 + 2 * 192) + 6 * 96 * 192 + 64 * 64 / 3
    assert head == pytest.approx(154_965.3, abs=0.1)
    rule = 15 * head
    assert gdn_lm.rule_forward_flops_per_token(config) == pytest.approx(rule)
    causal = 3 * 2 * t * 15 * 128                            # forward x 3, half masked
    assert gdn_lm.flops_per_item(config) == pytest.approx(
        6 * matmul + causal + 3 * rule * 3
    )
    assert gdn_lm.flops_per_item(config) == pytest.approx(4.423e9, rel=0.001)
    # shares of the counted work
    total = gdn_lm.flops_per_item(config)
    assert 6 * d * v / total == pytest.approx(0.065, abs=0.002)              # the head
    assert 4 * 6 * swiglu / total == pytest.approx(0.688, abs=0.002)
    assert 3 * 6 * (in_proj + out_proj) / total == pytest.approx(0.180, abs=0.002)
    assert 3 * 3 * rule / total == pytest.approx(0.0047, abs=0.0005)         # the rule itself
    # the mixers' share of a linear layer's matmul work: 26% here, 41% in the model
    assert (in_proj + out_proj) / (in_proj + out_proj + swiglu) == pytest.approx(0.259, abs=0.001)
    assert 2 * (in_proj + out_proj) / (2 * (in_proj + out_proj) + swiglu) == pytest.approx(
        0.412, abs=0.001
    )
    # what the file's depth holds: compile_for_v5e.py's 766,241,946
    small = 4 * 5760 + 15 + 15 + 192                         # conv, A_log, dt_bias, norm
    linear = in_proj + out_proj + small + swiglu + 2 * d
    full = attention + 2 * 1920 + swiglu + 2 * d             # with the QK norm's scales
    assert 3 * linear + full + 2 * d * v + d == 766_241_946
    # all 30 heads, all 32 layers and the whole vocabulary: the catalog's 7 B
    whole = dict(config, **config["published"])
    assert gdn_lm.head_dim(whole) == 128
    assert gdn_lm.mixer_params(whole) == 2 * (in_proj + out_proj) == 88_704_000
    assert gdn_lm.matmul_params(dict(whole, vocab_size=v, num_hidden_layers=4,
                                     layer_types=config["layer_types"])) == 880_512_000
    linear30 = 2 * (in_proj + out_proj) + 4 * 11520 + 252 + swiglu + 2 * d
    full30 = 2 * attention + 2 * d + swiglu + 2 * d
    assert 24 * linear30 + 8 * full30 + 2 * d * 100352 + d == pytest.approx(7.43e9, rel=0.002)
    # one period of all 30 heads with this vocabulary: the 928.9 M that did not fit
    assert 3 * linear30 + full30 + 2 * d * v + d == 928_862_196


def test_rule_work_by_hand():
    config = load("configs", "olmo_hybrid_7b.json")
    tokens = 8192
    assert gdn_lm.gdn_scan_flops(config, tokens) == pytest.approx(
        3 * 15 * 154_965.33 * tokens * 3, rel=1e-6
    )
    assert gdn_lm.gdn_scan_flops(config, tokens) == pytest.approx(171.4e9, rel=0.001)
    inputs = 2 * (1440 + 1440 + 2880) + 2 * 4 * 15           # q, k, v bf16; g, beta f32
    token = (inputs + 2 * 2880) + (inputs + 2 * 2880 + inputs)   # forward, backward
    assert (inputs, token) == (11_640, 46_440)
    assert gdn_lm.gdn_scan_bytes(config, tokens) == token * tokens * 3
    # bound by HBM: 150 operations a byte against the v5e's 240
    assert gdn_lm.gdn_scan_flops(config, tokens) / gdn_lm.gdn_scan_bytes(
        config, tokens
    ) == pytest.approx(150.2, abs=0.1)
    # the flash kernels' work counts the one full layer only, at the 15 heads held
    assert gdn_lm.kernel_flops(config, 1) == 3.5 * 2 * 15 * 8192 * 8192 * 128


def test_the_file_keeps_every_published_number():
    """The catalog's ``config`` for Olmo-Hybrid-7B, key for key; only
    ``num_hidden_layers``, ``layer_types``, ``vocab_size`` and the four counts
    of heads differ (no width does), ``published`` holds their values, and the
    ``share`` block says what is held and what compiled plan forced it."""
    pattern = ["linear_attention", "linear_attention", "linear_attention",
               "full_attention"] * 8
    published = {
        "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
        "intermediate_size": 11008, "num_hidden_layers": 32,
        "num_attention_heads": 30, "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False, "layer_types": pattern,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None},
    }
    config = load("configs", "olmo_hybrid_7b.json")
    differs = {k for k, v in published.items() if config[k] != v}
    heads = {"num_attention_heads", "num_key_value_heads", "linear_num_key_heads",
             "linear_num_value_heads"}
    assert differs == {"num_hidden_layers", "layer_types", "vocab_size"} | heads
    assert config["published"] == dict(
        {"num_hidden_layers": 32, "layer_types": pattern, "vocab_size": 100352},
        **{key: 30 for key in heads},
    )
    assert all(config[key] == 15 for key in heads)
    share = config["share"]
    assert (share["chips_a_layer"], share["heads_first"], share["head_dim"]) == (2, 0, 128)
    assert "compile_for_v5e.py" in share["forced_by"] and "15.05 GB" in share["forced_by"]
    assert config["layer_types"] == pattern[:4] and config["num_hidden_layers"] == 4
    assert config["vocab_size"] * 8 == 100352
    assert config["train"]["seq_len"] == 8192 and config["train"]["batch_per_chip"] == 1
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "olmo_hybrid_7b")
    assert sorted(entry["reduced"]) == sorted(differs)
    cell = next(w for w in bench["workloads"] if w["name"] == "olmo_hybrid_7b.steady")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("olmo_hybrid_7b", "steady", 1)
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == ["resnet50_vd.dp4"]


# -- the readers, on a hand-made table ----------------------------------------

OPS = {  # instruction -> (scope, seconds over the traced steps)
    "fusion.1": ("gdn_scan", 0.24), "while.2": ("gdn_scan", 0.16),
    "causal_conv_fwd.3": ("gdn_conv", 0.04), "fusion.4": ("gdn_gate", 0.06),
    "convolution.5": ("gdn_proj", 0.20), "fusion.6": (None, 1.30),
}


def hand_run(monkeypatch, scopes=True, trace=True):
    table = {name: scope for name, (scope, _) in OPS.items() if scope}
    monkeypatch.setattr(
        obs_profile, "step_scopes", lambda wanted: dict(table) if scopes else {}
    )
    config = load("configs", "olmo_hybrid_7b.json")
    reduced = {
        "steps": 4, "step_busy_s_total": 2.0,
        "op_seconds": {name: s for name, (_, s) in OPS.items()},
    }
    return types.SimpleNamespace(
        trace=reduced if trace else None, family=gdn_lm, config=config, chips=1,
        items_per_step=8192,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    )


def test_readers_split_the_mixers_device_time_by_scope(monkeypatch):
    run = hand_run(monkeypatch)
    assert gdn_scan_ms.read(run) == pytest.approx(100.0)      # 0.40 s over 4 steps
    assert gdn_conv_ms.read(run) == pytest.approx(10.0)
    assert gdn_gate_ms.read(run) == pytest.approx(15.0)
    assert gdn_proj_ms.read(run) == pytest.approx(50.0)
    assert gdn_share.read(run) == pytest.approx(35.0)         # 0.70 of 2.0 s
    # least time of four steps' rules: bytes bound, 4 * 1.141 GB at 819 GB/s
    least = 4 * 46_440 * 8192 * 3 / 819e9
    assert least > 4 * gdn_lm.gdn_scan_flops(run.config, 8192) / 197e12
    assert gdn_scan_roofline.read(run) == pytest.approx(100 * least / 0.40)
    assert gdn_scan_roofline.read(run) == pytest.approx(1.39, abs=0.01)


@pytest.mark.parametrize("missing", ["scopes", "trace"])
def test_readers_find_nothing_in_a_program_or_run_without_the_scopes(monkeypatch, missing):
    run = hand_run(monkeypatch, scopes=missing != "scopes", trace=missing != "trace")
    for reader in (gdn_share, gdn_scan_roofline, gdn_scan_ms, gdn_conv_ms,
                   gdn_gate_ms, gdn_proj_ms):
        assert reader.read(run) is None


def test_the_roofline_reader_finds_nothing_in_another_family(monkeypatch):
    from benchmark.families import ssm_lm

    run = hand_run(monkeypatch)
    run.family = ssm_lm
    assert gdn_scan_roofline.read(run) is None


def test_the_generated_benchmark_lists_the_six_readers_for_the_hybrids_cell():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    extended = gdn_timeline.with_gdn(bench)
    added = {m["name"]: m for m in extended["per_layer"][len(bench["per_layer"]):]}
    assert set(gdn_timeline.DEVICE_READERS) <= set(added)
    for name in gdn_timeline.DEVICE_READERS:
        assert added[name]["workloads"] == ["olmo_hybrid_7b.steady"]
        assert added[name]["layer"] == "Model + kernels"
        assert added[name]["moves"] == "throughput"
    assert added["gdn_scan_roofline"]["better"] == "higher"
    assert added["ssm_scan_ms"]["workloads"] == ["granite_4_0_h_micro.steady"]
    assert added["attn_full_ms"]["workloads"] == ["trinity_mini.steady"]
    assert extended["per_layer"][:len(bench["per_layer"])] == bench["per_layer"]
    assert gdn_timeline.cells_of(bench, "ssm_lm") == ["granite_4_0_h_micro.steady"]
