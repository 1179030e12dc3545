"""The ``ssm_lm`` family: its plain reference against the program at toy
widths on the CPU, its ``check`` passing on the program and failing on a wrong
one (a bfloat16 running sum of the decay, a bfloat16 carried state), its
operation and byte counts against arithmetic done by hand, and the mixer's
readers on a hand-made table of device operations."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import ssm_timeline
from benchmark.families import ssm_lm
from benchmark.layer_metrics import (
    ssm_conv_ms,
    ssm_gate_ms,
    ssm_proj_ms,
    ssm_scan_ms,
    ssm_scan_roofline,
    ssm_share,
)
from benchmark.reference import ssm_lm as reference
from edl_tpu.obs import profile as obs_profile
from edl_tpu.ops import ssd as ssd_module

HERE = os.path.dirname(os.path.dirname(__file__))


def load(*parts, **overrides):
    with open(os.path.join(HERE, *parts)) as f:
        return dict(json.load(f), **overrides)


def toy(**overrides):
    return load("rehearsal", "configs", "granite_4_0_h_micro.json", **overrides)


PATTERNS = [["mamba", "mamba", "attention"], ["attention", "mamba"], ["mamba"]]


@pytest.mark.parametrize("pattern", PATTERNS, ids=["-".join(p) for p in PATTERNS])
def test_reference_agrees_with_the_program_in_float32(pattern):
    config = toy(layer_types=pattern, num_hidden_layers=len(pattern))
    job = ssm_lm.build(config, 2, 0)
    model = job["model"].clone(dtype=jnp.float32, remat=False)
    tokens, targets = ssm_lm.host_batches(config, 2, 0, n_batches=1)[0]
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    got = model.apply({"params": params}, tokens)
    with jax.default_matmul_precision("highest"):
        want = reference.forward(config, params, tokens)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    head, _ = job["loss"](got, targets)
    np.testing.assert_allclose(head, reference.loss(want, targets), rtol=1e-5)


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        source = f.read()
    assert "edl_tpu" not in source.split('"""', 2)[2]        # after the docstring
    assert "lax.scan" in source                               # one step a token


class State:
    def __init__(self, config):
        job = ssm_lm.build(config, 1, 0)
        tokens, _ = ssm_lm.host_batches(config, 1, 0, n_batches=1)[0]
        self.model = job["model"]
        self.params = self.model.init(jax.random.PRNGKey(0), tokens)["params"]
        self.apply_fn = self.model.apply


def test_check_passes_on_the_program_and_names_what_it_compared():
    config = toy()
    result = ssm_lm.check(config, State(config), 0)
    assert result["ok"], result
    assert result["logits_rel_err"] <= ssm_lm.LOGITS_REL_TOL
    assert result["mixer"]["rel_err"] <= ssm_lm.MIXER_REL_TOL
    assert set(result["scan"]) >= {"y", "d_x", "d_dt", "d_a", "d_b", "d_c", "d_d",
                                   "state_rms_err"}
    assert result["scan"]["state_rms_err"] <= ssm_lm.STATE_RMS_TOL
    assert result["kernel"]["shape"] == [1, 4, 2, 128, 16]
    assert result["kernel"]["scale"] == config["attention_multiplier"]


@pytest.mark.parametrize("fault", ["logits", "unscaled_scores", "rotated"])
def test_check_fails_on_a_wrong_model(fault):
    config = toy()
    state = State(config)
    model = state.model
    if fault == "logits":
        state.apply_fn = lambda v, t: model.apply(v, t) * 1.2
    elif fault == "unscaled_scores":   # scores at head_dim ** -0.5, not 1/16
        arch = model.arch.__class__(**dict(model.arch.__dict__, attn_scale=None))
        state.apply_fn = model.clone(arch=arch).apply
    else:                              # a rotation the published model lacks
        arch = model.arch.__class__(**dict(model.arch.__dict__, rope=True))
        state.apply_fn = model.clone(arch=arch).apply
    # widen what the attention layer adds to the stream, so that the toy's one
    # attention layer shows in its logits as the published model's four do
    boost = lambda k: k * 8.0  # noqa: E731
    state.params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: boost(leaf) if "attn" in jax.tree_util.keystr(path) else leaf,
        state.params,
    )
    assert not ssm_lm.check(config, state, 0)["ok"]


class _Shim:
    """``base`` with some attributes replaced: a wrong ``jnp`` or ``jax`` for
    ``ops/ssd.py`` to compute with."""

    def __init__(self, base, **over):
        self._base, self._over = base, over

    def __getattr__(self, name):
        return self._over[name] if name in self._over else getattr(self._base, name)


def _bfloat16_running_sum(monkeypatch):
    rounded = lambda x, axis: jnp.cumsum(  # noqa: E731
        x.astype(jnp.bfloat16), axis=axis
    ).astype(jnp.float32)
    monkeypatch.setattr(ssd_module, "jnp", _Shim(jnp, cumsum=rounded))


def _bfloat16_carried_state(monkeypatch):
    def scan(step, init, xs):
        def rounded(carry, x):
            carry, out = step(carry, x)
            return carry.astype(jnp.bfloat16).astype(jnp.float32), out
        return jax.lax.scan(rounded, init, xs)

    monkeypatch.setattr(ssd_module, "jax", _Shim(jax, lax=_Shim(jax.lax, scan=scan)))


# the toy widths at the published chunk and a sequence of four chunks: a
# chunk's running sum of the log-decay reaches hundreds, as at the real size
LONG = dict(mamba_chunk_size=256)


def test_a_bfloat16_running_sum_of_the_decay_fails_the_scans_and_the_mixers_check(
    monkeypatch,
):
    config = toy(**LONG)
    sound = ssm_lm.scan_vs_reference(config, 1, 1024)
    assert sound["max_rel_err"] <= ssm_lm.SCAN_REL_TOL / 2
    params = State(config).params["layer_0"]["mamba"]
    assert ssm_lm.mixer_vs_reference(config, params, 1, 1024)["rel_err"] <= (
        ssm_lm.MIXER_REL_TOL / 2
    )
    _bfloat16_running_sum(monkeypatch)
    assert ssm_lm.scan_vs_reference(config, 1, 1024)["max_rel_err"] > 2 * ssm_lm.SCAN_REL_TOL
    # (0.026 at these widths; 0.084..0.110 at the published ones)
    assert ssm_lm.mixer_vs_reference(config, params, 1, 1024)["rel_err"] > (
        ssm_lm.MIXER_REL_TOL
    )


def test_a_bfloat16_carried_state_fails_the_states_check(monkeypatch):
    config = toy()
    sound = ssm_lm.scan_vs_reference(config, 1, 1024)["state_rms_err"]
    assert sound <= ssm_lm.STATE_RMS_TOL / 2
    _bfloat16_carried_state(monkeypatch)
    wrong = ssm_lm.scan_vs_reference(config, 1, 1024)
    assert wrong["state_rms_err"] > 2 * ssm_lm.STATE_RMS_TOL
    # by its outputs alone it would pass: the reason the state is compared
    assert wrong["max_rel_err"] <= ssm_lm.SCAN_REL_TOL


def test_granite_by_hand():
    config = load("configs", "granite_4_0_h_micro.json")
    d, f, v, t = 2048, 8192, 100352, 8192
    in_proj = d * (4096 + (4096 + 2 * 128) + 64)             # [z | xBC | dt]
    out_proj = 4096 * d
    attention = 2 * d * 32 * 64 + 2 * d * 8 * 64             # q, o and k, v: GQA 32:8 x 64
    swiglu = 3 * d * f
    assert (in_proj, out_proj, attention, swiglu, d * v) == (
        17_432_576, 8_388_608, 10_485_760, 50_331_648, 205_520_896
    )
    assert config["layer_types"] == ["mamba"] * 5 + ["attention"]
    matmul = 5 * (in_proj + out_proj) + attention + 6 * swiglu + d * v
    assert ssm_lm.matmul_params(config) == matmul
    # the scan in its chunked form, chunk 256, a token a layer forward:
    # C B^T 256*128 (half of 2*256*128), (L o CB^T)(dt x) 256*4096, the chunk's
    # state 2*128*4096, the inherited state's output 2*128*4096
    scan = 256 * 128 + 256 * 4096 + 2 * 128 * 4096 + 2 * 128 * 4096
    assert scan == 3_178_496
    assert ssm_lm.scan_forward_flops_per_token(config) == scan
    causal = 3 * 2 * t * 32 * 64                             # forward x 3, half masked
    assert ssm_lm.flops_per_item(config) == pytest.approx(
        6 * matmul + causal + 3 * scan * 5
    )
    assert ssm_lm.flops_per_item(config) == pytest.approx(4.031e9, rel=0.001)
    # depth 5 (the published layers 1 to 5) is ISSUE 29's 3.56 GFLOP a token
    five = dict(config, num_hidden_layers=5, layer_types=config["layer_types"][1:])
    assert ssm_lm.flops_per_item(five) == pytest.approx(3.56e9, rel=0.003)
    # shares of the counted work at depth 6
    total = ssm_lm.flops_per_item(config)
    assert 6 * d * v / total == pytest.approx(0.306, abs=0.005)          # the tied head
    assert 6 * 6 * swiglu / total == pytest.approx(0.45, abs=0.005)
    assert 5 * (6 * (in_proj + out_proj) + 3 * scan) / total == pytest.approx(0.204, abs=0.005)
    assert 5 * 3 * scan / total == pytest.approx(0.012, abs=0.001)       # the scan itself
    # all forty layers: 3.19 B parameters held
    small = 4 * 4352 + 4352 + 3 * 64 + 4096                  # conv, A_log dt_bias D, norm
    held = (
        36 * (in_proj + out_proj + small + swiglu + 2 * d)
        + 4 * (attention + swiglu + 2 * d) + d * v + d
    )
    assert held == pytest.approx(3.19e9, rel=0.002)
    # what the file's depth holds: compile_for_v5e.py's 647,259,328
    assert (
        5 * (in_proj + out_proj + small + swiglu + 2 * d)
        + attention + swiglu + 2 * d + d * v + d
    ) == 647_259_328


def test_scan_work_by_hand():
    config = load("configs", "granite_4_0_h_micro.json")
    tokens = 8192
    assert ssm_lm.ssm_scan_flops(config, tokens) == 3 * 3_178_496 * tokens * 5
    assert ssm_lm.ssm_scan_flops(config, tokens) == pytest.approx(390.6e9, rel=0.001)
    inputs = 2 * 4096 + 4 * 64 + 2 * 2 * 128                 # x, dt, B and C: 8960 bytes
    token = (inputs + 2 * 4096) + (inputs + 2 * 4096 + inputs)   # forward, backward
    assert token == 43_264
    assert ssm_lm.ssm_scan_bytes(config, tokens) == token * tokens * 5
    # the two bounds nearly meet: 220 operations a byte against the v5e's 240
    assert ssm_lm.ssm_scan_flops(config, tokens) / ssm_lm.ssm_scan_bytes(
        config, tokens
    ) == pytest.approx(220.4, abs=0.1)
    # the flash kernels' work counts the one attention layer only
    assert ssm_lm.kernel_flops(config, 1) == 3.5 * 2 * 32 * 8192 * 8192 * 64


def test_the_file_keeps_every_published_number():
    """The catalog's ``config`` for granite-4.0-h-micro, key for key; only
    ``num_hidden_layers`` and ``layer_types`` differ, and ``published`` holds
    their values."""
    pattern = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    assert [i for i, k in enumerate(pattern) if k == "attention"] == [5, 15, 25, 35]
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 8192, "layer_types": pattern, "logits_scaling": 8,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352,
    }
    config = load("configs", "granite_4_0_h_micro.json")
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == {"num_hidden_layers", "layer_types"}
    assert config["published"] == {"num_hidden_layers": 40, "layer_types": pattern}
    assert config["layer_types"] == pattern[:config["num_hidden_layers"]]
    assert config["train"]["seq_len"] == 8192 and config["train"]["batch_per_chip"] == 1
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "granite_4_0_h_micro")
    assert sorted(entry["reduced"]) == sorted(differs)


# -- the readers, on a hand-made table ----------------------------------------

OPS = {  # instruction -> (scope, seconds over the traced steps)
    "fusion.1": ("ssm_scan", 0.30), "fusion.2": ("ssm_scan", 0.10),
    "fusion.3": ("ssm_conv", 0.04), "fusion.4": ("ssm_gate", 0.06),
    "convolution.5": ("ssm_proj", 0.20), "fusion.6": (None, 1.30),
}


def hand_run(monkeypatch, scopes=True, trace=True):
    table = {name: scope for name, (scope, _) in OPS.items() if scope}
    monkeypatch.setattr(
        obs_profile, "step_scopes", lambda wanted: dict(table) if scopes else {}
    )
    config = load("configs", "granite_4_0_h_micro.json")
    reduced = {
        "steps": 4, "step_busy_s_total": 2.0,
        "op_seconds": {name: s for name, (_, s) in OPS.items()},
    }
    return types.SimpleNamespace(
        trace=reduced if trace else None, family=ssm_lm, config=config, chips=1,
        items_per_step=8192,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    )


def test_readers_split_the_mixers_device_time_by_scope(monkeypatch):
    run = hand_run(monkeypatch)
    assert ssm_scan_ms.read(run) == pytest.approx(100.0)      # 0.40 s over 4 steps
    assert ssm_conv_ms.read(run) == pytest.approx(10.0)
    assert ssm_gate_ms.read(run) == pytest.approx(15.0)
    assert ssm_proj_ms.read(run) == pytest.approx(50.0)
    assert ssm_share.read(run) == pytest.approx(35.0)         # 0.70 of 2.0 s
    # least time of four steps' scans: bytes bound, 4 * 1.772 GB at 819 GB/s
    least = 4 * 43_264 * 8192 * 5 / 819e9
    assert least > 4 * 3 * 3_178_496 * 8192 * 5 / 197e12
    assert ssm_scan_roofline.read(run) == pytest.approx(100 * least / 0.40)
    assert ssm_scan_roofline.read(run) == pytest.approx(2.16, abs=0.01)


@pytest.mark.parametrize("missing", ["scopes", "trace"])
def test_readers_find_nothing_in_a_program_or_run_without_the_scopes(monkeypatch, missing):
    run = hand_run(monkeypatch, scopes=missing != "scopes", trace=missing != "trace")
    for reader in (ssm_share, ssm_scan_roofline, ssm_scan_ms, ssm_conv_ms,
                   ssm_gate_ms, ssm_proj_ms):
        assert reader.read(run) is None


def test_the_generated_benchmark_lists_the_six_readers_for_the_hybrids_cell():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    extended = ssm_timeline.with_ssm(bench)
    added = {m["name"]: m for m in extended["per_layer"][len(bench["per_layer"]):]}
    assert set(ssm_timeline.DEVICE_READERS) <= set(added)
    for name in ssm_timeline.DEVICE_READERS:
        assert added[name]["workloads"] == ["granite_4_0_h_micro.steady"]
        assert added[name]["layer"] == "Model + kernels"
        assert added[name]["moves"] == "throughput"
    assert added["ssm_scan_roofline"]["better"] == "higher"
    assert extended["per_layer"][:len(bench["per_layer"])] == bench["per_layer"]
