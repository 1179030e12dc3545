"""``run.py --rehearse`` end to end for every cell of ``BENCHMARK.json`` (the
four-chip one on four virtual CPU devices), the refusals, and the proof that
a fifth cell with a new configuration, family, mix and per-layer metric
needs new files and one new ``workloads`` entry only."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


# read from a device trace, the peaks table or a device's memory_stats()
NEEDS_A_DEVICE = {"mfu", "step_gap_ms", "step_device_ms", "attn_kernel_share",
                  "attn_kernel_roofline", "collective_exposed_share", "hbm_peak_gb"}


def run_cell(cell, chips, *extra, env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % chips
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3", "--seconds", "2", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, lines


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_every_cell(cell, trace):
    proc, lines = run_cell(cell["name"], cell["chips"], "--trace", str(trace),
                           "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(lines[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["metrics"] == {}                 # no device metric from a CPU
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == cell["chips"]
    detail = json.loads(lines[-2])["detail"]
    assert detail["checks"]["compiles_in_window"] == 0
    assert detail["checks"]["state_step"] == detail["checks"]["steps_dispatched"]
    assert detail["checks"]["reference"]["ok"]
    # what the readers found is what BENCHMARK.json lists for the cell, less
    # what only a device gives (a peak, a device trace)
    kind = "per_layer" if trace else "end_to_end"
    listed = {m["name"] for m in BENCH[kind] if bench_run.applies(m, cell["name"])}
    assert set(detail["judged"]) <= listed
    assert listed - set(detail["judged"]) <= NEEDS_A_DEVICE
    if cell["chips"] > 1:
        assert detail["checks"]["replicas_agree"]
    assert detail["step_s"] > 0


def test_without_a_tpu_there_is_no_result():
    proc, lines = run_cell("resnet50_vd.steady", 1, "--trace", "0")
    assert proc.returncode != 0
    assert not any(ln.startswith('{"correct"') for ln in lines)
    assert "not a TPU" in proc.stderr


def test_fewer_chips_than_the_cell_asks_for_is_refused():
    proc, lines = run_cell("resnet50_vd.dp4", 1, "--rehearse")
    assert proc.returncode != 0 and not lines
    assert "needs 4 chip(s)" in proc.stderr


FAMILY = '''
"""A throwaway family: a two-layer perceptron on vectors."""
import numpy as np


def build(config, global_batch, seed):
    import flax.linen as nn
    import optax
    from edl_tpu.train.step import mse_loss

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.relu(nn.Dense(config["hidden"])(x))
            return nn.Dense(config["out"])(h)

    return {"model": Net(), "optimizer": optax.sgd(0.01), "loss": mse_loss,
            "sample_input": np.zeros((global_batch, config["in"]), np.float32),
            "apply_kwargs": None, "items_per_step": global_batch}


def host_batches(config, global_batch, seed, n_batches=2):
    rs = np.random.default_rng(seed)
    return [(rs.standard_normal((global_batch, config["in"]), dtype=np.float32),
             rs.standard_normal((global_batch, config["out"]), dtype=np.float32))
            for _ in range(n_batches)]


def flops_per_item(config):
    return 6.0 * (config["in"] * config["hidden"] + config["hidden"] * config["out"])


def check(config, state, seed):
    import jax.numpy as jnp
    x = host_batches(config, 4, seed, 1)[0][0]
    p = state.params
    want = jnp.maximum(x @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"], 0)
    want = want @ p["Dense_1"]["kernel"] + p["Dense_1"]["bias"]
    got = state.apply_fn({"params": p}, x)
    return {"ok": bool(jnp.allclose(got, want, atol=1e-4))}
'''

METRIC = '''
"""A throwaway per-layer metric: steps in the window."""
NAME = "toy_steps"
UNIT = "count"
LAYER = "Step loop"
MOVES = "throughput"
SOURCE = "host_clock"


def read(run):
    return float(run.window_steps)
'''


@pytest.fixture
def extended(tmp_path):
    """A copy of BENCHMARK.json that lists one more directory, which holds
    nothing but new files; the accepted directory is linked, not edited."""
    os.symlink(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    extra = tmp_path / "extra"
    for sub in ("configs", "rehearsal/configs", "families", "traffic", "layer_metrics"):
        (extra / sub).mkdir(parents=True)
    config = {"family": "toy_mlp", "item": "vector", "in": 16, "hidden": 32,
              "out": 4, "train": {"batch_per_chip": 8}}
    (extra / "configs" / "toy.json").write_text(json.dumps(config))
    (extra / "rehearsal" / "configs" / "toy.json").write_text(json.dumps(config))
    (extra / "families" / "toy_mlp.py").write_text(FAMILY)
    (extra / "layer_metrics" / "toy_steps.py").write_text(METRIC)
    (extra / "traffic" / "short.json").write_text(json.dumps({
        "warmup_steps": 2, "trace_steps": 2, "steps_per_epoch": 20, "ckpt": True,
        "async_save": False, "mesh_axes": None, "fsdp": False,
    }))
    bench = json.loads(json.dumps(BENCH))
    bench["paths"].append("extra")
    bench["configs"].append({"name": "toy", "source": "none", "reduced": [],
                             "file": "extra/configs/toy.json", "why": "throwaway"})
    bench["workloads"].append({"name": "toy.short", "config": "toy",
                               "traffic": "short", "chips": 1, "why": "throwaway"})
    bench["per_layer"].append({
        "name": "toy_steps", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "Step loop", "moves": "throughput",
        "workloads": ["toy.short"],
    })
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


def test_a_fifth_cell_needs_new_files_and_one_entry_only(extended):
    proc, lines = run_cell("toy.short", 1, "--rehearse", "--benchmark", extended)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["attempted"] >= 20
    # on a TPU its line would carry these (and mfu, which needs a peak)
    detail = json.loads(lines[-2])["detail"]
    assert detail["judged"] == ["setup_s", "throughput"]
    # its mix is whole epochs with a save after each: the window pays for
    # them, and the acknowledged save reads back
    assert detail["step_s_from"] == "window mean" and detail["save_stalls"] >= 1
    assert detail["checks"]["restored_step"] == detail["checks"]["steps_dispatched"]
    proc, lines = run_cell("toy.short", 1, "--rehearse", "--trace", "1",
                           "--benchmark", extended)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "toy_steps" in json.loads(lines[-2])["detail"]["judged"]
    # an accepted cell still runs from the extended file
    proc, lines = run_cell("mistral_7b.steady", 1, "--rehearse",
                           "--benchmark", extended)
    assert proc.returncode == 0, proc.stderr[-3000:]


def test_a_new_layer_metric_is_found_by_its_name(extended):
    finder = bench_run.Finder(extended)
    run = types.SimpleNamespace(
        window_steps=40, window_s=2.0, step_s=0.04, items_per_step=8, chips=1, trace=None,
        mix={"ckpt": False}, save_stalls=[], peaks=None, family=None, config={},
        clocks={"t_start": 0.0, "t_devices": 1.0, "t_open": 3.0, "t_close": 5.0},
        tracer_events=[], window_epochs=[1, 2],
        at_open={"registry": {}, "goodput": {"data_wait": 0.0}},
        at_close={"registry": {}, "goodput": {"data_wait": 0.5}},
        at_end={"registry": {}, "memory_peak_bytes": 0},
    )
    got, rest = bench_run.read_metrics(finder, "per_layer", "toy.short", run)
    assert got["toy_steps"] == {"value": 40.0, "unit": "count"}
    assert got["boot_s"]["value"] == 1.0
    assert got["step_ms"]["value"] == 50.0       # the mean: window over steps
    got, rest = bench_run.read_metrics(finder, "per_layer", "resnet50_vd.steady", run)
    assert got["data_wait_share"]["value"] == 25.0 and got["step_ms"]["value"] == 50.0
    assert rest == {"toy_steps": {"value": 40.0, "unit": "count"}}  # another cell's
    # readers that find nothing are left out
    assert "attn_kernel_share" not in got and "step_device_ms" not in got
    assert "hbm_peak_gb" not in got and "cache_misses" not in got
    got, rest = bench_run.read_metrics(finder, "end_to_end", "toy.short", run)
    assert sorted(got) == ["setup_s", "throughput"] and rest == {}
    assert got["throughput"]["value"] == 8 / 0.04  # the harness's seconds a step
