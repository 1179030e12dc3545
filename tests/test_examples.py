"""Examples smoke suite: every runnable workload in examples/ stays
runnable on CPU (the tree's claim), with tiny knobs so the whole file is
minutes, not hours. Anything here breaking means a user-facing entry
point rotted, not just a library.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")


def run_example(name, args, timeout=240, extra_env=None, devices=1):
    env = dict(
        os.environ,
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=%d" % devices,
    )
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, name)] + args,
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert proc.returncode == 0, "%s failed:\n%s" % (name, proc.stderr[-1500:])
    return proc.stdout


def run_tool(name, args, timeout=900):
    """CPU-pinned subprocess run of a tools/ script; returns the
    completed process (caller asserts). One home for the env scrubbing
    every tool smoke test needs."""
    env = dict(
        os.environ,
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
    )
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", name)] + args,
        capture_output=True, text=True, timeout=timeout, env=env,
    )


@pytest.mark.slow
def test_fit_a_line(tmp_path):
    out = run_example(
        "fit_a_line.py", ["--epochs", "2"],
        extra_env={"EDL_CKPT_PATH": str(tmp_path / "ckpt")},
    )
    assert "loss" in out.lower()


@pytest.mark.slow
def test_resnet_collective():
    out = run_example(
        "resnet_collective.py",
        ["--epochs", "1", "--steps_per_epoch", "2", "--batch_per_worker", "4"],
    )
    assert "epoch" in out.lower()


@pytest.mark.slow
def test_ctr_train():
    out = run_example(
        "ctr_train.py",
        ["--steps", "3", "--batch", "32", "--vocab", "1000"],
        devices=4,  # exercises the sharded-embedding (mp) path
    )
    assert "auc" in out.lower() or "loss" in out.lower()


@pytest.mark.slow
def test_lm_generate_round_trip():
    """Self-checking train -> KV-cached greedy decode loop: the example
    exits nonzero unless generation continues the learned pattern."""
    out = run_example("lm_generate.py", ["--steps", "60"])
    assert "OK: generation continues the learned pattern" in out


@pytest.mark.slow
def test_lm_long_context():
    out = run_example(
        "lm_long_context.py",
        ["--steps", "2", "--batch", "4", "--seq_len", "128",
         "--d_model", "32", "--num_layers", "2", "--num_heads", "2",
         "--vocab", "128"],
        devices=8,  # dp x sp ring-attention mesh
    )
    assert "trained" in out.lower()


@pytest.mark.slow
def test_elastic_text_lm_standalone(tmp_path):
    out = run_example(
        "elastic_text_lm.py",
        ["--epochs", "1", "--data_dir", str(tmp_path / "corpus")],
        timeout=360,
    )
    assert "digest" in out


@pytest.mark.slow
def test_attention_bench_tool_cpu():
    import json

    env = dict(
        os.environ,
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "attention_bench.py"),
         "--seqs", "128", "--iters", "2"],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-1200:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    # the summary row is now the dispatch-vs-dense acceptance metric
    assert last["metric"] == "attention_dispatch_speedup"
    assert last["seq"] == 128
    assert last["fwd"] > 0 and last["fwd_bwd"] > 0


@pytest.mark.slow
def test_attention_block_sweep_tool_cpu():
    """The block-sweep tool produces fwd AND fwd+bwd rows (the backward is
    composed explicitly), so the shipped _FLASH2_BLOCKS_* constants stay
    re-derivable."""
    import json

    proc = run_tool(
        "attention_block_sweep.py",
        ["--seqs", "64", "--batch", "1", "--heads", "1",
         "--head_dim", "8", "--blocks_q", "32", "--blocks_k", "32",
         "--iters", "1"],
    )
    assert proc.returncode == 0, proc.stderr[-1200:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert row["seq"] == 64
    # toy shapes can two-point-cancel to 0.0 ms; structure is the
    # contract here — both modes measured, no compile error recorded
    assert "error" not in row
    assert row["fwd_ms"] >= 0 and row["fwdbwd_ms"] >= 0


@pytest.mark.slow
def test_convergence_lm_worker_single_process(tmp_path):
    """The char-LM churn worker end to end in one process: corpus build,
    dispatcher-fed masked sync-SGD, checkpoint save, held-out eval with a
    final.json + row->step pair files (the perturbation-proof artifact)."""
    import json

    sys.path.insert(0, REPO)
    from tools.convergence_churn import build_text_corpus

    data = tmp_path / "data"
    out = tmp_path / "out"
    out.mkdir()
    n_train, n_held = build_text_corpus(str(data), max_bytes=120_000)
    assert n_held == 600

    from edl_tpu.store.server import StoreServer

    store = StoreServer(host="127.0.0.1", port=0).start()
    try:
        env = dict(
            os.environ,
            PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=1",
            EDL_JOB_ID="convsmoke",
            EDL_STORE_ENDPOINT=store.endpoint,
            EDL_WORKER_RANK="0",
            EDL_NUM_WORKERS="1",
            EDL_STAGE="s1",
            EDL_CKPT_PATH=str(tmp_path / "ckpt"),
            TEST_OUT_DIR=str(out),
            TEST_DATA_DIR=str(data),
            TEST_EPOCHS="1",
        )
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "convergence_lm_worker.py")],
            capture_output=True, text=True, timeout=420, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-1500:]
    finally:
        store.stop()
    final = json.loads((out / "final.json").read_text())
    assert final["eval_rows"] == 600
    assert 0.0 < final["test_accuracy"] < 1.0
    assert final["steps"] > 0
    pairs = [n for n in os.listdir(out) if n.startswith("pairs.")]
    assert pairs, "row->step pair files must exist"
