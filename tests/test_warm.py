"""Proactive compile-cache warming (edl_tpu/launch/warm.py).

Fast tests drive CacheWarmer directly with the marker-dropping toy
worker (no jax in the warmed processes); slow tests cover the
ElasticTrainer warm-mode contract and the launcher integration.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from tests.conftest import TOY_WORKER, incarnations

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _job_env(tmp_path, store_endpoint="", nodes_range="1:3"):
    from edl_tpu.cluster.job_env import JobEnv

    return JobEnv(
        job_id="warmjob",
        store_endpoint=store_endpoint,
        nodes_range=nodes_range,
        nproc_per_node=1,
        log_dir=str(tmp_path / "logs"),
        compile_cache_dir=str(tmp_path / "cache"),
    )


@pytest.fixture(autouse=True)
def _no_warm_delay(monkeypatch):
    # the live-stage-first delay is timing policy, not under test here
    monkeypatch.setenv("EDL_PREWARM_DELAY", "0")


def _wait(pred, timeout=30.0, interval=0.1):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


class TestCacheWarmer:
    def test_anticipated_world_sizes(self, tmp_path):
        from edl_tpu.cluster.job_env import JobEnv
        from edl_tpu.launch.warm import anticipated_world_sizes

        je = JobEnv(job_id="j", nodes_range="2:5", nproc_per_node=2)
        assert anticipated_world_sizes(je) == [4, 6, 8, 10]
        je1 = JobEnv(job_id="j", nodes_range="3")
        assert anticipated_world_sizes(je1) == [3]

    def test_warms_grow_sizes_first(self, tmp_path):
        from edl_tpu.launch.warm import CacheWarmer

        out = tmp_path / "markers"
        out.mkdir()
        warmer = CacheWarmer(
            _job_env(tmp_path),
            pod_id="podA",
            training_script=TOY_WORKER,
            extra_worker_env={
                "TEST_OUT_DIR": str(out),
                "TEST_EXIT_AFTER": "0.2",
                "JAX_PLATFORMS": "cpu",
            },
        )
        try:
            warmer.note_world(1)
            assert _wait(lambda: len(warmer.warmed) == 2)
        finally:
            warmer.stop()
        # grows first, largest grow first (current world 1 is skipped)
        assert warmer.warmed == [3, 2]
        runs = incarnations(str(out))
        # shadow stage "warm-2": ranks 0..1 each saw world 2, etc.
        assert runs["warm-2"] == {0: 2, 1: 2}
        assert runs["warm-3"] == {0: 3, 1: 3, 2: 3}

    def test_store_claim_dedupes_across_pods(self, tmp_path, store):
        from edl_tpu.launch.warm import CacheWarmer
        from edl_tpu.store.client import StoreClient

        # another pod already claimed world 2
        client = StoreClient(store.endpoint, timeout=5.0)
        assert client.cas("/warmjob/warm/2", 0, b"other-pod")
        out = tmp_path / "markers"
        out.mkdir()
        warmer = CacheWarmer(
            _job_env(tmp_path, store_endpoint=store.endpoint),
            pod_id="podB",
            training_script=TOY_WORKER,
            extra_worker_env={
                "TEST_OUT_DIR": str(out),
                "TEST_EXIT_AFTER": "0.2",
                "JAX_PLATFORMS": "cpu",
            },
        )
        try:
            warmer.note_world(1)
            assert _wait(lambda: len(warmer.warmed) == 1)
        finally:
            warmer.stop()
        assert warmer.warmed == [3]  # 2 was claimed elsewhere, skipped
        assert client.get("/warmjob/warm/3") == b"done:podB"
        client.close()

    def test_oversized_shadow_stages_skipped(self, tmp_path, monkeypatch):
        from edl_tpu.launch.warm import CacheWarmer

        monkeypatch.setenv("EDL_PREWARM_MAX_WORLD", "2")
        out = tmp_path / "markers"
        out.mkdir()
        warmer = CacheWarmer(
            _job_env(tmp_path),  # window 1:3
            pod_id="podC",
            training_script=TOY_WORKER,
            extra_worker_env={
                "TEST_OUT_DIR": str(out),
                "TEST_EXIT_AFTER": "0.2",
                "JAX_PLATFORMS": "cpu",
            },
        )
        try:
            warmer.note_world(1)
            assert _wait(lambda: len(warmer.warmed) == 1)
            time.sleep(0.5)
        finally:
            warmer.stop()
        assert warmer.warmed == [2]  # 3 exceeds the cap, never spawned

    def test_disabled_without_flag_or_cache(self, tmp_path, monkeypatch):
        from edl_tpu.launch.warm import make_warmer_if_enabled

        monkeypatch.delenv("EDL_PREWARM", raising=False)
        je = _job_env(tmp_path)
        assert (
            make_warmer_if_enabled(je, "p", TOY_WORKER, [], {}, False, "cpu")
            is None
        )
        # enabled by flag, but a 1-size window has nothing to warm
        je_fixed = _job_env(tmp_path, nodes_range="2:2")
        assert (
            make_warmer_if_enabled(
                je_fixed, "p", TOY_WORKER, [], {}, True, "cpu"
            )
            is None
        )
        # non-CPU platform (what the launcher's probe found): shadow
        # stages can't run — the live stage owns the devices
        for platform in ("tpu", ""):
            assert (
                make_warmer_if_enabled(
                    je, "p", TOY_WORKER, [], {}, True, platform
                )
                is None
            )
        w = make_warmer_if_enabled(je, "p", TOY_WORKER, [], {}, True, "cpu")
        assert w is not None
        w.stop()


@pytest.mark.slow
class TestWarmModeTrainer:
    def test_trainer_exits_after_first_step_without_ckpt(self, tmp_path):
        """EDL_WARM_ONLY=1: ElasticTrainer.fit compiles, runs ONE step,
        exits 0, and never creates the checkpoint dir."""
        script = tmp_path / "warm_trainer.py"
        script.write_text(
            "import sys\n"
            "sys.path.insert(0, %r)\n"
            "import numpy as np, optax\n"
            "from edl_tpu.models import MLP\n"
            "from edl_tpu.train import ElasticTrainer, cross_entropy_loss\n"
            "t = ElasticTrainer(\n"
            "    MLP(hidden=(8,), features=4), optax.sgd(0.1),\n"
            "    cross_entropy_loss, np.zeros((8, 8), np.float32),\n"
            "    ckpt_dir=%r, batch_size=8)\n"
            "def data(epoch):\n"
            "    rng = np.random.RandomState(epoch)\n"
            "    for _ in range(50):\n"
            "        yield (rng.randn(8).astype(np.float32),\n"
            "               rng.randint(0, 4))\n"
            "t.fit(data, epochs=3)\n"
            "print('UNREACHABLE-IN-WARM-MODE')\n"
            % (REPO, str(tmp_path / "ckpt"))
        )
        env = dict(
            os.environ,
            EDL_WARM_ONLY="1",
            EDL_JOB_ID="wj",
            JAX_PLATFORMS="cpu",
            PYTHONPATH=REPO,
        )
        res = subprocess.run(
            [sys.executable, str(script)],
            env=env, capture_output=True, text=True, timeout=180,
        )
        assert res.returncode == 0, res.stderr[-1500:]
        assert "warm-only stage" in res.stdout
        assert "UNREACHABLE-IN-WARM-MODE" not in res.stdout
        assert not (tmp_path / "ckpt").exists()

    def test_launcher_prewarm_integration(self, tmp_path, store):
        """--prewarm end to end: a 1-pod job in a 1:2 window warms world 2
        (marker files + store claim), live stage unaffected."""
        out = tmp_path / "markers"
        out.mkdir()
        env = dict(
            os.environ,
            TEST_OUT_DIR=str(out),
            TEST_EXIT_AFTER="8",
            JAX_PLATFORMS="cpu",
            PYTHONPATH=REPO,
        )
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "edl_tpu.launch",
                "--job_id", "prewarmjob",
                "--store", store.endpoint,
                "--nodes_range", "1:2",
                "--ttl", "2.0",
                "--prewarm",
                "--compile_cache_dir", str(tmp_path / "cache"),
                TOY_WORKER,
            ],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        out_text, _ = proc.communicate(timeout=120)
        assert proc.returncode == 0, out_text[-1500:]
        runs = incarnations(str(out))
        # one real stage at world 1 + one shadow stage at world 2
        assert runs["warm-2"] == {0: 2, 1: 2}, runs
        live = [s for s in runs if not s.startswith("warm-")]
        assert len(live) == 1 and runs[live[0]] == {0: 1}
        from edl_tpu.store.client import StoreClient

        client = StoreClient(store.endpoint, timeout=5.0)
        assert client.get("/prewarmjob/warm/2") is not None
        client.close()


class TestAllRankCacheWrites:
    def test_patch_applies_and_is_idempotent(self, tmp_path, monkeypatch):
        monkeypatch.setenv("EDL_CACHE_ALL_RANKS", "1")
        from edl_tpu.train.context import enable_compilation_cache

        enable_compilation_cache(str(tmp_path / "c"))
        from jax._src import compiler as _compiler

        assert getattr(_compiler._cache_write, "_edl_all_ranks", False)
        before = _compiler._cache_write
        enable_compilation_cache(str(tmp_path / "c"))
        assert _compiler._cache_write is before  # no double-wrap

    def test_patched_write_ignores_process_id(self, tmp_path, monkeypatch):
        """The wrapped _cache_write must not take the rank-0-only early
        return: with a fake nonzero process_id it should proceed into the
        write path (observed via the compilation_cache call)."""
        monkeypatch.setenv("EDL_CACHE_ALL_RANKS", "1")
        from edl_tpu.train.context import enable_compilation_cache

        enable_compilation_cache(str(tmp_path / "c"))
        from jax._src import compiler as _compiler
        from jax._src import compilation_cache as _cc

        calls = []
        monkeypatch.setattr(
            _cc, "put_executable_and_time",
            lambda *a, **kw: calls.append(a),
        )
        real_gs = _compiler.distributed.global_state
        monkeypatch.setattr(real_gs, "process_id", 3, raising=False)
        try:
            _compiler._cache_write(
                "k", 1.0, "jit_x", object(), object(), []
            )
        except Exception:
            pass  # fake executable may explode later in the write path
        assert calls, "write path never reached despite process_id=3"


@pytest.mark.slow
def test_prewarm_survives_churn(tmp_path, store):
    """Prewarming must coexist with real churn: a harness-driven schedule
    (SIGKILL shrink included) with EDL_PREWARM=1 completes within its
    budget, warm claims exist, and the job still restages (>=2 live
    stages). Restage latency itself is bounded by the resize bench
    artifacts, not asserted here."""
    from edl_tpu.harness.resize import ResizeHarness
    from edl_tpu.store.client import StoreClient

    out = tmp_path / "markers"
    out.mkdir()
    harness = ResizeHarness(
        store.endpoint,
        "churnwarm",
        TOY_WORKER,
        nodes_range="1:3",
        ttl=2.0,
        extra_env={
            "EDL_PREWARM": "1",
            "EDL_PREWARM_DELAY": "0",
            "JAX_PLATFORMS": "cpu",
            "EDL_DEVICES_PER_PROC": "1",
            "TEST_OUT_DIR": str(out),
            "TEST_EXIT_AFTER": "14",
        },
    )
    try:
        done = harness.run_schedule([2, 3, 1], interval=6.0, timeout=120.0)
        assert done, "job did not complete under churn with prewarm on"
    finally:
        harness.shutdown()
    runs = incarnations(str(out))
    warm_stages = [s for s in runs if s.startswith("warm-")]
    live_stages = [s for s in runs if not s.startswith("warm-")]
    assert warm_stages, "no shadow stage ever ran"
    assert len(live_stages) >= 2, "churn produced no restage"
    client = StoreClient(store.endpoint, timeout=5.0)
    try:
        claims = [
            w for w in (1, 2, 3)
            if client.get("/churnwarm/warm/%d" % w) is not None
        ]
        assert claims, "no warm claims recorded"
    finally:
        client.close()
