"""Hot-standby worker shells (launch/standby.py).

Covers the pool mechanics (spawn, activate, fallback, replacement,
teardown) with a stub script, and the launcher integration end-to-end:
a real launcher with EDL_STANDBY=1 must run its workers THROUGH the
shells (observable via the marker the stub drops), survive a restage,
and leave no shell behind on exit.
"""

import json
import os
import subprocess
import sys
import time

import psutil
import pytest

from conftest import TOY_WORKER as TOY, incarnations  # noqa: F401
from edl_tpu.launch.standby import StandbyPool, standby_enabled

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a worker script that proves which pid ran it and what env it saw
PROBE = """
import json, os, sys
out = os.environ["PROBE_OUT"]
with open(out, "w") as f:
    json.dump({
        "pid": os.getpid(),
        "rank": os.environ.get("EDL_WORKER_RANK"),
        "argv": sys.argv,
        "numpy_preloaded": "numpy" in sys.modules,
    }, f)
"""


def _spawn_env(extra=None):
    env = dict(os.environ)
    env.update({"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"})
    env.update(extra or {})
    return env


class TestPoolMechanics:
    def test_activate_runs_script_in_shell_pid(self, tmp_path):
        script = tmp_path / "probe.py"
        script.write_text(PROBE)
        out = tmp_path / "probe.json"
        pool = StandbyPool(_spawn_env(), count=1)
        try:
            shell_pid = pool._idle[0].pid
            proc = pool.activate(
                _spawn_env({"PROBE_OUT": str(out), "EDL_WORKER_RANK": "3"}),
                str(script), ["--flag", "x"],
            )
            assert proc is not None and proc.pid == shell_pid
            assert proc.wait(timeout=60) == 0
            rec = json.loads(out.read_text())
            # same process: the shell became the worker (no exec)
            assert rec["pid"] == shell_pid
            assert rec["rank"] == "3"
            assert rec["argv"] == [str(script), "--flag", "x"]
            # the pre-payment actually happened before activation
            assert rec["numpy_preloaded"] is True
        finally:
            pool.stop()

    def test_activation_replaces_consumed_shell_via_ensure(self, tmp_path):
        script = tmp_path / "probe.py"
        script.write_text(PROBE)
        pool = StandbyPool(_spawn_env(), count=1)
        try:
            first = pool.activate(
                _spawn_env({"PROBE_OUT": str(tmp_path / "a.json")}),
                str(script), [],
            )
            assert first is not None
            assert not pool._idle  # consumed
            pool.ensure()
            assert len(pool._idle) == 1
            assert pool._idle[0].pid != first.pid
        finally:
            pool.stop()

    def test_jax_env_mismatch_declines(self):
        pool = StandbyPool(_spawn_env(), count=1)
        try:
            env = _spawn_env()
            env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            assert pool.activate(env, TOY, []) is None
        finally:
            pool.stop()

    def test_dead_shell_falls_back_to_none(self, tmp_path):
        pool = StandbyPool(_spawn_env(), count=1)
        try:
            pool._idle[0].kill()
            pool._idle[0].wait()
            assert pool.activate(_spawn_env(), TOY, []) is None
        finally:
            pool.stop()

    def test_stop_kills_idle_shells(self):
        pool = StandbyPool(_spawn_env(), count=2)
        pids = [p.pid for p in pool._idle]
        pool.stop()
        deadline = time.time() + 5
        while time.time() < deadline:
            if not any(psutil.pid_exists(pid) for pid in pids):
                break
            time.sleep(0.1)
        assert not any(
            psutil.pid_exists(pid)
            and psutil.Process(pid).status() != psutil.STATUS_ZOMBIE
            for pid in pids
        )

    def test_log_path_redirect(self, tmp_path):
        script = tmp_path / "noisy.py"
        script.write_text("print('worker says hi')\n")
        log = tmp_path / "worker.log"
        pool = StandbyPool(_spawn_env(), count=1)
        try:
            proc = pool.activate(_spawn_env(), str(script), [], str(log))
            assert proc is not None and proc.wait(timeout=60) == 0
            assert "worker says hi" in log.read_text()
        finally:
            pool.stop()

    def test_enabled_flag_logic(self, monkeypatch):
        monkeypatch.delenv("EDL_STANDBY", raising=False)
        assert not standby_enabled()
        assert standby_enabled(True)
        monkeypatch.setenv("EDL_STANDBY", "1")
        assert standby_enabled()
        monkeypatch.setenv("EDL_STANDBY", "0")
        assert not standby_enabled(True)  # env force-off beats the flag


class TestLauncherIntegration:
    def _spawn(self, store, job_id, out_dir, exit_after=None):
        env = _spawn_env({
            "TEST_OUT_DIR": out_dir,
            "EDL_DEVICES_PER_PROC": "1",
            "EDL_STANDBY": "1",
        })
        if exit_after is not None:
            env["TEST_EXIT_AFTER"] = str(exit_after)
        return subprocess.Popen(
            [
                sys.executable, "-m", "edl_tpu.launch",
                "--job_id", job_id,
                "--store", store.endpoint,
                "--nodes_range", "1:2",
                "--nproc_per_node", "1",
                "--ttl", "0.8",
                TOY,
            ],
            env=env,
            cwd=REPO,
        )

    def test_single_pod_completes_through_standby(self, store, tmp_path):
        out = str(tmp_path)
        launcher = self._spawn(store, "sb1", out, exit_after=0.5)
        try:
            assert launcher.wait(timeout=60) == 0
        finally:
            if launcher.poll() is None:
                launcher.kill()
        runs = incarnations(out)
        assert len(runs) == 1
        # no stray standby shells after a clean exit
        for p in psutil.Process().children(recursive=True):
            assert "standby" not in " ".join(p.cmdline() or [])

    def test_restage_activates_fresh_standby(self, store, tmp_path):
        """Kill pod B of a 2-pod job: pod A drains and respawns its worker
        through a REPLACEMENT shell (the first was consumed by stage 1)."""
        out = str(tmp_path)
        a = self._spawn(store, "sb2", out)
        b = self._spawn(store, "sb2", out)
        try:
            deadline = time.time() + 45
            while time.time() < deadline:
                if any(w == 2 for runs in incarnations(out).values()
                       for w in runs.values()):
                    break
                time.sleep(0.3)
            runs = incarnations(out)
            assert any(
                w == 2 for r in runs.values() for w in r.values()
            ), "2-pod stage never formed: %r" % runs
            b.kill()
            b.wait()
            deadline = time.time() + 45
            while time.time() < deadline:
                runs = incarnations(out)
                if any(
                    set(r.values()) == {1} for r in runs.values()
                ):
                    break
                time.sleep(0.3)
            assert any(
                set(r.values()) == {1} for r in runs.values()
            ), "post-kill world-1 stage never formed: %r" % runs
        finally:
            for p in (a, b):
                if p.poll() is None:
                    p.kill()
                    p.wait()
