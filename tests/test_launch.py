"""Elastic launcher tests: real launcher processes, simulated churn.

The reference only exercises elasticity by wall-clock churn demos
(SURVEY §4.5); per SURVEY §7 "hard parts" we test the resize state machine
deterministically: N real launcher subprocesses against a live store, with
pods SIGKILLed and added mid-run, asserting on the marker files the toy
worker drops for every (stage, rank, world) incarnation.
"""

import json
import os
import signal
import subprocess
import sys
import time

import psutil

from conftest import TOY_WORKER as TOY, incarnations  # noqa: F401 (store fixture via conftest)
from edl_tpu.store import StoreClient
import pytest

pytestmark = pytest.mark.slow  # compile-heavy / multi-process integration


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TTL = "0.8"


def spawn_launcher(store, job_id, out_dir, nodes_range="1:4", exit_after=None, nproc=1, script=None):
    env = dict(os.environ)
    env.update(
        {
            "PYTHONPATH": REPO,
            "TEST_OUT_DIR": out_dir,
            "EDL_DEVICES_PER_PROC": "1",  # keep jax out of the toy pipeline
        }
    )
    if exit_after is not None:
        env["TEST_EXIT_AFTER"] = str(exit_after)
    return subprocess.Popen(
        [
            sys.executable,
            "-m",
            "edl_tpu.launch",
            "--job_id",
            job_id,
            "--store",
            store.endpoint,
            "--nodes_range",
            nodes_range,
            "--nproc_per_node",
            str(nproc),
            "--ttl",
            TTL,
            script or TOY,
        ],
        env=env,
        cwd=REPO,
    )


def wait_for(cond, timeout=25.0, interval=0.1, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        result = cond()
        if result:
            return result
        time.sleep(interval)
    raise AssertionError("timed out waiting for %s" % msg)


def stage_with_world(out_dir, world):
    """A stage in which exactly ranks 0..world-1 ran with that world size."""

    def check():
        for stage, ranks in incarnations(out_dir).items():
            if set(ranks) == set(range(world)) and all(
                w == world for w in ranks.values()
            ):
                return stage
        return None

    return check


def test_single_pod_completes(store, tmp_path):
    launcher = spawn_launcher(store, "j1", str(tmp_path), exit_after=0.5)
    try:
        assert launcher.wait(timeout=30) == 0
    finally:
        if launcher.poll() is None:
            launcher.kill()
    runs = incarnations(str(tmp_path))
    assert len(runs) == 1
    (ranks,) = runs.values()
    assert ranks == {0: 1}
    # job status is COMPLETE in the store
    client = StoreClient(store.endpoint)
    assert client.get("/j1/job/status") == b"COMPLETE"
    client.close()


def test_two_pods_form_world_of_two(store, tmp_path):
    a = spawn_launcher(store, "j2", str(tmp_path))
    b = spawn_launcher(store, "j2", str(tmp_path))
    try:
        stage = wait_for(
            stage_with_world(str(tmp_path), 2), msg="stage with world=2"
        )
        assert stage
    finally:
        for p in (a, b):
            p.send_signal(signal.SIGKILL)
            p.wait()


def test_scale_in_on_pod_kill_then_scale_out(store, tmp_path):
    out = str(tmp_path)
    a = spawn_launcher(store, "j3", out)
    b = spawn_launcher(store, "j3", out)
    c = None
    try:
        first = wait_for(stage_with_world(out, 2), msg="initial world=2")

        # hard-kill pod B: the survivor must drain and republish world=1
        b.send_signal(signal.SIGKILL)
        b.wait()

        def world1_after_first():
            for stage, ranks in incarnations(out).items():
                if stage != first and set(ranks) == {0} and ranks[0] == 1:
                    return stage
            return None

        second = wait_for(world1_after_first, msg="post-kill world=1 restage")

        # now scale out again with a fresh pod
        c = spawn_launcher(store, "j3", out)

        def world2_after_second():
            for stage, ranks in incarnations(out).items():
                if stage not in (first, second) and set(ranks) == {0, 1} and all(
                    w == 2 for w in ranks.values()
                ):
                    return stage
            return None

        wait_for(world2_after_second, msg="scale-out world=2 restage")
    finally:
        for p in (a, b, c):
            if p is not None and p.poll() is None:
                p.send_signal(signal.SIGKILL)
                p.wait()


def test_autoscale_pause_publishes_empty_generation(
    store, tmp_path, monkeypatch
):
    """Preempt-to-0: every pod drains out, and whoever leads next
    publishes the EMPTY generation — cluster/current is the scaler's
    actual-world source, so it must record world 0 (not the victims'
    last roster) WITHOUT the vacuous all-pods-complete check marking
    the job done; raising the target then readmits the held pod."""
    monkeypatch.setenv("EDL_DRAIN_BUDGET", "1")
    out = str(tmp_path)
    client = StoreClient(store.endpoint)
    a = spawn_launcher(store, "j9", out)
    b = spawn_launcher(store, "j9", out)
    c = None
    try:
        wait_for(stage_with_world(out, 2), msg="initial world=2")
        # the scaler pauses the job: preempt-to-0
        client.put(
            "/j9/scale/target",
            json.dumps({"pods": 0, "seq": 1, "cause": "pause"}).encode(),
        )
        assert a.wait(timeout=30) == 76  # DRAINED_EXIT
        assert b.wait(timeout=30) == 76
        # a fresh pod arrives, is held, and publishes the pause marker
        c = spawn_launcher(store, "j9", out)

        def empty_generation():
            raw = client.get("/j9/cluster/current")
            return raw is not None and json.loads(raw).get("pods") == []

        wait_for(empty_generation, msg="empty pause generation")
        assert client.get("/j9/job/status") != b"COMPLETE"
        before = set(incarnations(out))
        # the scaler readmits: the held pod forms world 1 under a NEW stage
        client.put(
            "/j9/scale/target",
            json.dumps({"pods": 1, "seq": 2, "cause": "grow"}).encode(),
        )

        def world1_readmitted():
            for stage, ranks in incarnations(out).items():
                if stage not in before and ranks == {0: 1}:
                    return stage
            return None

        wait_for(world1_readmitted, msg="world-1 readmission")
    finally:
        for p in (a, b, c):
            if p is not None and p.poll() is None:
                p.send_signal(signal.SIGKILL)
                p.wait()
        client.close()


def test_min_nodes_blocks_publication(store, tmp_path):
    out = str(tmp_path)
    a = spawn_launcher(store, "j4", out, nodes_range="2:4")
    try:
        time.sleep(3.0)  # well past several TTLs
        assert incarnations(out) == {}, "must not start below min_nodes"
        b = spawn_launcher(store, "j4", out, nodes_range="2:4")
        try:
            wait_for(stage_with_world(out, 2), msg="world=2 once min reached")
        finally:
            b.send_signal(signal.SIGKILL)
            b.wait()
    finally:
        a.send_signal(signal.SIGKILL)
        a.wait()


def test_max_nodes_caps_cluster(store, tmp_path):
    out = str(tmp_path)
    pods = [spawn_launcher(store, "j5", out, nodes_range="1:2") for _ in range(3)]
    try:
        wait_for(stage_with_world(out, 2), msg="world capped at 2")
        time.sleep(1.0)
        for ranks in incarnations(out).values():
            assert all(w <= 2 for w in ranks.values())
    finally:
        for p in pods:
            p.send_signal(signal.SIGKILL)
            p.wait()


def test_workers_die_with_sigkilled_launcher(store, tmp_path):
    """PR_SET_PDEATHSIG: a SIGKILL'd launcher must not leave orphan workers
    holding devices (machine-death simulation on one host)."""
    out = str(tmp_path)
    launcher = spawn_launcher(store, "j7", out)
    try:
        wait_for(stage_with_world(out, 1), msg="worker started")
        children = psutil.Process(launcher.pid).children(recursive=True)
        assert children, "launcher has no worker children"
        launcher.send_signal(signal.SIGKILL)
        launcher.wait()

        def dead(p):
            # reparented-to-us workers linger as zombies until wait()ed;
            # PDEATHSIG did its job once they are no longer running code
            try:
                return p.status() == psutil.STATUS_ZOMBIE
            except psutil.NoSuchProcess:
                return True

        wait_for(
            lambda: all(dead(p) for p in children),
            timeout=5.0,
            msg="workers reaped after launcher SIGKILL",
        )
    finally:
        if launcher.poll() is None:
            launcher.kill()
        for p in psutil.Process().children(recursive=True):
            if "toy_worker" in " ".join(p.cmdline() or []):
                p.kill()


def test_nproc_per_node_multi_worker_pod(store, tmp_path):
    out = str(tmp_path)
    launcher = spawn_launcher(store, "j6", out, exit_after=0.5, nproc=2)
    try:
        assert launcher.wait(timeout=30) == 0
    finally:
        if launcher.poll() is None:
            launcher.kill()
    runs = incarnations(out)
    assert len(runs) == 1
    (ranks,) = runs.values()
    assert ranks == {0: 2, 1: 2}


def test_sixteen_pod_join_and_churn(store, tmp_path):
    """Rank-racing stress (VERDICT #7): 16 pods join one job (each join
    range-reads the rank service and races only free slots), then 4 are
    SIGKILLed and 4 fresh pods take their slots."""
    out = str(tmp_path)
    n = 16
    pods = [
        spawn_launcher(store, "j16", out, nodes_range="1:%d" % n)
        for _ in range(n)
    ]
    fresh = []
    try:
        first = wait_for(
            stage_with_world(out, n), timeout=90, msg="world=16 formed"
        )

        for p in pods[:4]:
            p.send_signal(signal.SIGKILL)
            p.wait()
        fresh = [
            spawn_launcher(store, "j16", out, nodes_range="1:%d" % n)
            for _ in range(4)
        ]

        def full_world_after_churn():
            for stage, ranks in incarnations(out).items():
                if stage != first and set(ranks) == set(range(n)) and all(
                    w == n for w in ranks.values()
                ):
                    return stage
            return None

        wait_for(
            full_world_after_churn, timeout=90,
            msg="world=16 reformed after killing 4 and adding 4",
        )
    finally:
        for p in pods + fresh:
            if p.poll() is None:
                p.send_signal(signal.SIGKILL)
                p.wait()


def test_jax_distributed_bootstrap_two_pods(store, tmp_path):
    """Two launcher pods -> world 2 -> the workers really initialize
    jax.distributed from the EDL_* contract and run a cross-process XLA
    collective (a globally sharded sum = 1 + 2): the TPU-pod bootstrap
    path, executed for real on the CPU backend (Gloo)."""
    out = str(tmp_path)
    script = os.path.join(REPO, "tests", "jaxdist_worker.py")
    a = spawn_launcher(store, "jdist", out, nodes_range="2:2", script=script)
    b = spawn_launcher(store, "jdist", out, nodes_range="2:2", script=script)

    def both_summed():
        got = []
        for r in (0, 1):
            path = os.path.join(out, "psum.%d" % r)
            if not os.path.exists(path):
                return None
            parts = open(path).read().split()
            if len(parts) != 4:
                return None
            got.append(tuple(float(x) for x in parts))
        # global sum = local_devices * (1 + 2), identical on every process
        return got if all(
            g[0] == 2.0 and g[1] == 2.0 and g[3] == g[2] * 3.0 for g in got
        ) else None

    try:
        assert wait_for(both_summed, timeout=90, msg="cross-process psum")
    finally:
        for p in (a, b):
            p.send_signal(signal.SIGKILL)
            p.wait()


def test_jax_distributed_survives_coordinator_death(store, tmp_path):
    """Kill the COORDINATOR pod (rank 0 hosts the jax.distributed service):
    survivors must drain, re-race ranks, elect a new coordinator, re-init
    jax.distributed at world=2 and complete a fresh cross-process
    collective — the stop-resume answer to SURVEY §7's 'coordinator may be
    the removed host' hard part."""
    out = str(tmp_path)
    script = os.path.join(REPO, "tests", "jaxdist_worker.py")
    pods = [
        spawn_launcher(store, "jdist2", out, nodes_range="1:3", script=script)
        for _ in range(3)
    ]

    def summed(world):
        def check():
            got = []
            for r in range(world):
                path = os.path.join(out, "psum.%d" % r)
                if not os.path.exists(path):
                    return None
                parts = open(path).read().split()
                if len(parts) != 4 or float(parts[0]) != world:
                    return None
                got.append(tuple(float(x) for x in parts))
            expect = world * (world + 1) / 2
            return all(g[3] == g[2] * expect for g in got) or None

        return check

    try:
        assert wait_for(summed(3), timeout=90, msg="world=3 psum")
        # the rank-0 slot holder hosts the coordinator; SIGKILL that pod
        client = StoreClient(store.endpoint)
        rank0_pod = client.get("/jdist2/pod_rank/0").decode()
        client.close()
        import psutil as _ps

        victim = None
        for p in pods:
            try:
                kids = _ps.Process(p.pid).children(recursive=True)
                # EDL_POD_ID is injected into the WORKER children, not the
                # launcher itself (process.py)
                if any(
                    k.environ().get("EDL_POD_ID") == rank0_pod for k in kids
                ):
                    victim = p
            except (_ps.NoSuchProcess, _ps.AccessDenied):
                continue
        assert victim is not None, "no launcher owns the rank-0 pod id"
        victim.send_signal(signal.SIGKILL)
        victim.wait()
        assert wait_for(summed(2), timeout=90, msg="world=2 psum after kill")
    finally:
        for p in pods:
            if p.poll() is None:
                p.send_signal(signal.SIGKILL)
                p.wait()


def test_true_worker_crash_still_fails_job(store, tmp_path):
    """A worker that crashes with stable membership must still fail the
    pod (fail-fast) — the restage grace only forgives crashes that a
    membership change follows."""
    crash = os.path.join(str(tmp_path), "crash.py")
    with open(crash, "w") as f:
        f.write("import sys; sys.exit(3)\n")
    launcher = spawn_launcher(store, "jcrash", str(tmp_path), script=crash)
    try:
        assert launcher.wait(timeout=30) == 3
    finally:
        if launcher.poll() is None:
            launcher.kill()


def test_job_survives_store_kill_and_restart(tmp_path):
    """Round-3 durability acceptance: SIGKILL the store daemon mid-job and
    restart it on the same data_dir — the job must keep its stage (no
    worker restarts) and complete. The reference gets this from etcd being
    an external disk-persistent service + client reconnect
    (etcd_client.py:40-50); here it's the store's snapshot/WAL + the
    client's reconnect/lease-keeper tolerance."""
    from edl_tpu.utils.net import find_free_ports, wait_until_alive

    port = find_free_ports(1)[0]
    endpoint = "127.0.0.1:%d" % port
    data_dir = str(tmp_path / "store")
    out_dir = str(tmp_path / "out")
    os.makedirs(out_dir)
    store_cmd = [
        sys.executable, "-m", "edl_tpu.store.server",
        "--host", "127.0.0.1", "--port", str(port), "--data_dir", data_dir,
    ]
    env = dict(os.environ, PYTHONPATH=REPO)
    store_proc = subprocess.Popen(store_cmd, env=env)
    launchers = []
    try:
        assert wait_until_alive(endpoint, timeout=10.0)

        import types

        fake_store = types.SimpleNamespace(endpoint=endpoint)
        # ttl=3s: the keeper tolerates a store outage shorter than the TTL
        # (reference heartbeat re-register semantics, register.py:57-76)
        worker_env = dict(
            PYTHONPATH=REPO, TEST_OUT_DIR=out_dir, EDL_DEVICES_PER_PROC="1",
            TEST_EXIT_AFTER="12",
        )
        for _ in range(2):
            lenv = dict(os.environ)
            lenv.update(worker_env)
            launchers.append(subprocess.Popen(
                [
                    sys.executable, "-m", "edl_tpu.launch",
                    "--job_id", "store-bounce",
                    "--store", endpoint,
                    "--nodes_range", "2:2",
                    "--ttl", "3",
                    TOY,
                ],
                env=lenv, cwd=REPO,
            ))
        stage = wait_for(
            stage_with_world(out_dir, 2), timeout=30, msg="world-2 stage"
        )

        # hard-kill the store; ~1s outage, well under the 3s lease TTL
        store_proc.kill()
        store_proc.wait()
        time.sleep(1.0)
        store_proc = subprocess.Popen(store_cmd, env=env)
        assert wait_until_alive(endpoint, timeout=10.0)

        for proc in launchers:
            assert proc.wait(timeout=60) == 0
        # the bounce caused no restage: the one stage is the only one
        assert set(incarnations(out_dir)) == {stage}
        client = StoreClient(endpoint, timeout=5.0)
        try:
            assert client.get("/store-bounce/job/status") == b"COMPLETE"
        finally:
            client.close()
    finally:
        for proc in launchers:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if store_proc.poll() is None:
            store_proc.kill()
            store_proc.wait()


def test_job_survives_store_death_via_launcher_standby(tmp_path):
    """Control-plane HA acceptance for --store_standby: the primary store
    dies FOR GOOD mid-job, and the launcher's co-hosted warm standby
    promotes (epoch-fenced) and carries the job to COMPLETE. Unlike
    test_job_survives_store_kill_and_restart, nothing ever comes back on
    the old endpoint — completion is only possible through failover."""
    from edl_tpu.utils.net import find_free_ports, wait_until_alive

    port = find_free_ports(1)[0]
    endpoint = "127.0.0.1:%d" % port
    out_dir = str(tmp_path / "out")
    os.makedirs(out_dir)
    store_cmd = [
        sys.executable, "-m", "edl_tpu.store.server",
        "--host", "127.0.0.1", "--port", str(port),
        "--data_dir", str(tmp_path / "store"),
    ]
    env = dict(os.environ, PYTHONPATH=REPO)
    store_proc = subprocess.Popen(store_cmd, env=env)
    launcher = None
    try:
        assert wait_until_alive(endpoint, timeout=10.0)
        lenv = dict(os.environ)
        lenv.update(
            PYTHONPATH=REPO, TEST_OUT_DIR=out_dir, EDL_DEVICES_PER_PROC="1",
            TEST_EXIT_AFTER="16",
        )
        launcher = subprocess.Popen(
            [
                sys.executable, "-m", "edl_tpu.launch",
                "--job_id", "standby-ha",
                "--store", endpoint,
                "--store_standby", str(tmp_path / "standby"),
                "--nodes_range", "1:1",
                "--ttl", "3",
                TOY,
            ],
            env=lenv, cwd=REPO,
        )
        wait_for(stage_with_world(out_dir, 1), timeout=30, msg="world-1 stage")
        # hold long enough for the launcher client's periodic endpoint
        # refresh (5s cadence, driven by keepalive traffic) to learn the
        # standby's address, then kill the primary permanently
        time.sleep(7.0)
        store_proc.kill()
        store_proc.wait()
        assert launcher.wait(timeout=90) == 0
    finally:
        for proc in (launcher, store_proc):
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait()


def test_multiprocess_evaluate_ragged_tail(store, tmp_path):
    """ElasticTrainer.evaluate across a REAL 2-process stage with a
    ragged final batch: the masked static-shape eval path (train/step.py)
    must keep every process on one uniform compilation and collective
    schedule — the round-2 advisor's shape-divergence hang scenario —
    and both ranks must report identical global metrics that match a
    single-process evaluate of the same model and records."""
    out = str(tmp_path)
    script = os.path.join(REPO, "tests", "eval_mp_worker.py")
    a = spawn_launcher(store, "jeval", out, nodes_range="2:2", script=script)
    b = spawn_launcher(store, "jeval", out, nodes_range="2:2", script=script)

    def both_wrote():
        paths = [os.path.join(out, "eval.%d.json" % r) for r in (0, 1)]
        if not all(os.path.exists(p) for p in paths):
            return None
        try:
            return [json.load(open(p)) for p in paths]
        except ValueError:
            return None  # mid-write

    try:
        got = wait_for(both_wrote, timeout=120, msg="both ranks' eval metrics")
    finally:
        for p in (a, b):
            p.send_signal(signal.SIGKILL)
            p.wait()
    assert got[0].keys() == got[1].keys() and "loss" in got[0]
    for k in got[0]:
        assert abs(got[0][k] - got[1][k]) < 1e-6, (k, got)

    # single-process reference over the same records (uniform duplication
    # across dp groups preserves the weighted mean, so the values agree)
    env = dict(os.environ, TEST_OUT_DIR=out, EDL_WORKER_RANK="9",
               PYTHONPATH=REPO)
    env.pop("EDL_STORE_ENDPOINT", None)
    res = subprocess.run(
        [sys.executable, script], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert res.returncode == 0, res.stderr[-1200:]
    ref = json.load(open(os.path.join(out, "eval.9.json")))
    for k in ref:
        assert abs(got[0][k] - ref[k]) < 1e-4, (k, got[0], ref)
