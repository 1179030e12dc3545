"""Worker-process management: spawn, watch, terminate.

Capability parity with the reference's trainer process manager
(python/edl/utils/edl_process.py:39-166): one subprocess per worker with the
rank env contract injected, per-rank ``workerlog.N`` files, proxy env
stripped (the reference strips proxies so NCCL's socket bootstrap works,
edl_process.py:45-50 — the same applies to the JAX coordinator's gRPC
bootstrap), SIGTERM-then-SIGKILL teardown of the whole descendant tree via
psutil, and exit-code polling.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import psutil

from edl_tpu.chaos.plane import fault_point as _fault_point
from edl_tpu.cluster.model import Cluster, Pod, Worker
from edl_tpu.utils.log import get_logger

logger = get_logger("launch.process")

_FP_SPAWN = _fault_point(
    "launch.process.spawn",
    "per-worker spawn: delay (slow cold start) or kill (pod dies mid-spawn)",
)


@dataclass
class WorkerProc:
    worker: Worker
    proc: subprocess.Popen
    log_path: str = ""
    log_file: object = None
    exit_code: Optional[int] = None


# Child-side bootstrap run via ``python -c``: arms PR_SET_PDEATHSIG, then
# replaces itself with the real worker via execv (prctl survives a normal
# execve, so the final process keeps the death signal and an argv identical
# to a direct launch). This replaces the old preexec_fn approach: a
# preexec_fn forces subprocess onto the fork+Python-hooks path, which JAX's
# at-fork handler (rightly) flags as a deadlock hazard in any parent that
# has JAX loaded. The session split is handled by ``start_new_session=True``
# (C-side setsid with the same completed-before-Popen-returns guarantee).
# PDEATHSIG is armed a few ms later than preexec_fn would — the interpreter
# startup window — which only widens the already-nonzero fork-to-prctl gap.
_PDEATHSIG_BOOT = (
    "import ctypes, os, signal, sys\n"
    "try:\n"
    "    ctypes.CDLL('libc.so.6', use_errno=True)"
    ".prctl(1, int(signal.SIGKILL), 0, 0, 0)\n"
    "except Exception:\n"
    "    pass  # non-glibc: orphan cleanup degrades to lease TTL\n"
    "os.execv(sys.executable, [sys.executable, '-u'] + sys.argv[1:])\n"
)


def worker_command(training_script: str, training_args: Sequence[str]) -> List[str]:
    """argv for one worker: PDEATHSIG bootstrap + ``python -u script args``.

    PR_SET_PDEATHSIG delivers SIGKILL to the worker if the launcher dies
    without running its teardown (SIGKILL, OOM) — otherwise workers would
    outlive the launcher as orphans still holding TPU devices, and the
    respawned pod could not reacquire them.
    """
    return [sys.executable, "-c", _PDEATHSIG_BOOT, training_script, *training_args]


def base_worker_env() -> Dict[str, str]:
    """The launcher env with worker-hostile vars stripped — the common
    base of every spawned worker AND the standby shells (which must see
    the same import-time jax env a real worker would)."""
    env = dict(os.environ)
    for key in ("http_proxy", "https_proxy", "HTTP_PROXY", "HTTPS_PROXY"):
        env.pop(key, None)
    return env


def worker_env(cluster: Cluster, pod: Pod, worker: Worker, extra: Dict[str, str]) -> Dict[str, str]:
    env = base_worker_env()
    env.update(
        {
            "EDL_JOB_ID": extra.get("EDL_JOB_ID", ""),
            "EDL_POD_ID": pod.pod_id,
            "EDL_STAGE": cluster.stage,
            "EDL_WORKER_RANK": str(worker.global_rank),
            "EDL_WORKER_RANK_IN_POD": str(worker.rank_in_pod),
            "EDL_NUM_WORKERS": str(cluster.world_size),
            "EDL_COORDINATOR": cluster.coordinator,
            "EDL_WORKER_ENDPOINTS": ",".join(cluster.worker_endpoints()),
            # distributed tracing: the worker's restage trace records a
            # worker_boot segment from this wall-clock stamp, so the
            # interpreter+import cold start is attributed, not a gap
            "EDL_SPAWN_TS": repr(time.time()),
        }
    )
    env.update(extra)
    return env


def start_local_workers(
    cluster: Cluster,
    pod: Pod,
    training_script: str,
    training_args: Sequence[str],
    log_dir: str = "",
    extra_env: Optional[Dict[str, str]] = None,
    standby=None,
) -> List[WorkerProc]:
    """Spawn this pod's workers for ``cluster``'s stage. With a
    ``standby`` pool (launch/standby.py), each worker first tries to
    activate a pre-imported shell — the restage fast path — and cold
    spawns only when the pool declines."""
    procs: List[WorkerProc] = []
    extra = dict(extra_env or {})
    for worker in sorted(pod.workers, key=lambda w: w.rank_in_pod):
        if _FP_SPAWN.armed:
            _FP_SPAWN.fire(rank=worker.global_rank, stage=cluster.stage[:8])
        env = worker_env(cluster, pod, worker, extra)
        log_path, log_file = "", None
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
            log_path = os.path.join(log_dir, "workerlog.%d" % worker.global_rank)
        proc = None
        if standby is not None:
            proc = standby.activate(
                env, training_script, training_args, log_path
            )
        if proc is None:
            if log_path:
                log_file = open(log_path, "ab")
            proc = subprocess.Popen(  # edl: blocking-ok(spawning workers IS the supervision action; fork+exec is bounded and restage-rare)
                worker_command(training_script, training_args),
                env=env,
                stdout=log_file if log_file else None,
                stderr=subprocess.STDOUT if log_file else None,
                start_new_session=True,
            )
        logger.info(
            "spawned worker rank=%d pid=%d stage=%s log=%s",
            worker.global_rank,
            proc.pid,
            cluster.stage[:8],
            log_path or "-",
        )
        procs.append(WorkerProc(worker, proc, log_path, log_file))
    if standby is not None:
        # replace what activation consumed — DEFERRED and niced, so the
        # respawned shells' imports don't contend with the new workers'
        # own startup (measured to add downtime when immediate)
        standby.ensure_later()
    return procs


def watch_local_workers(procs: List[WorkerProc]) -> Optional[int]:
    """Poll exit codes. Returns the first nonzero exit code, 0 when ALL
    workers exited cleanly, or None while any is still running."""
    alive = False
    for wp in procs:
        if wp.exit_code is None:
            wp.exit_code = wp.proc.poll()
        if wp.exit_code is None:
            alive = True
        elif wp.exit_code != 0:
            return wp.exit_code
    return None if alive else 0


def terminate_local_workers(procs: List[WorkerProc], grace: float = 3.0) -> None:
    """SIGTERM the worker trees, escalate to SIGKILL after ``grace``."""
    trees: List[psutil.Process] = []
    for wp in procs:
        if wp.proc.poll() is None:
            try:
                root = psutil.Process(wp.proc.pid)
                trees.extend([root, *root.children(recursive=True)])
            except psutil.NoSuchProcess:
                pass
    for proc in trees:
        try:
            proc.terminate()
        except psutil.NoSuchProcess:
            pass
    _, survivors = psutil.wait_procs(trees, timeout=grace)
    for proc in survivors:
        try:
            proc.kill()
        except psutil.NoSuchProcess:
            pass
    for wp in procs:
        try:
            wp.exit_code = wp.proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            logger.warning("worker pid=%d did not exit after SIGKILL", wp.proc.pid)
        if wp.log_file:
            try:
                wp.log_file.close()
            except OSError:
                pass
            wp.log_file = None
    if trees:
        logger.info("terminated %d worker process(es)", len(procs))


def close_worker_logs(procs: List[WorkerProc]) -> None:
    for wp in procs:
        if wp.log_file:
            try:
                wp.log_file.close()
            except OSError:
                pass
            wp.log_file = None
