"""Job and worker environment contracts.

Capability parity with the reference's ``JobEnv``/``TrainerEnv``
(python/edl/utils/edl_env.py:30-180): job config merged from CLI args and
``EDL_*`` env vars, elastic node window "min:max", per-node process count,
checkpoint path — and the worker-side env the process manager injects
(reference edl_process.py:54-62 injects ``PADDLE_TRAINER_*``; we inject
``EDL_*`` consumed by :func:`edl_tpu.train.init` to drive
``jax.distributed.initialize``).

TPU topology: instead of ``get_cuda_device_count`` (reference
utils.py:98-120), the local device count comes from ``EDL_DEVICES_PER_PROC``
when set (CPU-simulated meshes in tests), else from :func:`probe_devices`
— a throwaway child process. A TPU chip belongs to one process at a
time, so a control-plane process that initialised a JAX backend would own
the chip its workers need: nothing in this module imports jax.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List, NamedTuple, Optional, Tuple

from edl_tpu.utils.log import get_logger

logger = get_logger("cluster.job_env")

MAX_PODS = 1024  # reference caps the elastic window at 1024 nodes


def _parse_nodes_range(spec: str) -> Tuple[int, int]:
    """Parse "min:max" / "n" (fixed) elastic node windows."""
    if ":" in spec:
        lo_s, hi_s = spec.split(":", 1)
        lo, hi = int(lo_s), int(hi_s)
    else:
        lo = hi = int(spec)
    if not (1 <= lo <= hi <= MAX_PODS):
        raise ValueError("invalid nodes range %r" % spec)
    return lo, hi


def job_identity(
    default_job: str = "", default_pod: str = ""
) -> Tuple[str, str]:
    """``(job_id, pod_id)`` from the environment, with caller-chosen
    fallbacks for off-cluster use.

    This is the ONE place `EDL_JOB_ID`/`EDL_POD_ID` are read with a
    component-specific default: every other reader uses the empty
    string, and the env-registry lint flags conflicting literal
    defaults — the chaos trainee's ``("chaos", "nopod")`` storeless
    identity lives in its *call* here, not in a divergent env read.
    An empty env value counts as unset, matching every call site's
    ``env.get(...) or fallback`` behavior before this helper existed."""
    env = os.environ
    return (
        env.get("EDL_JOB_ID", "") or default_job,
        env.get("EDL_POD_ID", "") or default_pod,
    )


class LocalDevices(NamedTuple):
    """What one process finds when it initialises jax on this host."""

    count: int
    platform: str   # jax.default_backend(): "tpu", "cpu", ...
    kind: str       # jax.devices()[0].device_kind ("" when not asked)


_PROBE = (
    "import json, jax; print(json.dumps({'platform': jax.default_backend(), "
    "'count': jax.local_device_count(), "
    "'kind': jax.local_devices()[0].device_kind}))"
)


def probe_devices(
    env: Optional[Dict[str, str]] = None, timeout: float = 300.0
) -> LocalDevices:
    """The devices a worker spawned with ``env`` would see.

    ``EDL_DEVICES_PER_PROC`` answers without a process (the platform is
    then whatever ``JAX_PLATFORMS`` names, possibly ""). Otherwise a child
    interpreter initialises the backend, prints the answer and EXITS —
    releasing the chips — before this returns, so call it before anything
    that needs a device is spawned. A child that cannot reach a device is
    an error here, not a default: the workers would fail the same way."""
    env = dict(os.environ if env is None else env)
    override = env.get("EDL_DEVICES_PER_PROC")
    if override:
        return LocalDevices(
            int(override), env.get("JAX_PLATFORMS", "").strip().lower(), ""
        )
    try:
        out = subprocess.run(
            [sys.executable, "-c", _PROBE], env=env, timeout=timeout,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
    except subprocess.TimeoutExpired:
        raise RuntimeError(
            "device probe did not answer in %.0fs — is another process "
            "holding the accelerator?" % timeout
        ) from None
    if out.returncode != 0:
        raise RuntimeError(
            "device probe failed (exit %d): %s"
            % (out.returncode, out.stderr.strip()[-2000:])
        )
    found = json.loads(out.stdout.strip().splitlines()[-1])
    return LocalDevices(
        int(found["count"]), str(found["platform"]), str(found["kind"])
    )


def default_compile_cache_dir() -> str:
    """The one compile-cache home when nobody placed it: inside the
    checkout (git-ignored), the same for every job id, user and run. The
    directory's path is part of every cache key, so a cache that moves
    never hits."""
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(checkout, ".cache", "xla")


class JobEnv:
    """Launcher-side job configuration (args override env)."""

    def __init__(
        self,
        job_id: Optional[str] = None,
        store_endpoint: Optional[str] = None,
        nodes_range: Optional[str] = None,
        nproc_per_node: Optional[int] = None,
        log_dir: Optional[str] = None,
        ckpt_path: Optional[str] = None,
        compile_cache_dir: Optional[str] = None,
    ) -> None:
        env = os.environ
        self.job_id = job_id or env.get("EDL_JOB_ID", "")
        if not self.job_id:
            raise ValueError("job_id required (flag --job_id or env EDL_JOB_ID)")
        self.store_endpoint = store_endpoint or env.get("EDL_STORE_ENDPOINT", "")
        self.min_nodes, self.max_nodes = _parse_nodes_range(
            nodes_range or env.get("EDL_NODES_RANGE", "1:%d" % MAX_PODS)
        )
        self.nproc_per_node = int(
            nproc_per_node or env.get("EDL_NPROC_PER_NODE", "1")
        )
        self.log_dir = log_dir or env.get("EDL_LOG_DIR", "")
        self.ckpt_path = ckpt_path or env.get("EDL_CKPT_PATH", "")
        # Persistent XLA compilation cache shared by every worker the job
        # ever spawns. Stop-resume elasticity restarts all JAX processes
        # per resize; without this each stage recompiles from scratch and
        # spawn->first-step dominates resize downtime. Where the operator
        # placed JAX's cache (JAX_COMPILATION_CACHE_DIR) that IS the cache
        # — jax reads the variable itself, workers inherit it, and the
        # exchange and the ladder must scan the same place. Otherwise the
        # flag/env, else one fixed default; "none" disables.
        placed = env.get("JAX_COMPILATION_CACHE_DIR", "")
        if placed:
            compile_cache_dir = placed
        elif compile_cache_dir is None:
            compile_cache_dir = env.get("EDL_COMPILE_CACHE_DIR", "")
        if not compile_cache_dir:
            compile_cache_dir = default_compile_cache_dir()
        self.compile_cache_dir = (
            "" if compile_cache_dir == "none" else compile_cache_dir
        )

    def __repr__(self) -> str:
        return (
            "JobEnv(job_id=%r, store=%r, nodes=%d:%d, nproc=%d)"
            % (
                self.job_id,
                self.store_endpoint,
                self.min_nodes,
                self.max_nodes,
                self.nproc_per_node,
            )
        )


class WorkerEnv:
    """Worker-process-side view of the env injected by the process manager.

    The training entrypoint reads this (via :func:`edl_tpu.train.init`) to
    join the job: global rank, world size, the JAX coordinator endpoint,
    and the stage token of the cluster generation it belongs to.
    """

    VARS = (
        "EDL_JOB_ID",
        "EDL_POD_ID",
        "EDL_STAGE",
        "EDL_WORKER_RANK",
        "EDL_WORKER_RANK_IN_POD",
        "EDL_NUM_WORKERS",
        "EDL_COORDINATOR",
        "EDL_WORKER_ENDPOINTS",
        "EDL_STORE_ENDPOINT",
        "EDL_CKPT_PATH",
        "EDL_CKPT_LOCAL_DIR",
        "EDL_COMPILE_CACHE_DIR",
        "EDL_NODES_RANGE",
        "EDL_NPROC_PER_NODE",
    )

    def __init__(self) -> None:
        env = os.environ
        self.job_id = env.get("EDL_JOB_ID", "")
        self.pod_id = env.get("EDL_POD_ID", "")
        self.stage = env.get("EDL_STAGE", "")
        self.global_rank = int(env.get("EDL_WORKER_RANK", "0"))
        self.rank_in_pod = int(env.get("EDL_WORKER_RANK_IN_POD", "0"))
        self.world_size = int(env.get("EDL_NUM_WORKERS", "1"))
        self.coordinator = env.get("EDL_COORDINATOR", "")
        self.worker_endpoints: List[str] = [
            e for e in env.get("EDL_WORKER_ENDPOINTS", "").split(",") if e
        ]
        self.store_endpoint = env.get("EDL_STORE_ENDPOINT", "")
        self.ckpt_path = env.get("EDL_CKPT_PATH", "")
        # pod-local checkpoint tier (checkpoint/replicate.py): derived
        # per pod by the launcher from EDL_CKPT_LOCAL_BASE; empty = the
        # classic single-tier layout where ckpt_path is the only dir
        self.ckpt_local_dir = env.get("EDL_CKPT_LOCAL_DIR", "")
        self.compile_cache_dir = env.get(
            "JAX_COMPILATION_CACHE_DIR", ""
        ) or env.get("EDL_COMPILE_CACHE_DIR", "")
        # the elastic window, worker-visible (the AOT resize ladder
        # derives its neighbor worlds from it). Absent or malformed =
        # a window pinned to the current world — the ladder is a no-op.
        try:
            self.nproc_per_node = max(1, int(env.get("EDL_NPROC_PER_NODE", "1") or 1))
        except ValueError:
            self.nproc_per_node = 1
        pods = max(1, self.world_size // self.nproc_per_node)
        try:
            self.min_nodes, self.max_nodes = _parse_nodes_range(
                env["EDL_NODES_RANGE"]
            )
        except (KeyError, ValueError):
            self.min_nodes = self.max_nodes = pods

    @property
    def is_rank0(self) -> bool:
        return self.global_rank == 0

    @staticmethod
    def present() -> bool:
        """True when running under the edl_tpu launcher."""
        return "EDL_WORKER_RANK" in os.environ and "EDL_JOB_ID" in os.environ
