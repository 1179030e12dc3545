"""Worker-side job bootstrap: join the distributed runtime, barrier.

This is the TPU-native seam the reference fills with Paddle fleet init:
where ``fleet.init(PaddleCloudRoleMaker)`` reads ``PADDLE_TRAINER_*`` env
set by the launcher and bootstraps NCCL (reference
example/collective/resnet50/train_with_fleet.py:377 + edl_process.py:54-62),
:func:`init` reads the ``EDL_*`` contract set by
:mod:`edl_tpu.launch.process` and drives ``jax.distributed.initialize``
with the published coordinator, so XLA collectives ride ICI/DCN.

Each elastic stage restarts worker processes, so ``init`` is always a
fresh-process bootstrap — the reference's stop-resume trick is what makes
coordinator handoff tractable (SURVEY §7 hard parts: the new stage's rank 0
hosts a fresh coordinator service on its own endpoint).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

from edl_tpu.cluster.job_env import WorkerEnv
from edl_tpu.utils.exceptions import EdlBarrierError
from edl_tpu.utils.log import get_logger

logger = get_logger("train.context")

_env: Optional[WorkerEnv] = None
_distributed_up = False  # jax.distributed bootstrapped by a previous init()

from edl_tpu.cluster.contract import (  # shared with launch/launcher.py
    CLUSTER_SERVICE,
    DRAIN_SERVICE,
    DRAINED_EXIT,
    HEARTBEAT_SERVICE,
    HOT_RESTAGE_EXIT,
    HOTADOPT_SERVICE,
    PREEMPT_SERVICE,
)


def hot_restage_enabled() -> bool:
    """True when the job runs in hot-restage mode (``EDL_HOT_RESTAGE=1``):
    surviving workers adopt new stages IN-PROCESS instead of being killed
    and respawned — jax.distributed shutdown/initialize cycle, mesh
    rebuild, checkpoint restore — skipping the interpreter+import+compile
    cold start that dominates measured stop-resume downtime."""
    return os.environ.get("EDL_HOT_RESTAGE") == "1"


def enable_compilation_cache(path: str) -> None:
    """Arm XLA's persistent compilation cache at ``path``.

    The resize-cost lever: stop-resume elasticity restarts every JAX
    process per stage, and without a persistent cache each incarnation
    recompiles the train step from scratch. With one cache dir shared by
    every incarnation the SECOND visit to any world size loads the
    executable instead of compiling it. Thresholds drop to zero so even
    small test/CPU computations cache. Must run before the first
    computation; safe to call again.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and ``path`` is
    ignored: jax read the variable at import, and nothing here sets a
    directory. A directory that cannot be used is an ERROR, never a quiet
    uncached run — a job that wants none says so (``--compile_cache_dir
    none``). No ownership or mode test: the default lives inside the
    checkout (whoever can write there can already change the code), and
    any other directory was placed by the operator.
    """
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if placed:
        path = placed
    try:
        os.makedirs(path, mode=0o700, exist_ok=True)
        probe = os.path.join(path, ".edl_probe_%d" % os.getpid())
        with open(probe, "w"):
            pass
        os.unlink(probe)
    except OSError as exc:
        raise RuntimeError(
            "compilation cache dir %s is unusable (%s): place the cache "
            "with JAX_COMPILATION_CACHE_DIR, or run uncached on purpose "
            "with --compile_cache_dir none" % (path, exc)
        ) from exc
    if not placed:
        jax.config.update("jax_compilation_cache_dir", path)
    elif jax.config.jax_compilation_cache_dir != placed:
        raise RuntimeError(
            "JAX_COMPILATION_CACHE_DIR=%s was set after jax was imported "
            "(jax caches in %r): set it in the environment the process "
            "starts with" % (placed, jax.config.jax_compilation_cache_dir)
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax would otherwise arm XLA's GPU autotune cache UNDER the cache
    # dir, and that path rides the compile options into every cache key —
    # two pods with different cache paths could never share an entry
    jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
    if os.environ.get("EDL_CACHE_ALL_RANKS", "1") == "1":
        _enable_all_rank_cache_writes()
    # AOT resize plane (train/aot.py): topology-independent cache keys —
    # without them an entry the ladder compiles inside an N-process world
    # can never be hit by the N±1 incarnation it was compiled FOR — and
    # the hit/miss/write counters resize_bench and the monitor read.
    from edl_tpu.train import aot as _aot

    _aot.enable_portable_cache_keys()
    _aot.instrument_compilation_cache()
    _aot.instrument_compile_spans()
    _aot.instrument_backend_init()


def _enable_all_rank_cache_writes() -> None:
    """Let EVERY process persist its compiled executables, not just rank 0.

    JAX hard-codes "only process 0 writes cache entries" to avoid write
    contention on shared filesystems like GCS — but cache keys include
    the process index, so in a multi-process job ranks >= 1 can never
    hit entries written by rank 0 and, with the default gate, nothing
    ever writes theirs: every elastic restage pays a full recompile on
    every non-zero rank, forever. On a host-local (or per-process-keyed)
    cache dir the contention rationale doesn't apply — distinct keys
    mean distinct files. This replaces ``jax._src.compiler._cache_write``
    (a private seam of the pinned jax 0.9.0, signature asserted in
    tests/test_chip_smoke.py) with a copy that drops only that gate;
    ``EDL_CACHE_ALL_RANKS=0`` opts out.
    """
    import functools
    import types

    from jax._src import compiler as _compiler

    orig = _compiler._cache_write
    if getattr(orig, "_edl_all_ranks", False):
        return
    real_distributed = _compiler.distributed

    class _GSView:
        """global_state view reporting process_id 0 (write-gate only)."""

        def __init__(self, gs):
            self._gs = gs

        process_id = 0

        def __getattr__(self, name):
            return getattr(self._gs, name)

    class _DistView:
        @property
        def global_state(self):
            return _GSView(real_distributed.global_state)

        def __getattr__(self, name):
            return getattr(real_distributed, name)

    # A COPY of the function whose `distributed` global resolves to the
    # view: no runtime module mutation, no cross-thread effect on other
    # compiler-module code.
    patched = types.FunctionType(
        orig.__code__,
        {**orig.__globals__, "distributed": _DistView()},
        orig.__name__,
        orig.__defaults__,
        orig.__closure__,
    )
    patched = functools.wraps(orig)(patched)
    patched._edl_all_ranks = True
    _compiler._cache_write = patched


_cache_pulled = False


def _pull_cache_entries(env: WorkerEnv) -> None:
    """Bounded best-effort compile-cache pull at stage init (before the
    first jit): diff peer manifests, fetch entries any pod already
    compiled. Once per process — a hot restage re-runs init() but the
    cache dir it already pulled into is still warm; the standby shell
    sets ``EDL_CACHE_PULLED`` after its own (earlier, overlapped) pull
    for the same reason. Never raises, never blocks past the budget:
    the exchange is a perf lever, not a correctness gate."""
    global _cache_pulled
    if (
        _cache_pulled
        or os.environ.get("EDL_CACHE_PULLED") == "1"
        or os.environ.get("EDL_CACHE_EXCHANGE", "1") == "0"
        or not env.store_endpoint
        or not env.job_id
    ):
        return
    _cache_pulled = True
    try:
        from edl_tpu.train import aot as _aot

        _aot.pull_missing(
            env.compile_cache_dir,
            endpoint=env.store_endpoint,
            job_id=env.job_id,
            own_pod=env.pod_id,
        )
    except Exception as exc:  # noqa: BLE001
        logger.warning("compile-cache pull failed: %s", exc)


_boot_recorded = False


def _record_boot_span(obs_trace) -> None:
    """Once per process: a ``worker_boot`` restage-trace segment from the
    launcher's spawn stamp (``EDL_SPAWN_TS``) to now: fork and exec, then
    what the process itself saw of its start, the ring's ``process_boot`` (the
    OS's start of the process -> the package's first statement) and the
    ``package_import`` spans taken so far, which become its children in the
    restage trace. Skipped on hot restages (the process was not respawned,
    the stamp is stale)."""
    global _boot_recorded
    if _boot_recorded:
        return
    _boot_recorded = True
    raw = os.environ.get("EDL_SPAWN_TS", "")
    if not raw:
        return
    try:
        age = time.time() - float(raw)
    except ValueError:
        return
    if not 0.0 < age < 3600.0:
        return  # a clock step or an inherited stale stamp: drop it
    obs_trace.get_tracer().record_over(
        "worker_boot", time.monotonic() - age, age, obs_trace.BOOT_SPANS
    )


_obs_registered: Optional[tuple] = None


def _mount_obs(env: WorkerEnv) -> None:
    """Worker-side observability mount: /metrics + /healthz (gated on
    ``EDL_OBS_PORT``) plus endpoint registration in the job's obs
    keyspace so ``edl-top`` finds every worker. Re-registers when the
    (stage, rank) changes — a hot restage can move this process to a new
    rank. Never raises: obs must not break worker bootstrap."""
    global _obs_registered
    try:
        from edl_tpu.obs import http as obs_http

        server = obs_http.start_from_env(
            "worker",
            health_fn=lambda: {
                "rank": current_env().global_rank,
                "world": current_env().world_size,
                "stage": current_env().stage[:8],
            },
        )
        if server is None or not env.store_endpoint or not env.job_id:
            return
        key = (env.stage, env.global_rank)
        if _obs_registered == key:
            return
        from edl_tpu.store.client import connect_store

        client = connect_store(env.store_endpoint, timeout=2.0)
        try:
            obs_http.register_endpoint(
                client, env.job_id, "worker", "w%d" % env.global_rank,
                server.endpoint,
            )
        finally:
            client.close()
        _obs_registered = key
    except Exception as exc:  # noqa: BLE001
        logger.warning("worker obs mount failed: %s", exc)


def init(env: Optional[WorkerEnv] = None) -> WorkerEnv:
    """Join the job: returns the worker env; in multi-worker stages also
    initializes ``jax.distributed`` (rank 0's endpoint is the coordinator).

    Idempotent per process: user scripts call it for the env, and
    ``ElasticTrainer.fit`` calls it again — only the first call
    bootstraps ``jax.distributed`` (a second bootstrap is a hard error
    upstream). Stop-resume gives every stage a fresh process, so the
    guard can never carry across stages.
    """
    global _env, _distributed_up
    env = env or WorkerEnv()
    _env = env
    _mount_obs(env)
    # goodput: from process start (stop-resume respawn) or in-process
    # re-init until training resumes, the wall-clock is restage cost
    from edl_tpu.obs import goodput as obs_goodput

    obs_goodput.enter("restage", cause="init")
    if env.stage:
        # distributed tracing: this worker's whole restage window —
        # boot, cache pull, jax.distributed join, restore, first jit
        # — stitches into the stage's restage trace (trace id derives
        # from the stage token, the key every participant shares).
        # Idempotent for the same stage; the step loop ends the op at
        # the first completed step.
        from edl_tpu.obs import trace as obs_trace

        # the tracer (and its process_boot) before the operation opens:
        # worker_boot adopts what was taken before it, the root what comes after
        obs_trace.get_tracer()
        obs_trace.begin_process_op(
            "restage", env.stage, rank=str(env.global_rank)
        )
        _record_boot_span(obs_trace)
    if env.compile_cache_dir:
        enable_compilation_cache(env.compile_cache_dir)
        _pull_cache_entries(env)
    else:
        # an uncached worker's start is traced all the same: jax's trace,
        # lower and compile events as spans under the restage operation
        from edl_tpu.train import aot as _aot

        _aot.instrument_compile_spans()
        _aot.instrument_backend_init()
    if _distributed_up:
        return env
    if env.world_size > 1 and env.coordinator:
        import jax

        logger.info(
            "worker %d/%d joining stage %s (coordinator %s)",
            env.global_rank,
            env.world_size,
            env.stage[:8] or "-",
            env.coordinator,
        )
        try:
            # restage-trace segment: the distributed join can dominate a
            # restage (it barriers on the slowest joiner's cold start)
            from edl_tpu.obs import trace as obs_trace

            with obs_trace.child_span(
                "dist_init", world=str(env.world_size)
            ):
                jax.distributed.initialize(
                    coordinator_address=env.coordinator,
                    num_processes=env.world_size,
                    process_id=env.global_rank,
                )
            _distributed_up = True
        except RuntimeError as exc:
            if "must be called before" in str(exc):
                raise RuntimeError(
                    "jax was initialised before joining the multi-worker "
                    "stage: build device arrays only AFTER init()/fit() "
                    "(e.g. pass numpy arrays as ElasticTrainer sample_input)"
                ) from exc
            raise
    return env


def current_env() -> WorkerEnv:
    return _env if _env is not None else WorkerEnv()


# -- hot restage (in-process stage adoption) --------------------------------


class StageMonitor:
    """Worker-side watch of the job's drain token and published cluster.

    The stop-resume contract learns about stage changes by being killed;
    a hot-restage worker learns by watching the same store keys the
    launcher does: a drain-token bump ≠ my stage sets ``restage_pending``
    (checked between train steps — never inside compiled code), and
    ``wait_for_my_stage`` then blocks until the leader publishes the new
    generation. ``mark_adopted`` reports success back to the launcher,
    which kills+respawns any worker that misses its adoption deadline
    (the dirty fallback: a peer death can leave this process wedged in a
    collective, where only the runtime's own abort or the launcher's
    kill can recover it)."""

    def __init__(self, env: WorkerEnv) -> None:
        from edl_tpu.discovery.registry import Registry
        from edl_tpu.store.client import connect_store

        self._client = connect_store(env.store_endpoint, timeout=10.0)
        self._registry = Registry(self._client, env.job_id)
        self._stage = env.stage
        self._changed = threading.Event()
        self._drain = self._registry.watch_service(
            DRAIN_SERVICE, on_change=self._on_change
        )
        self._cluster = self._registry.watch_service(
            CLUSTER_SERVICE, on_change=self._on_change
        )
        self._on_change()

    def _token(self) -> str:
        meta = self._drain.snapshot().get("token")
        return meta.value.decode() if meta else ""

    def _on_change(self, _snapshot=None) -> None:
        token = self._token()
        if token and token != self._stage:
            self._changed.set()

    @property
    def restage_pending(self) -> bool:
        return self._changed.is_set()

    def wait_for_my_stage(self, pod_id: str, timeout: float = 20.0):
        """Block until the CURRENT token's generation is published with
        ``pod_id`` in it; returns the Cluster, or None when this pod is
        excluded from the generation or nothing converges in time."""
        from edl_tpu.cluster.model import Cluster

        deadline = time.time() + timeout
        while time.time() < deadline:
            token = self._token()
            meta = self._cluster.snapshot().get("current")
            if token and meta is not None:
                cluster = Cluster.from_json(meta.value)
                if cluster.stage == token:
                    return cluster if cluster.get_pod(pod_id) else None
            time.sleep(0.05)
        return None

    def arm(self, stage: str) -> None:
        """Reset for a newly adopted stage (and immediately re-flag if the
        token has already moved past it)."""
        self._stage = stage
        self._changed.clear()
        self._on_change()

    def mark_adopted(self, pod_id: str, rank_in_pod: int, stage: str) -> None:
        self._registry.set_permanent(
            HOTADOPT_SERVICE, "%s.%d" % (pod_id, rank_in_pod), stage.encode()
        )

    def close(self) -> None:
        for watch in (self._drain, self._cluster):
            try:
                watch.cancel()
            except Exception:
                pass
        self._client.close()


# -- health plane (graceful drain + progress heartbeat) ----------------------


class HealthMonitor:
    """Worker-side half of the health plane.

    Watches the job's ``preempt/{pod_id}`` key — published by the launcher
    when it receives an advance preemption notice (SIGTERM/SIGUSR1), or by
    an operator directly — and exposes the drain deadline so the training
    loop can take an emergency checkpoint between steps and exit with
    ``DRAINED_EXIT``. Also publishes the per-step progress heartbeat
    (``heartbeat/{pod_id}.{rank_in_pod}``) the launcher-side straggler
    watchdog reads; publication is throttled to ``EDL_HEARTBEAT_EVERY``
    seconds (default 1.0) and strictly fire-and-forget — a sick store must
    never stall a training step.

    Notice delivery is belt-and-suspenders: the watch is the fast path,
    and :meth:`heartbeat` re-reads the pod's own preempt key about once a
    second — a watch event lost to a reconnect race costs at most that
    second, never the whole drain window.
    """

    _POLL_EVERY = 1.0  # direct preempt-key read cadence (watch-miss floor)

    def __init__(self, env: WorkerEnv, min_interval: Optional[float] = None) -> None:
        from edl_tpu.discovery.registry import Registry
        from edl_tpu.store.client import connect_store

        self._env = env
        self._client = connect_store(env.store_endpoint, timeout=2.0)
        self._registry = Registry(self._client, env.job_id or "job")
        self._hb_key = "/%s/%s/%s.%d" % (
            env.job_id, HEARTBEAT_SERVICE, env.pod_id, env.rank_in_pod,
        )
        self._preempt_key = "/%s/%s/%s" % (
            env.job_id, PREEMPT_SERVICE, env.pod_id,
        )
        if min_interval is None:
            min_interval = float(os.environ.get("EDL_HEARTBEAT_EVERY", "1.0"))
        self._min_interval = min_interval
        self._last_pub = 0.0
        self._last_poll = 0.0
        self._backoff_until = 0.0
        self._deadline: Optional[float] = None
        self._noticed = threading.Event()
        self._watch = self._registry.watch_service(
            PREEMPT_SERVICE, on_change=self._on_change
        )
        self._on_change(self._watch.snapshot())

    @property
    def store_client(self):
        """The health plane's store client, shared with sibling
        best-effort planes (the numerics digest exchange) so one worker
        holds one store connection, not one per observer."""
        return self._client

    def _apply_notice(self, value: bytes) -> None:
        import json as _json

        try:
            payload = _json.loads(value)
            deadline = float(payload.get("deadline", 0)) or None
        except (ValueError, TypeError):
            deadline = None
        self._deadline = deadline
        self._noticed.set()

    def _on_change(self, snapshot=None) -> None:
        if snapshot is None:
            snapshot = self._watch.snapshot()
        meta = snapshot.get(self._env.pod_id)
        if meta is None:
            return
        self._apply_notice(meta.value)

    @property
    def drain_notice(self) -> bool:
        """True once this pod has been told to drain."""
        return self._noticed.is_set()

    @property
    def drain_deadline(self) -> Optional[float]:
        """Wall-clock deadline of the notice (None = no notice, or one
        without a parseable deadline — drain immediately, best effort)."""
        return self._deadline

    def drain_budget_left(self, floor: float = 0.5) -> float:
        """Seconds the emergency checkpoint may still spend."""
        if self._deadline is None:
            return floor
        return max(floor, self._deadline - time.time())

    def heartbeat(self, step: int, dt: float = 0.0) -> None:
        """Publish step progress (throttled, fire-and-forget)."""
        now = time.time()
        if now < self._backoff_until:
            return
        if not self._noticed.is_set() and now - self._last_poll >= self._POLL_EVERY:
            # watch-miss insurance: one direct read of the preempt key
            self._last_poll = now
            try:
                raw = self._client.get(self._preempt_key)
                if raw is not None:
                    self._apply_notice(raw)
            except Exception as exc:  # noqa: BLE001 — never stall a step
                self._backoff_until = now + 5.0
                logger.debug("preempt poll failed: %s", exc)
                return
        if now - self._last_pub < self._min_interval:
            return
        import json as _json

        try:
            self._client.put(
                self._hb_key,
                _json.dumps(
                    {
                        "step": int(step),
                        "ts": now,
                        "dt": round(float(dt), 4),
                        "stage": self._env.stage,
                    }
                ).encode(),
            )
            self._last_pub = now
        except Exception as exc:  # noqa: BLE001 — never stall a train step
            self._backoff_until = now + 5.0
            logger.debug("heartbeat publish failed: %s", exc)

    def record_drained(self, step: int) -> None:
        """Best-effort 'drained' telemetry event + final heartbeat, written
        right before the worker exits with ``DRAINED_EXIT``."""
        from edl_tpu.obs import events as obs_events
        from edl_tpu.obs import goodput as obs_goodput
        from edl_tpu.obs import trace as obs_trace
        from edl_tpu.utils import telemetry

        obs_goodput.enter("drain", cause="preempt")
        # the drain op's closing segment (zero-duration anchor): marks
        # the trace complete for edl-trace even when no emergency save
        # ran (multi-pod partial drains skip it — Orbax is collective)
        obs_trace.get_tracer().record(
            "drained", time.monotonic(), 0.0, step=str(step)
        )
        obs_events.record(
            "drained", fsync=True, step=step,
            pod=self._env.pod_id, rank=self._env.global_rank,
        )
        self._min_interval = 0.0  # the exit heartbeat must not be throttled
        self._backoff_until = 0.0
        self.heartbeat(step)
        telemetry.record_event(
            self._client, self._env.job_id, self._env.stage, "drained",
            "w%d" % self._env.global_rank,
        )

    def close(self) -> None:
        try:
            self._watch.cancel()
        except Exception:  # noqa: BLE001
            pass
        self._client.close()


def reinit_for_stage(cluster, pod_id: str, rank_in_pod: int) -> WorkerEnv:
    """Adopt ``cluster``'s stage in-process: recompute this worker's env
    from the published generation, tear down the old distributed runtime
    and backends, and re-run :func:`init`.

    After this returns, every jax Array and compiled function from the
    previous stage is dead weight — callers rebuild mesh/state/steps from
    scratch (the persistent compile cache makes the re-jit a load, not a
    compile). Raises on anything dirty; callers translate that into a
    ``HOT_RESTAGE_EXIT`` respawn request.
    """
    global _distributed_up
    from edl_tpu.obs import goodput as obs_goodput

    obs_goodput.enter("restage", cause="hot_restage")
    pod = cluster.get_pod(pod_id)
    if pod is None:
        raise RuntimeError("pod %s not in stage %s" % (pod_id, cluster.stage))
    worker = next(
        (w for w in pod.workers if w.rank_in_pod == rank_in_pod), None
    )
    if worker is None:
        raise RuntimeError(
            "rank_in_pod %d not in pod %s for stage %s"
            % (rank_in_pod, pod_id, cluster.stage)
        )
    os.environ.update(
        {
            "EDL_STAGE": cluster.stage,
            "EDL_WORKER_RANK": str(worker.global_rank),
            "EDL_NUM_WORKERS": str(cluster.world_size),
            "EDL_COORDINATOR": cluster.coordinator,
            "EDL_WORKER_ENDPOINTS": ",".join(cluster.worker_endpoints()),
        }
    )

    import jax

    if _distributed_up:
        jax.distributed.shutdown()
        _distributed_up = False
    jax.clear_caches()
    # backends hold the old distributed client; initialize() refuses to
    # run while they exist. Private API by necessity — guarded so drift
    # degrades to the respawn fallback instead of undefined behavior.
    from jax._src import xla_bridge

    xla_bridge._clear_backends()
    if xla_bridge.backends_are_initialized():
        raise RuntimeError("jax backends survived _clear_backends()")
    new_env = WorkerEnv()
    logger.info(
        "hot restage: adopting stage %s as rank %d/%d (coordinator %s)",
        new_env.stage[:8],
        new_env.global_rank,
        new_env.world_size,
        new_env.coordinator,
    )
    return init(new_env)


_barrier_rounds: dict = {}


def worker_barrier(name: str, timeout: float = 600.0, ttl: float = 10.0) -> None:
    """Control-plane barrier across all workers of the current stage.

    Capability parity with the reference's leader-hosted ``Barrier`` RPC
    (python/edl/utils/pod_server.py:63, pod_client.py:37), built on the
    store instead of a dedicated server: every worker registers
    ``barrier/{stage}:{name}#{round}/{rank}`` (leased) and waits until all
    ``world_size`` ranks are present. The per-process round counter makes
    the same barrier name reusable back-to-back: keys from round N (left
    to lease expiry) can never satisfy round N+1. All ranks hit barriers
    in program order, so counters agree across processes; a restarted
    worker resets to round 0 together with everyone else because restarts
    only happen at stage changes and the stage is part of the key.
    """
    env = current_env()
    if env.world_size <= 1 or not env.store_endpoint:
        return
    from edl_tpu.discovery.registry import Registry
    from edl_tpu.store.client import connect_store

    round_key = (env.stage, name)
    seq = _barrier_rounds.get(round_key, 0)
    _barrier_rounds[round_key] = seq + 1
    service = "barrier/%s:%s#%d" % (env.stage or "static", name, seq)
    client = connect_store(env.store_endpoint, timeout=min(timeout, 30.0))
    try:
        registry = Registry(client, env.job_id or "job")
        # push-based wait: the store watch wakes us on every membership
        # change (the reference polls its leader barrier RPC at ~3 Hz,
        # pod_client.py:37; early rounds here polled at 20 Hz)
        full = threading.Event()
        seen = [0]

        def on_change(snapshot):
            seen[0] = len(snapshot)
            if len(snapshot) >= env.world_size:
                full.set()

        watch = registry.watch_service(service, on_change=on_change)
        reg = registry.register(service, str(env.global_rank), b"1", ttl=ttl)
        try:
            if not full.wait(timeout):
                raise EdlBarrierError(
                    "barrier %r timed out: %d/%d workers"
                    % (name, seen[0], env.world_size)
                )
        finally:
            watch.cancel()
            reg.stop(delete=False)  # leave the key; lease expiry cleans up
    finally:
        client.close()
