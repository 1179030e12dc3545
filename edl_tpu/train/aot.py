"""AOT resize ladder + cluster-shared compile-cache exchange.

The dominant restage cost on TPU is XLA recompilation for the new mesh
shape (12-28 s per resize, bench_results/resize_tpu_r4b.json) — yet the
elastic window makes every resize target enumerable, and pjit binds the
mesh at *call site*, not trace time: nothing stops a live worker from
compiling the N±1/N±2 executables while training runs. Three pieces make
the post-resize re-jit a cache load instead of a compile:

**Portable cache keys** (:func:`enable_portable_cache_keys`). JAX's
persistent-cache key hashes the *backend topology* (process count,
global device set), so an entry compiled inside an N-process world can
never be hit by an (N-1)-process incarnation even when the program, the
compile options and the program's own devices are identical — measured:
the same world-1 step gets a different key in every topology it is
compiled from. The patch re-keys the accelerator-config component to
the *program's* device kinds (JAX's own documented fallback for backends
without serializable topology), making the key a pure function of (HLO,
compile options, device kinds, platform). The key's other host-bound
part — the per-fusion-autotune-cache *path* jax arms under the local
cache dir, which rides the compile options into the hash — is switched
off with a public option (``enable_compilation_cache``). Proven on
the CPU rig: a world-1 entry compiled from inside a 2-process world is
hit byte-for-byte by a real world-1 job. Written against the private
seams of the pinned jax 0.9.0 (signatures asserted in
tests/test_chip_smoke.py), env opt-out, CPU-only by default
(``EDL_CACHE_PORTABLE_KEYS=all`` extends it to TPU, which no chip run has
tried; topology-keyed entries are the conservative default where real ICI
topology differences could matter).

**The AOT ladder** (:class:`AotLadder`). Once a stage reaches steady
state (first step done), a low-priority background thread compiles the
train step for the anticipated neighbor world sizes — pods ±1 and ±2
inside the elastic window, nearest first — via
``jit(...).lower(shapes).compile()`` with ``ShapeDtypeStruct`` avals
scaled to each target world, populating the persistent cache every
incarnation already points at. Only *shrink* shapes are compilable
in-process (a grow mesh needs devices this process cannot see; those
ride the exchange below), and only by a worker whose local device sits
in the target sub-mesh. Sizes are claimed through the store (a key
leased while compiling, rewritten to a permanent ``done:`` on success,
released on failure) so co-hosted pods never compile the same shape
twice. Ladder time is attributed to the new
``aot_compile`` goodput state on its own flight-recorder lane
(component ``aot``) — never the ``train`` lane.

**The cache exchange** (:class:`CacheExchange` / :func:`pull_missing`).
Portable keys make entries *host-portable*, so no pod ever needs to
compile what any peer already paid for: each launcher publishes a
sha256 digest manifest of its local cache entries under
``compile_cache/{pod}`` and serves entry bytes over the wire protocol;
a restaging or newly joined pod diffs manifests against its local dir
and pulls what is missing — from ``train.init()`` (bounded, before the
first jit) and from the standby shell's activation path (where the
pull overlaps the control-plane convergence window). A corrupted or
dropped pull degrades to a normal compile, never a wedged worker:
every entry is digest-verified before an atomic rename into the cache
dir, and the whole pull is deadline-bounded and exception-contained
(chaos point ``store.cache.exchange`` drills exactly this).

Observability: ``edl_train_aot_compiles_total{outcome}``,
``edl_train_cache_exchange_bytes_total{dir}``,
``edl_train_compile_cache_events_total{kind}`` (hit/miss/write, from
the instrumented persistent-cache read/write seam),
``edl_train_restage_compile_seconds`` (real compile time paid between a
cache miss and its write — the number speculation exists to zero), and
``aot``/``exchange`` flight records so edl-timeline shows the
speculation paying off. In the span ring: ``cache_load`` (the persistent
cache's read, with ``module``, ``hit``, ``ladder``) and jax's own
``jit_trace`` / ``jit_lower`` / ``jit_compile`` events
(:func:`instrument_compile_spans`), which is how a worker's first step
reads as trace, lowering and load on a restage's critical path.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import socket
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from edl_tpu.chaos.plane import fault_point as _fault_point
from edl_tpu.obs import events as obs_events
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import trace as obs_trace
from edl_tpu.utils.log import get_logger

logger = get_logger("train.aot")

AOT_SERVICE = "aot"                    # store claims: aot/{world}
MANIFEST_SERVICE = "compile_cache"     # store manifests: compile_cache/{pod}

_FP_COMPILE = _fault_point(
    "train.aot.compile",
    "one ladder compile: delay (slow speculative compile) or drop "
    "(compile fails; the ladder counts it and moves on)",
)
_FP_EXCHANGE = _fault_point(
    "store.cache.exchange",
    "one pulled cache entry: corrupt (digest mismatch -> entry skipped, "
    "resize degrades to a normal compile), delay, drop (peer unreachable "
    "mid-pull)",
)

class RungUnavailable(ValueError):
    """A ladder rung that can never compile here — a permanent property
    of the model/window (e.g. a sharded dim not divisible over the
    neighbor mesh), distinct from a real compile failure."""


_M_AOT = obs_metrics.counter(
    "edl_train_aot_compiles_total",
    "speculative ladder compiles, by outcome (ok/failed/skipped_grow/"
    "skipped_nonlocal/skipped_claimed/skipped_indivisible)",
)
_M_XCHG_BYTES = obs_metrics.counter(
    "edl_train_cache_exchange_bytes_total",
    "compile-cache entry bytes moved between pods, by dir (rx/tx)",
)
_M_CACHE_EVENTS = obs_metrics.counter(
    "edl_train_compile_cache_events_total",
    "persistent compile-cache events at the jit seam, by kind "
    "(hit/miss/write)",
)
_M_RESTAGE_COMPILE = obs_metrics.histogram(
    "edl_train_restage_compile_seconds",
    "real XLA compile time paid per cache miss (miss-to-write interval); "
    "zero entries here after a resize means the speculation paid off",
)

# the ladder's OWN speculative compiles go through the same instrumented
# persistent-cache seam as a restage jit — but a speculation in progress
# is the opposite of a missed one: its miss->write interval must not
# feed the restage histogram (the restage-compile-regression rule would
# fire exactly when the ladder works as designed) nor the hit/miss
# ledger resize_bench reads ("compile events = 0" means the FOREGROUND
# jit paid nothing)
_in_ladder = threading.local()


# -- portable cache keys ------------------------------------------------------

#: the scopes ``train/step.py`` names inside the step program, and a
#: count to bump when one moves. jax keys a program after stripping its
#: debug info, so a scope changes no key, and a cache written before the
#: scopes were there hands back an executable whose ``op_name``s lack
#: them (``obs/profile.py:step_phases`` would then find nothing). Mixed
#: into every key, this makes such entries miss once.
#: ``tests/test_timeline.py`` holds it to the scopes the step enters.
STEP_SCOPES_KEY = "forward,grad_mean,optimizer,numerics/2"


def enable_portable_cache_keys() -> bool:
    """Make persistent-cache keys topology-independent (see module doc),
    and mix ``STEP_SCOPES_KEY`` into every key.

    Idempotent; returns True when keys are portable. Opt out of that
    with ``EDL_CACHE_PORTABLE_KEYS=0``; ``=all`` extends it beyond CPU
    (read at every key: the scopes' constant is mixed in either way).
    Replaces ``jax._src.cache_key._hash_accelerator_config`` — a private
    seam of the pinned jax 0.9.0, whose ``(hash_obj, accelerators)``
    signature tests/test_chip_smoke.py asserts. (The key's other
    host-bound part, a filesystem path in the compile options, is switched
    off through a public option in ``enable_compilation_cache``.)
    """
    from jax._src import cache_key as _ck

    def mode() -> str:
        return os.environ.get("EDL_CACHE_PORTABLE_KEYS", "cpu").lower()

    current = _ck._hash_accelerator_config
    if not getattr(current, "_edl_portable", False):

        def _portable(hash_obj, accelerators, _orig=current):
            hash_obj.update(STEP_SCOPES_KEY.encode())
            now = mode()
            if now in ("0", "off", "none") or (
                now != "all" and accelerators.flat[0].platform != "cpu"
            ):
                return _orig(hash_obj, accelerators)
            # the program's own device kinds — JAX's documented fallback for
            # backends without serializable topology (the platform and its
            # version are a key component of their own). The device COUNT
            # and KINDS still key (a 4-device program never collides with a
            # 2-device one); what no longer keys is the process topology the
            # compile happened to run inside.
            _ck._hash_devices(hash_obj, accelerators)

        _portable._edl_portable = True
        _ck._hash_accelerator_config = _portable
    return mode() not in ("0", "off", "none")


# -- cache hit/miss instrumentation -------------------------------------------

_miss_started: Dict[str, float] = {}  # cache_key -> monotonic at miss
_miss_lock = threading.Lock()


def instrument_compilation_cache() -> bool:
    """Count persistent-cache hits/misses/writes at the jit seam.

    Wraps ``compilation_cache.get_executable_and_time`` /
    ``put_executable_and_time`` (private seams of the pinned jax 0.9.0;
    signatures asserted in tests/test_chip_smoke.py) so resize_bench, the
    monitor and chip_smoke.py can tell "cache load" from "real compile"
    without parsing logs, and times the miss→write interval into
    ``edl_train_restage_compile_seconds`` (the actual XLA compile the miss
    forced). Idempotent; opt-out with ``EDL_CACHE_EVENTS=0``.
    """
    if os.environ.get("EDL_CACHE_EVENTS", "1") == "0":
        return False
    from jax._src import compilation_cache as _cc

    orig_get = _cc.get_executable_and_time
    orig_put = _cc.put_executable_and_time
    if getattr(orig_get, "_edl_events", False):
        return True

    def get_wrapper(cache_key, compile_options, backend, executable_devices):
        # the read where it happens (file read + deserialise, and on a chip
        # the executable's load): jit_compile less this is key hashing on a
        # hit and XLA on a miss. The key is "<module>-<hash>".
        in_ladder = getattr(_in_ladder, "active", False)
        with obs_trace.span(
            "cache_load", module=cache_key.rpartition("-")[0],
            ladder=in_ladder,
        ) as load:
            found = orig_get(
                cache_key, compile_options, backend, executable_devices
            )
            load.args["hit"] = found[0] is not None
        if in_ladder:
            return found
        if found[0] is None:
            _M_CACHE_EVENTS.inc(kind="miss")
            with _miss_lock:
                _miss_started[cache_key] = time.monotonic()
        else:
            _M_CACHE_EVENTS.inc(kind="hit")
        return found

    def put_wrapper(cache_key, module_name, executable, backend,
                    compile_time):
        if not getattr(_in_ladder, "active", False):
            with _miss_lock:
                t0 = _miss_started.pop(cache_key, None)
            if t0 is not None:
                _M_RESTAGE_COMPILE.observe(time.monotonic() - t0)
            _M_CACHE_EVENTS.inc(kind="write")
        return orig_put(
            cache_key, module_name, executable, backend, compile_time
        )

    get_wrapper._edl_events = True
    put_wrapper._edl_events = True
    _cc.get_executable_and_time = get_wrapper
    _cc.put_executable_and_time = put_wrapper
    return True


# -- jax's own compile spans --------------------------------------------------

#: jax's compile events (``jax._src.dispatch.log_elapsed_time``, pinned jax
#: 0.9.0) -> the ring's span names. Each fires once a traced, lowered or
#: backend-compiled function with ``fun_name``; a backend compile is a
#: persistent-cache load on a hit (``cache_load`` lies inside it).
JAX_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit_lower",
    "/jax/core/compile/backend_compile_duration": "jit_compile",
}


#: a start traces thousands of inner ``jit``s (``jnp``'s own helpers) for well
#: under a millisecond each, all of them inside the trace of whatever called
#: them: left out, so that one start cannot push a worker's boot and restore
#: spans out of the ring (16,384 events). Lowerings and compiles are a few
#: dozen a start and all kept: a recompile is never dropped.
JIT_TRACE_FLOOR_S = 1e-3


def _on_jax_time_span(event, start_time, end_time, fun_name="", **_):
    name = JAX_COMPILE_SPANS.get(event)
    if name is None:
        return
    if name == "jit_trace" and end_time - start_time < JIT_TRACE_FLOOR_S:
        return
    obs_trace.get_tracer().record_wall(
        name, start_time, end_time, fun=str(fun_name)
    )


def instrument_compile_spans() -> None:
    """jax's trace / lower / compile events into the span ring, as
    ``jit_trace`` / ``jit_lower`` / ``jit_compile`` with ``fun``: one
    listener a process (idempotent), cache or no cache. They nest by time
    under whatever span is open (``state_init``, ``first_step``,
    ``step_relower``) and stitch into the open ``restage`` operation; a
    ``jit_compile`` after the first step is a recompile."""
    from jax import monitoring
    from jax._src import monitoring as _monitoring

    if _on_jax_time_span not in _monitoring.get_event_time_span_listeners():
        monitoring.register_event_time_span_listener(_on_jax_time_span)


#: what starts one platform's runtime (``jax._src.xla_bridge``, pinned jax
#: 0.9.0): ``backends()`` calls it once a platform, under its lock, whoever
#: asked for a device first; a hot restage's ``_clear_backends()`` makes it
#: run again
JAX_BACKEND_INIT = "_init_backend"


def instrument_backend_init() -> None:
    """``backend_init`` into the span ring, one span a platform with
    ``platform`` and ``devices``: the TPU runtime's start (and the CPU
    client's), whoever triggers it. A wrapper of :data:`JAX_BACKEND_INIT`,
    installed once (idempotent) and only ahead of the initialisation: where
    the backends are up already, or the private name is gone, nothing is
    installed and nothing recorded."""
    from jax._src import xla_bridge

    init = getattr(xla_bridge, JAX_BACKEND_INIT, None)
    if (
        init is None or getattr(init, "_edl_span", False)
        or xla_bridge.backends_are_initialized()
    ):
        return

    @functools.wraps(init)
    def traced(platform):
        with obs_trace.get_tracer().span("backend_init", platform=platform) as span:
            backend = init(platform)
            span.args["devices"] = backend.device_count()
            return backend

    traced._edl_span = True
    setattr(xla_bridge, JAX_BACKEND_INIT, traced)


def cache_event_counts() -> Dict[str, int]:
    """Snapshot of {hit, miss, write} counts this process has seen."""
    return {
        kind: int(_M_CACHE_EVENTS.value(kind=kind))
        for kind in ("hit", "miss", "write")
    }


def missed_modules() -> List[str]:
    """The modules the foreground jit looked for in the persistent cache
    and did not find, oldest first, from the ring's ``cache_load`` spans:
    what speculation (or the last run) failed to cover."""
    return [
        e["args"]["module"] for e in obs_trace.get_tracer().to_events()
        if e.get("name") == "cache_load"
        and e["args"].get("hit") is False and not e["args"].get("ladder")
    ]


# -- the AOT ladder -----------------------------------------------------------

def aot_enabled() -> bool:
    """Ladder gate: on by default wherever a compile cache is armed;
    ``EDL_AOT=0`` (resize_bench ``--no-aot``) disables."""
    return os.environ.get("EDL_AOT", "1") != "0"


def neighbor_worlds(
    world: int, nproc: int, min_nodes: int, max_nodes: int,
    depth: int = 2,
) -> List[int]:
    """The ladder's target world sizes: pods ±1..±depth inside the
    elastic window, nearest rung first, shrink before grow at equal
    distance (shrinks are what this process can compile)."""
    nproc = max(1, nproc)
    pods = world // nproc
    if pods * nproc != world:
        return []
    out: List[int] = []
    for k in range(1, depth + 1):
        for target in (pods - k, pods + k):
            if min_nodes <= target <= max_nodes and target != pods:
                w = target * nproc
                if w not in out:
                    out.append(w)
    return out


def devices_per_process(env=None) -> int:
    """Devices each process of ANY incarnation of this job owns.

    ``world`` everywhere in this module counts PROCESSES (that is the
    store-claim key and the metric label), but meshes are built from
    devices — and on real TPU a process owns several chips, so the
    world->mesh mapping must scale by this factor or the ladder compiles
    executables for meshes no real stage ever runs. The launcher's
    contract is homogeneous (``num_devices = local_device_count //
    nproc``): ``EDL_DEVICES_PER_PROC`` (the CPU rigs pin it to 1) wins;
    otherwise it is derived from the live backend — global devices over
    the current process count."""
    override = os.environ.get("EDL_DEVICES_PER_PROC")
    if override:
        try:
            return max(1, int(override))
        except ValueError:
            pass
    import jax

    if env is None:
        # no world to divide by: the process's OWN device count is the
        # per-process figure (dividing the global set by a defaulted
        # world=1 would claim every device in the job is ours)
        return max(1, len(jax.local_devices()))
    world = max(1, int(getattr(env, "world_size", 1) or 1))
    return max(1, len(jax.devices()) // world)


class AotLadder:
    """Background speculative compiler for neighbor world sizes.

    ``compile_for(world)`` is supplied by the integration site (it
    closes over the jitted step and the live avals — see
    :func:`make_neighbor_compiler`); the ladder owns everything else:
    rung enumeration, local-device feasibility, store claims, the
    low-priority thread, pacing, metrics, the ``aot_compile`` goodput
    lane and the ``train.aot.compile`` fault point.

    ``close()`` is cooperative: a compile in flight cannot be
    interrupted, so close joins briefly and abandons the daemon thread —
    a hot restage that tears the backends down under a running compile
    turns it into a counted failure, never a crash.
    """

    def __init__(
        self,
        env,
        compile_for: Callable[[int], None],
        worlds: Optional[Sequence[int]] = None,
        client=None,
        delay: Optional[float] = None,
    ) -> None:
        self._env = env
        self._compile_for = compile_for
        if worlds is None:
            worlds = neighbor_worlds(
                env.world_size, env.nproc_per_node,
                env.min_nodes, env.max_nodes,
            )
        self._worlds = list(worlds)
        # guards _client create/close and the _compile_for release: the
        # ladder thread lazily dials the store / drops the closure while
        # close() runs on the training thread
        self._mu = threading.Lock()
        self._client = client  # edl: guarded-by(self._mu)
        self._owns_client = client is None
        # let the live stage settle before stealing cycles from it: a
        # compile that starts beside the stage's own first jit slows both
        if delay is None:
            delay = float(os.environ.get("EDL_AOT_DELAY", "1.0"))
        self._delay = delay
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.compiled: List[int] = []
        # separate ledger + flight lane: the MAIN thread keeps owning the
        # process's train/data_wait attribution; ladder seconds land in
        # aot_compile on a component="aot" lane and can never displace
        # the train lane in the job-level sweep (priority is below every
        # foreground state)
        from edl_tpu.obs import goodput as obs_goodput

        self._ledger = obs_goodput.GoodputLedger(component="aot")

    def start(self) -> "AotLadder":
        if self._thread is None and self._worlds:
            self._thread = threading.Thread(
                target=self._run, name="edl-aot-ladder", daemon=True
            )
            self._thread.start()
        return self

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        # drop the (state, batch) closure even when the thread was
        # abandoned mid-compile: a hot restage keeps this process (and
        # its HBM) alive long after the ladder is gone
        with self._mu:
            self._compile_for = None
            owns, client = self._owns_client, self._client
            if owns:
                self._client = None
        self._ledger.close(cause="ladder_close")
        if owns and client is not None:
            try:
                client.close()
            except Exception:  # noqa: BLE001
                pass

    # -- store claims (leased while compiling, ``done:`` on success) -------

    def _store(self):
        with self._mu:
            client = self._client
        endpoint = getattr(self._env, "store_endpoint", "")
        if client is not None or not endpoint:
            return client
        # dial OUTSIDE the lock: close() on the training thread's hot-
        # restage path takes _mu and must never wait behind this connect
        try:
            from edl_tpu.store.client import StoreClient

            client = StoreClient(endpoint, timeout=5.0)
        except Exception as exc:  # noqa: BLE001
            logger.debug("aot: no store client (%s)", exc)
            return None
        with self._mu:
            if self._client is None:
                self._client = client
                return client
            existing = self._client
        try:
            client.close()  # lost a (theoretical) publish race
        except Exception:  # noqa: BLE001
            pass
        return existing

    def _claim(self, world: int):
        """Returns a held Registration, True (no store — lone pod, rank 0
        compiles), or None (claimed/done elsewhere)."""
        client = self._store()
        if client is None:
            return True if self._env.global_rank == 0 else None
        from edl_tpu.discovery.registry import Registry
        from edl_tpu.utils.exceptions import EdlStoreError

        try:
            reg, _holder = Registry(
                client, self._env.job_id or "job"
            ).register_if_absent(
                AOT_SERVICE, str(world),
                ("%s.%d" % (self._env.pod_id, self._env.global_rank)).encode(),
                ttl=60.0,
            )
        except EdlStoreError:
            return None  # transient store trouble: drop the rung this pass
        return reg

    def _finish_claim(self, world: int, reg, ok: bool) -> None:
        if reg is True:
            return
        if ok:
            client = self._store()
            if client is not None:
                from edl_tpu.discovery.registry import Registry
                from edl_tpu.utils.exceptions import EdlStoreError

                try:
                    Registry(client, self._env.job_id or "job").set_permanent(
                        AOT_SERVICE, str(world),
                        b"done:" + self._env.pod_id.encode(),
                    )
                except EdlStoreError:
                    pass
            reg.stop(delete=False)
        else:
            reg.stop(delete=True)

    # -- the compile loop --------------------------------------------------

    def _run(self) -> None:
        # the whole thread body is contained: speculation is "a counted
        # outcome, never a crash" and that must hold for failures OUTSIDE
        # _compile_rung too — jax.devices() itself can raise mid-restage
        # (backend re-init race) and an unhandled thread death would both
        # skip the closure release and dump a traceback over training
        try:
            self._run_inner()
        except Exception as exc:  # noqa: BLE001
            _M_AOT.inc(outcome="failed")
            logger.warning("aot: ladder aborted (%s)", exc)
        finally:
            with self._mu:
                self._compile_for = None

    def _run_inner(self) -> None:
        try:
            # best-effort thread-level niceness (Linux: a tid is a valid
            # PRIO_PROCESS target) — the ladder must lose CPU arbitration
            # to the training step it runs beside
            os.setpriority(
                os.PRIO_PROCESS, threading.get_native_id(),
                int(os.environ.get("EDL_AOT_NICE", "10")),
            )
        except (AttributeError, OSError, ValueError):
            pass
        if self._stop.wait(timeout=self._delay):
            return
        import jax

        devices = jax.devices()
        local_ids = {d.id for d in jax.local_devices()}
        per_proc = devices_per_process(self._env)
        deferred: List[int] = []
        for world in self._worlds:
            if self._stop.is_set():
                return
            ndev = world * per_proc
            if ndev > len(devices):
                # grow rung: the mesh needs devices this process cannot
                # see — the cache exchange owns this side of the ladder
                _M_AOT.inc(outcome="skipped_grow")
                obs_events.record(
                    "aot", component="aot", world=world,
                    outcome="skipped_grow",
                )
                continue
            if not any(d.id in local_ids for d in devices[:ndev]):
                # the target sub-mesh excludes every local device: the
                # executable could not even load here (and a surviving
                # peer whose device IS in it holds the claimable work)
                _M_AOT.inc(outcome="skipped_nonlocal")
                continue
            reg = self._claim(world)
            if reg is None:
                _M_AOT.inc(outcome="skipped_claimed")
                deferred.append(world)
                continue
            self._compile_rung(world, reg)
        # second chance for rungs a peer had claimed: a FAILED peer
        # compile deletes its lease and writes no done marker, so one
        # bounded re-pass picks the rung up instead of stranding it
        # until the next stage re-arms a ladder
        for world in deferred:
            if self._stop.wait(timeout=self._RETRY_DELAY):
                return
            reg = self._claim(world)
            if reg is None:
                continue  # done, still being compiled, or store trouble
            self._compile_rung(world, reg)
        # _run's finally then drops the (state, batch) closure: on TPU it
        # pins the first prefetched batch (and, for non-donating steps, a
        # full state duplicate) in HBM if held past the last rung

    _RETRY_DELAY = 5.0  # deferred-rung recheck (one peer-compile's width)

    def _compile_rung(self, world: int, reg) -> None:
        compile_for = self._compile_for  # close() may null it under us
        if compile_for is None:
            self._finish_claim(world, reg, False)
            return
        ok = False
        indivisible = False
        t0 = time.monotonic()
        try:
            with self._ledger.phase("aot_compile", cause="w%d" % world):
                if _FP_COMPILE.armed:
                    _FP_COMPILE.fire(world=world)
                _in_ladder.active = True
                try:
                    compile_for(world)
                finally:
                    _in_ladder.active = False
            ok = True
        except RungUnavailable as exc:
            # a permanent property of the model/window (e.g. an fsdp dim
            # not divisible over the neighbor mesh), not a breakage —
            # must not pollute the failed counter or warn every stage
            indivisible = True
            logger.debug("aot: world=%d rung unavailable (%s)", world, exc)
        except Exception as exc:  # noqa: BLE001 — speculation never kills training
            logger.warning(
                "aot: speculative compile for world=%d failed (%s)",
                world, exc,
            )
        finally:
            self._finish_claim(world, reg, ok)
        _M_AOT.inc(
            outcome="ok" if ok
            else ("skipped_indivisible" if indivisible else "failed")
        )
        obs_events.record(
            "aot", fsync=True, component="aot", world=world,
            outcome="ok" if ok
            else ("skipped_indivisible" if indivisible else "failed"),
            dur=round(time.monotonic() - t0, 3),
        )
        if ok:
            self.compiled.append(world)
            logger.info(
                "aot: world=%d step compiled ahead of time (%.1fs)",
                world, time.monotonic() - t0,
            )


def _scale_dim(shape, spec, mesh, new_mesh, scale_axes) -> Tuple:
    """Scale every dim of ``shape`` sharded over an axis in
    ``scale_axes`` by that axis's size ratio (the dp-batch contract:
    per-worker rows constant, global rows ∝ world)."""
    dims = list(shape)
    for i, part in enumerate(spec or ()):
        names = part if isinstance(part, tuple) else (part,)
        for name in names:
            if name in scale_axes:
                old = mesh.shape[name]
                new = new_mesh.shape[name]
                if old and dims[i] % old == 0:
                    dims[i] = dims[i] // old * new
    return tuple(dims)


def make_neighbor_compiler(
    step,
    state,
    batch,
    mesh_axes: Optional[Dict[str, int]] = None,
    batch_axis: str = "dp",
    devices_per_proc: Optional[int] = None,
    on_compiled: Optional[Callable[[int, object], None]] = None,
):
    """Build the ``compile_for(world)`` callback for :class:`AotLadder`
    from a live steady-state (step, state, batch) triple.

    The avals are mirrored from the live arrays — shapes, dtypes and
    sharding SPECS — and re-bound to a mesh of the target world's device
    prefix: state leaves keep their global shapes (fsdp shards them over
    more or fewer devices; divisibility failures skip the rung), batch
    dims sharded over ``batch_axis`` scale with the world size
    (per-worker rows are the constant). Lowering with ShapeDtypeStructs
    is a jax trace + XLA compile — no data, no execution — and the
    compile lands in the persistent cache under the portable key the
    future stage will look up.
    """
    import jax
    from jax.sharding import NamedSharding

    from edl_tpu.parallel import make_mesh

    live_mesh = None
    for leaf in jax.tree.leaves((state, batch)):
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and getattr(sharding, "mesh", None) is not None:
            live_mesh = sharding.mesh
            break
    if live_mesh is None:
        raise ValueError("no NamedSharding-placed leaf to mirror avals from")
    axes = dict(mesh_axes) if mesh_axes else {batch_axis: -1}

    def as_sds(leaf, new_mesh, scale_axes):
        sharding = getattr(leaf, "sharding", None)
        spec = getattr(sharding, "spec", None)
        shape = _scale_dim(
            leaf.shape, spec, live_mesh, new_mesh, scale_axes
        )
        new_sharding = (
            NamedSharding(new_mesh, spec) if spec is not None else None
        )
        for i, part in enumerate(spec or ()):
            names = part if isinstance(part, tuple) else (part,)
            for name in names:
                if name and shape[i] % new_mesh.shape[name]:
                    raise RungUnavailable(
                        "dim %d (%d) not divisible over %r=%d"
                        % (i, shape[i], name, new_mesh.shape[name])
                    )
        return jax.ShapeDtypeStruct(shape, leaf.dtype, sharding=new_sharding)

    # world counts PROCESSES; the target mesh needs the device prefix of
    # world x devices-per-process (on real TPU a process owns several
    # chips — a 1-device-per-world mesh would speculate shapes no real
    # stage ever runs)
    per_proc = (
        devices_per_proc
        if devices_per_proc
        else devices_per_process(None)
    )

    def compile_for(world: int) -> None:
        devices = jax.devices()[: world * per_proc]
        new_mesh = make_mesh(axes, devices=devices)
        state_sds = jax.tree.map(
            lambda x: as_sds(x, new_mesh, ()), state
        )
        batch_sds = jax.tree.map(
            lambda x: as_sds(x, new_mesh, (batch_axis,)), batch
        )
        with new_mesh:
            compiled = step.lower(state_sds, batch_sds).compile()
        if on_compiled is not None:
            # the rung's compiled executable in hand: the memory plane
            # harvests its memory_analysis() here (the plan is free —
            # the compile already happened for the resize ladder)
            try:
                on_compiled(world, compiled)
            except Exception as exc:  # noqa: BLE001 — telemetry never fails a rung
                logger.debug(
                    "aot: on_compiled hook failed for world=%d: %s",
                    world, exc,
                )

    return compile_for


# -- the cache exchange -------------------------------------------------------

_TMP_MARK = ".edlpull"


def _is_entry(name: str) -> bool:
    """True for a shippable persistent-cache entry file name. XLA's
    ``-atime`` sidecars (rewritten on every hit — literally access-time
    records), in-flight pull temps and dotfiles are excluded. The single
    definition of "what is a cache entry" — the manifest scanners must
    agree or published manifests drift from what peers can serve."""
    return not (
        name.endswith("-atime") or _TMP_MARK in name or name.startswith(".")
    )


def _safe_name(name: str) -> bool:
    """True when a PEER-supplied entry name is a bare filename. Enforced
    on both exchange directions: the server never reads a path-shaped
    name out of its cache dir, and the puller never writes one — a
    hostile manifest naming ``../../...`` must not choose where entry
    bytes land."""
    return bool(name) and "/" not in name and "\\" not in name and not name.startswith(".")


def _digest_file(path: str) -> str:
    digest = hashlib.sha256()
    with open(path, "rb") as fh:
        for chunk in iter(lambda: fh.read(1 << 20), b""):
            digest.update(chunk)
    return digest.hexdigest()


def _scan_dir(
    cache_dir: str, digests: Dict[str, Tuple[float, int, str]]
) -> Tuple[Dict[str, Tuple[float, int, str]], Dict[str, Dict]]:
    """THE definition of "what is a publishable cache entry": one
    enumeration shared by every manifest scanner, or published manifests
    drift from what peers can serve. ``digests`` memoizes by
    (mtime, size) so an unchanged file is a stat, not a re-digest; pass
    ``{}`` for a full scan. Returns ``(fresh_digests, manifest)`` where
    manifest is ``{entry_name: {"sha": hex, "size": n}}`` — entry names
    double as cache keys, so a manifest diff IS a key diff."""
    fresh: Dict[str, Tuple[float, int, str]] = {}
    out: Dict[str, Dict] = {}
    try:
        names = os.listdir(cache_dir)
    except OSError:
        return fresh, out
    for name in names:
        if not _is_entry(name):
            continue
        path = os.path.join(cache_dir, name)
        try:
            st = os.stat(path)
            cached = digests.get(name)
            if cached and cached[0] == st.st_mtime and cached[1] == st.st_size:
                sha = cached[2]
            else:
                sha = _digest_file(path)
            fresh[name] = (st.st_mtime, st.st_size, sha)
            out[name] = {"sha": sha, "size": st.st_size}
        except OSError:
            continue
    return fresh, out


def scan_manifest(cache_dir: str) -> Dict[str, Dict]:
    """One-shot full scan (see :func:`_scan_dir`)."""
    return _scan_dir(cache_dir, {})[1]


class CacheExchange:
    """Pod-side half of the exchange: manifest publication + entry server.

    Owned by the LAUNCHER (pod-scoped, survives worker restarts across
    stages); sharing the launcher's store client. ``refresh()`` is cheap
    and throttled internally — call it from the supervision loop; it
    rescans the cache dir (digesting only new/changed files) and
    republishes the manifest when it changed.
    """

    _REFRESH_EVERY = 5.0

    def __init__(
        self, cache_dir: str, client, job_id: str, pod_id: str,
        host: str = "0.0.0.0", port: int = 0,
    ) -> None:
        self.cache_dir = cache_dir
        self._client = client
        self.job_id = job_id
        self.pod_id = pod_id
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((host, port))
        self._listener.listen(16)
        self._host = host
        self.port = self._listener.getsockname()[1]
        self._stop = threading.Event()
        self._accept_thread: Optional[threading.Thread] = None
        self._refresh_thread: Optional[threading.Thread] = None
        self._refresh_lock = threading.Lock()
        self._digests: Dict[str, Tuple[float, int, str]] = {}  # name -> (mtime, size, sha)
        self._published: Optional[str] = None
        self._last_refresh = 0.0

    @property
    def endpoint(self) -> str:
        from edl_tpu.utils.net import get_host_ip

        host = self._host if self._host not in ("", "0.0.0.0") else get_host_ip()
        return "%s:%d" % (host, self.port)

    def start(self) -> "CacheExchange":
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="edl-cache-exchange", daemon=True
        )
        self._accept_thread.start()
        # ALL digest work — the initial scan included — lives on the
        # exchange's own thread, never the caller's: the common restage
        # case relaunches a launcher over a WARM cache dir (GBs of
        # TPU-sized entries), and sha256 over that inline in start()
        # or on the supervision loop would stall worker spawn / drain
        # windows for seconds. The manifest appears moments after
        # start() returns; peers that race it simply pull on their next
        # look.
        self._refresh_thread = threading.Thread(
            target=self._refresh_loop, name="edl-cache-exchange-scan",
            daemon=True,
        )
        self._refresh_thread.start()
        return self

    def _refresh_loop(self) -> None:
        self.refresh(force=True)  # initial publish, off the start() path
        while not self._stop.wait(timeout=self._REFRESH_EVERY):
            self.refresh(force=True)

    def stop(self) -> None:
        self._stop.set()
        # the scan thread must be gone before the retraction below, or
        # an in-flight refresh republishes the manifest right after we
        # delete it
        if self._refresh_thread is not None:
            self._refresh_thread.join(timeout=2.0)
        # retract the manifest: it is a plain (unleased) key, so without
        # this a departed pod's entry outlives it and every later pull
        # burns budget dialing a dead endpoint (a SIGKILLed pod still
        # leaves one behind — the per-peer dial cap in pull_missing is
        # the backstop for that case)
        if self._published is not None:
            try:
                self._client.delete(
                    "/%s/%s/%s" % (self.job_id, MANIFEST_SERVICE, self.pod_id)
                )
            except Exception:  # noqa: BLE001 — best-effort retraction
                pass
        try:
            self._listener.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._listener.close()
        except OSError:
            pass

    # -- manifest ----------------------------------------------------------

    def _scan_incremental(self) -> Dict[str, Dict]:
        """:func:`_scan_dir` against the memoized digest map — the
        steady-state refresh cost is one listdir + a stat per entry."""
        self._digests, out = _scan_dir(self.cache_dir, self._digests)
        return out

    def refresh(self, force: bool = False) -> None:
        """Republish the manifest if the cache dir changed. Runs on the
        exchange's own scan thread in steady state (manual calls are
        fine — serialized by a lock). Best-effort: a sick store delays
        the next pod's pull, it never breaks this one."""
        with self._refresh_lock:
            self._refresh_locked(force)

    # edl: blocking-ok(hashing under _refresh_lock is the design: the lock exists only to serialize the exchange's own scan thread against manual refresh() calls — nothing latency-critical contends it, PR-8 moved all scans off the supervision loop)
    def _refresh_locked(self, force: bool) -> None:
        now = time.monotonic()
        if not force and now - self._last_refresh < self._REFRESH_EVERY:
            return
        self._last_refresh = now
        entries = self._scan_incremental()
        # the change check must exclude the publication timestamp: with
        # ts inside, every throttle window republishes an identical
        # manifest — steady store-journal chatter that rides the
        # replication stream of an HA control plane for nothing
        payload = {
            "endpoint": self.endpoint,
            "entries": {n: e["sha"] for n, e in sorted(entries.items())},
        }
        body = json.dumps(payload, sort_keys=True)
        if body == self._published:
            return
        payload["ts"] = time.time()
        try:
            self._client.put(
                "/%s/%s/%s" % (self.job_id, MANIFEST_SERVICE, self.pod_id),
                json.dumps(payload, sort_keys=True).encode(),
            )
            self._published = body
        except Exception as exc:  # noqa: BLE001
            logger.debug("cache-exchange manifest publish failed: %s", exc)

    # -- serving -----------------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                sock, _addr = self._listener.accept()
            except OSError:
                return
            threading.Thread(
                target=self._serve_conn, args=(sock,), daemon=True
            ).start()

    def _serve_conn(self, sock: socket.socket) -> None:
        from edl_tpu.rpc.wire import pack_frame, read_frame_blocking

        try:
            with sock:
                sock.settimeout(30.0)
                req = read_frame_blocking(sock)
                from edl_tpu.rpc.wire import TC_FIELD, server_span

                if req.get("m") != "cache_pull":
                    sock.sendall(pack_frame(
                        {"i": req.get("i", 0), "ok": False,
                         "err": {"etype": "EdlStoreError",
                                 "detail": "unknown method"}}
                    ))
                    return
                from edl_tpu.rpc.wire import read_entries_capped

                cap = int(os.environ.get(
                    "EDL_CACHE_PULL_MAX_BYTES", str(64 << 20)
                ))
                # per-method server latency + caller-linked span when the
                # pulling pod propagated its restage trace context
                with server_span(
                    "cache_pull", req.get(TC_FIELD), server="cache"
                ):
                    # the manifest is the only namespace a peer may name:
                    # never serve a path-shaped name out of the cache dir
                    entries, truncated, sent = read_entries_capped(
                        req.get("names", ()),
                        lambda name: (
                            os.path.join(self.cache_dir, name)
                            if _safe_name(name) else None
                        ),
                        cap,
                    )
                sock.sendall(pack_frame(
                    {"i": req.get("i", 0), "ok": True, "entries": entries,
                     "truncated": truncated}
                ))
                _M_XCHG_BYTES.inc(sent, dir="tx")
        except Exception as exc:  # noqa: BLE001 — a sick peer is its problem
            logger.debug("cache-exchange serve failed: %s", exc)


def read_manifests(client, job_id: str) -> Dict[str, Dict]:
    """``{pod_id: manifest}`` for every published pod manifest."""
    out: Dict[str, Dict] = {}
    prefix = "/%s/%s/" % (job_id, MANIFEST_SERVICE)
    try:
        rows, _rev = client.range(prefix)
    except Exception as exc:  # noqa: BLE001
        logger.debug("cache-exchange manifest read failed: %s", exc)
        return out
    for key, value, _c, _m in rows:
        try:
            out[key[len(prefix):]] = json.loads(value)
        except ValueError:
            continue
    return out


def pull_missing(
    cache_dir: str,
    client=None,
    endpoint: str = "",
    job_id: str = "",
    own_pod: str = "",
    deadline: Optional[float] = None,
    chunk: int = 16,
) -> Dict[str, int]:
    """Diff peer manifests against ``cache_dir`` and pull what is missing.

    Returns ``{"pulled": n, "bytes": n, "skipped_bad": n, "peers": n}``.
    Bounded (``deadline`` seconds, default ``EDL_CACHE_PULL_BUDGET`` =
    10) and exception-contained: ANY failure — peer gone, frame torn,
    digest mismatch (the ``store.cache.exchange`` corrupt drill) — skips
    that entry or peer and the resize degrades to a normal compile.
    Entries land via write-to-temp + atomic rename, digest-verified
    first, so a torn pull can never poison the cache.
    """
    stats = {"pulled": 0, "bytes": 0, "skipped_bad": 0, "peers": 0}
    if not cache_dir:
        return stats
    if deadline is None:
        deadline = float(os.environ.get("EDL_CACHE_PULL_BUDGET", "10"))
    t_end = time.monotonic() + deadline
    owns_client = False
    if client is None:
        if not endpoint:
            return stats
        try:
            from edl_tpu.store.client import StoreClient

            client = StoreClient(endpoint, timeout=min(5.0, deadline))
            owns_client = True
        except Exception as exc:  # noqa: BLE001
            logger.debug("cache pull: no store (%s)", exc)
            return stats
    # restage-trace segment: the pull is one hop of the restage critical
    # path (spawn -> CACHE PULL -> restore -> first jit), and the span's
    # context rides each cache_pull RPC to the serving peer
    import contextlib as _contextlib

    span = (
        obs_trace.child_span("cache_pull")
        if obs_trace.PROPAGATION.armed
        else _contextlib.nullcontext()
    )
    try:
        manifests = read_manifests(client, job_id)
        try:
            local = set(os.listdir(cache_dir))
        except OSError:
            os.makedirs(cache_dir, mode=0o700, exist_ok=True)
            local = set()
        t0 = time.monotonic()
        with span:
            for pod, manifest in manifests.items():
                if pod == own_pod or time.monotonic() > t_end:
                    continue
                peer = manifest.get("endpoint", "")
                wanted = {
                    name: sha
                    for name, sha in (manifest.get("entries") or {}).items()
                    # the write direction enforces the same bare-filename rule
                    # the server does: a hostile manifest must not pick where
                    # pulled bytes land
                    if name not in local and _safe_name(name)
                }
                if not peer or not wanted:
                    continue
                stats["peers"] += 1
                names = sorted(wanted)
                while names and time.monotonic() <= t_end:
                    batch, names = names[:chunk], names[chunk:]
                    got, truncated = _pull_chunk(
                        peer, batch,
                        # per-dial cap: a dead endpoint (SIGKILLed pod whose
                        # manifest survived) must cost one bounded connect,
                        # not the whole remaining pull budget
                        max(0.5, min(
                            float(os.environ.get(
                                "EDL_CACHE_PULL_PEER_TIMEOUT", "5"
                            )),
                            t_end - time.monotonic(),
                        )),
                    )
                    if not got:
                        break  # peer sick/gone: stop dialing it, try the next
                    # entries the server pushed out of a byte-capped response
                    # come back later; got nonempty guarantees progress
                    names.extend(truncated)
                    for name, data in got.items():
                        if _FP_EXCHANGE.armed:
                            try:
                                data = _FP_EXCHANGE.fire(data, name=name[:32])
                            except ConnectionError:
                                stats["skipped_bad"] += 1
                                continue
                        sha = hashlib.sha256(data).hexdigest()
                        if sha != wanted.get(name):
                            # corrupted in flight or torn at the peer: skip —
                            # the next stage simply compiles this one itself
                            stats["skipped_bad"] += 1
                            logger.warning(
                                "cache pull: digest mismatch for %s from %s; "
                                "entry dropped (degrades to a compile)",
                                name[:48], pod[:8],
                            )
                            continue
                        tmp = os.path.join(
                            cache_dir,
                            "%s%s.%d" % (name, _TMP_MARK, os.getpid()),
                        )
                        try:
                            with open(tmp, "wb") as fh:
                                fh.write(data)
                                # a digest-verified entry must not be torn by
                                # the next SIGKILL: rename persists the name,
                                # fsync persists the bytes
                                fh.flush()
                                os.fsync(fh.fileno())
                            os.replace(tmp, os.path.join(cache_dir, name))
                        except OSError as exc:
                            logger.warning("cache pull: write failed: %s", exc)
                            try:
                                os.unlink(tmp)
                            except OSError:
                                pass
                            continue
                        local.add(name)
                        stats["pulled"] += 1
                        stats["bytes"] += len(data)
                        _M_XCHG_BYTES.inc(len(data), dir="rx")
        if stats["pulled"] or stats["skipped_bad"]:
            obs_events.record(
                "exchange", fsync=True, component="aot",
                pulled=stats["pulled"], bytes=stats["bytes"],
                skipped_bad=stats["skipped_bad"],
                dur=round(time.monotonic() - t0, 3),
            )
            logger.info(
                "cache exchange: pulled %d entr%s (%d bytes) from %d "
                "peer(s)%s",
                stats["pulled"], "y" if stats["pulled"] == 1 else "ies",
                stats["bytes"], stats["peers"],
                ", %d bad skipped" % stats["skipped_bad"]
                if stats["skipped_bad"] else "",
            )
    except Exception as exc:  # noqa: BLE001 — the pull is a perf lever, never a gate
        logger.warning("cache pull failed (%s); continuing uncached", exc)
    finally:
        if owns_client:
            try:
                client.close()
            except Exception:  # noqa: BLE001
                pass
    return stats


def _pull_chunk(
    peer: str, names: List[str], timeout: float
) -> Tuple[Dict[str, bytes], List[str]]:
    """One bounded cache_pull RPC. Returns ``(entries, truncated)`` —
    ``truncated`` names the server pushed out of a byte-capped response
    for the caller to re-request; both empty on any transport failure."""
    from edl_tpu.rpc.wire import request_once

    try:
        resp = request_once(
            peer, {"i": 1, "m": "cache_pull", "names": names},
            timeout=min(timeout, 30.0),
        )
    except Exception as exc:  # noqa: BLE001
        logger.debug("cache pull from %s failed: %s", peer, exc)
        return {}, []
    if not resp.get("ok"):
        return {}, []
    entries = resp.get("entries") or {}
    return {
        str(name): bytes(data)
        for name, data in entries.items()
        if isinstance(data, (bytes, bytearray))
    }, [str(n) for n in (resp.get("truncated") or ())]
