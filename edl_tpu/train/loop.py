"""ElasticTrainer: the one-call elastic training loop.

The reference sketches this user-facing API but never built it — its
aspirational test (python/edl/tests/unittests/test_train.py:28-67) wants a
``PaddleState`` with ``register_adjust_function`` and per-batch notify,
and its flagship example hand-assembles the same ~80-line loop in every
script (example/collective/resnet50/train_with_fleet.py:367-570: fleet
init → build → load checkpoint → epoch loop → rank-0 save). Here the loop
is a reusable class over the edl_tpu primitives:

  - joins the elastic job from the launcher env (``train.init``),
  - builds the device mesh and dp-shards the input pipeline
    (``batched`` + ``prefetch_to_device`` keep HBM fed),
  - resolves hyper-parameter adjustments for the CURRENT world size
    (``AdjustRegistry``, e.g. linear-scaled lr) before building the
    optimizer — the elastic-resize contract,
  - restores the latest checkpoint (Orbax reshards across topology
    changes) and saves per epoch, rank-0 logs,
  - barriers the stage so all workers enter compiled collectives
    together.

A stage change (resize) is handled the stop-resume way: the launcher
kills and respawns the process, and ``fit`` naturally resumes from the
last checkpoint under the new world size with re-resolved
hyper-parameters.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, Iterable, Optional

import jax
import numpy as np
import optax

from edl_tpu.checkpoint import AdjustRegistry, CheckpointManager, TrainStatus
from edl_tpu.obs import events as obs_events
from edl_tpu.obs import goodput as obs_goodput
from edl_tpu.obs import memory as obs_memory
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import numerics as obs_numerics
from edl_tpu.obs import profile as obs_profile
from edl_tpu.obs import trace as obs_trace

_M_STEP_SECONDS = obs_metrics.histogram(
    "edl_train_step_seconds",
    "seconds a train step, as the device paced it: wall time between two "
    "moments the host knew a numbered step had retired, over the steps "
    "between them (one observation a stretch; with the numerics plane off, "
    "the dispatch-to-dispatch interval of every step)",
)
_M_STEPS = obs_metrics.counter(
    "edl_train_steps_total", "train steps dispatched"
)
_M_EPOCHS = obs_metrics.counter(
    "edl_train_epochs_total", "epochs completed"
)
_M_FIRST_STEP = obs_metrics.gauge(
    "edl_train_first_step_seconds",
    "first step of the stage (jit trace + compile or cache load)",
)
from edl_tpu.data import batched, prefetch_to_device
from edl_tpu.parallel import (
    batch_sharding,
    fsdp_shardings,
    make_mesh,
    replicated,
    shard_batch,
)
from edl_tpu.train.context import init, worker_barrier
from edl_tpu.train.step import TrainState, create_state, make_train_step

DataFn = Callable[[int], Iterable]  # epoch -> records or ready batches


_M_DRAINS = obs_metrics.counter(
    "edl_train_drains_total", "graceful worker drains (preemption notices honored)"
)


def _state_shardings(mesh, fsdp: bool):
    """Where ``create_state``'s outputs are born: every leaf replicated
    over ``mesh``, or under ``fsdp`` each ``params`` / ``opt_state`` leaf
    split by ``fsdp_shardings`` and ``step`` / ``batch_stats`` replicated
    (a function of the abstract state: the split reads the shapes, which
    costs ``create_state`` a second trace; one sharding for every leaf
    needs none)."""
    rep = replicated(mesh)
    if not fsdp:
        return rep
    return lambda abstract: abstract.replace(
        step=rep,
        params=fsdp_shardings(mesh, abstract.params),
        opt_state=fsdp_shardings(mesh, abstract.opt_state),
        # tree.map over None is None: no-op without stats
        batch_stats=jax.tree.map(lambda _: rep, abstract.batch_stats),
    )


def _lower_step(step, state, device_batch, compile: bool):
    """``(Lowered, Compiled)`` of the step that has just run: one more jax
    trace and, with ``compile``, a compile-cache hit. Either is None where
    it fails: what reads them is telemetry, never a correctness
    dependency."""
    lowered = compiled = None
    try:
        lowered = step.lower(state, device_batch)
        if compile:
            compiled = lowered.compile()
    except Exception as exc:  # noqa: BLE001 — backend/API drift degrades to no plan
        print(
            "elastic-trainer: the step could not be lowered and compiled "
            "again (%s); its cost model, memory plan or phase table is "
            "missing" % exc,
            file=sys.stderr,
        )
    return lowered, compiled


#: seconds a stage that trained to its end waits for its census thread
CENSUS_JOIN_S = 30.0


def _publish_step_census(tracer, notes, plan, env, dropped) -> None:
    """The census thread's body: ``obs_profile.publish_step_census``, which
    must never take the stage down. A stage that left early (a resize, an
    exception) has set ``dropped`` and did not wait: its census is not
    published into the next stage's ring and gauges."""
    try:
        obs_profile.step_program()  # the seconds: the text and the pass over it
        if dropped.is_set():
            return
        obs_profile.publish_step_census(
            tracer, notes, plan.by_kind() if plan is not None else None,
            stage=env.stage, world=env.world_size,
        )
    except Exception as exc:  # noqa: BLE001 — telemetry, never a correctness dependency
        print(
            "elastic-trainer: no census of the compiled step (%s)" % exc,
            file=sys.stderr,
        )


def _record_step_launch(tracer: obs_trace.SpanTracer) -> None:
    """``step_launch``: what the step's first call does after jax's last
    compile event inside it has ended and before it returns — the loaded
    executable's argument handlers, its upload to each chip, the first
    enqueue (1.3 s on four chips, 0.1–0.6 s on one). jax reports no event
    for it, so it is read off the ring: from the end of the last
    ``jit_compile`` inside this thread's newest ``step_dispatch`` to that
    dispatch's end. Nothing where the call compiled nothing."""
    tid = threading.get_ident() & 0x7FFFFFFF
    mine = [
        e for e in tracer.to_events()
        if e.get("tid") == tid and e.get("ph") == "X"
    ]
    dispatch = next(
        (e for e in reversed(mine) if e["name"] == "step_dispatch"), None
    )
    if dispatch is None:
        return
    compiled = [
        e["ts"] + e["dur"] for e in mine
        if e["name"] == "jit_compile" and e["ts"] >= dispatch["ts"]
    ]
    if compiled:
        tracer.record_wall(
            "step_launch", max(compiled) / 1e6,
            (dispatch["ts"] + dispatch["dur"]) / 1e6,
        )


class RetireClock:
    """Seconds a step from the moments the host knows a numbered step has
    retired on the device.

    The step loop runs ahead of the device, so its own dispatch-to-dispatch
    intervals are a few ms seven times in eight and eight steps long the
    eighth. What it does know is each moment a wait for the device returned
    (the numerics plane's fetch of step ``k``'s bundle, the end-of-epoch
    sync): step ``k`` has retired by then, and the wait returned because
    it did. Between two such marks the device ran ``Δsteps`` whole steps
    in ``Δt``, whatever the host did in between. Marks chain inside one
    epoch: an epoch boundary holds host work (callback, save, a new feed)
    that no step paced. Steps count through the stage, as the probe and
    the heartbeat count them.
    """

    def __init__(self, tracer: obs_trace.SpanTracer) -> None:
        self._tracer = tracer
        self._last: Optional[tuple] = None  # (step, monotonic time)

    def start_epoch(self) -> None:
        self._last = None

    def mark(
        self, step: int, t: float, epoch: int,
        gauges: Optional[Dict[str, float]] = None,
    ) -> Optional[float]:
        """Step ``step`` had retired at ``t``. Returns seconds a step since
        the previous mark (None for an epoch's first), observes it into
        ``edl_train_step_seconds`` and leaves a ``step_retired`` instant.
        ``gauges`` is what the model sowed in that step (``moe_held_load_max``,
        ``dsa_tile_live``, ...), as the wait that made the mark brought it
        back: the instant carries it, so the ring holds mark by mark the
        seconds a step beside the routing the step ran under."""
        last, self._last = self._last, (step, t)
        derived = {}
        if last is not None and step > last[0]:
            steps = step - last[0]
            derived = {"steps": steps, "seconds_per_step": (t - last[1]) / steps}
            _M_STEP_SECONDS.observe(derived["seconds_per_step"])
        if gauges:
            derived["gauges"] = {k: float(v) for k, v in gauges.items()}
        self._tracer.instant("step_retired", step=step, epoch=epoch, **derived)
        return derived.get("seconds_per_step")


class _RestageRequested(Exception):
    """Raised out of the step loop when the stage this process runs under
    has been superseded (hot-restage mode only)."""


@dataclasses.dataclass(slots=True)
class _Stage:
    """What the parts of ``_fit_stage`` share, one frame's locals once: a
    record its parts read and write, and nothing else."""

    env: Any
    mesh: Any
    closing: contextlib.ExitStack  # every close of the stage, see _fit_stage
    tracer: obs_trace.SpanTracer
    retired: RetireClock
    mngr: Optional[CheckpointManager] = None
    health: Any = None  # train.context.HealthMonitor
    mem_plane: Optional[obs_memory.MemoryPlane] = None
    probe: Optional[obs_numerics.NumericsProbe] = None
    step_telemetry: Optional[obs_profile.StepTelemetry] = None
    capture: Optional[obs_profile.CaptureController] = None
    step: Any = None  # the jitted train step
    sharding: Any = None  # a batch's
    state: Optional[TrainState] = None
    start_epoch: int = 0
    steps_done: int = 0  # stage-cumulative, drives the heartbeat
    last_flight: float = 0.0  # throttled flight-recorder step marker
    first_step_done: bool = False
    ladder: Any = None  # AOT resize ladder, armed after the first step
    census: Optional[threading.Thread] = None  # the compiled step's census
    census_dropped: threading.Event = dataclasses.field(
        default_factory=threading.Event
    )


class ElasticTrainer:
    """Drive an elastic SPMD training job end to end.

    ``optimizer`` is either an ``optax.GradientTransformation`` or a
    factory ``overrides_dict -> tx`` — the factory form is what makes
    hyper-parameter adjustment on resize work (it is called with the
    merged ``AdjustRegistry`` output for the current world size, e.g.
    ``{"lr": 0.4}``).

    ``data_fn(epoch)`` returns the epoch's data: raw records when
    ``batch_size`` is set (they get packed into fixed-shape batches,
    ragged tail dropped), or ready ``(x, y)`` host batches otherwise.
    Epoch-seeded generators give the reference's ``pass_id_as_seed``
    deterministic-resume contract (train_with_fleet.py:458-464).

    ``sample_input`` should be a NUMPY array (or shape-dtype struct): a
    jax device array built before ``fit()`` initialises the backend,
    which breaks ``jax.distributed`` bootstrap in multi-worker stages.
    """

    def __init__(
        self,
        model,
        optimizer,
        loss: Callable,
        sample_input,
        mesh_axes: Optional[Dict[str, int]] = None,
        fsdp: bool = False,
        ckpt_dir: Optional[str] = None,
        adjusts: Optional[AdjustRegistry] = None,
        apply_kwargs: Optional[Dict[str, Any]] = None,
        init_kwargs: Optional[Dict[str, Any]] = None,
        batch_size: Optional[int] = None,
        batch_axis: str = "dp",
        async_save: bool = False,
        prefetch_depth: int = 2,
        seed: int = 0,
        log: bool = True,
    ) -> None:
        t_init = time.monotonic()
        self._model = model
        self._optimizer = optimizer
        self._loss = loss
        self._sample_input = sample_input
        self._mesh_axes = mesh_axes
        self._fsdp = fsdp
        self._ckpt_dir = ckpt_dir
        self._adjusts = adjusts
        self._apply_kwargs = apply_kwargs
        self._init_kwargs = dict(init_kwargs or {})
        self._batch_size = batch_size
        self._batch_axis = batch_axis
        self._async_save = async_save
        self._depth = prefetch_depth
        self._seed = seed
        self._log = log
        self._eval_step = None  # jitted once, reused across evaluate() calls
        self._masked_eval_step = None
        obs_trace.get_tracer().record(
            "trainer_init", t_init, time.monotonic() - t_init,
            ckpt=bool(ckpt_dir),
        )

    def _make_tx(self, overrides: Dict[str, Any]):
        if isinstance(self._optimizer, optax.GradientTransformation):
            return self._optimizer
        return self._optimizer(overrides)

    def fit(
        self,
        data_fn: DataFn,
        epochs: int,
        on_epoch_end: Optional[Callable[[int, Dict], None]] = None,
    ) -> TrainState:
        """Train to ``epochs``; under ``EDL_HOT_RESTAGE=1`` this also
        survives elastic stage changes WITHOUT a process restart: a
        drain-token bump raises out of the step loop, the distributed
        runtime is torn down and re-initialized for the new generation,
        and the loop re-enters from the last checkpoint — the same
        resume contract as stop-resume, minus the interpreter, import,
        and compile-cache cold start. Anything dirty during the
        handover exits with ``HOT_RESTAGE_EXIT`` so the launcher falls
        back to a cold respawn."""
        from edl_tpu.train import context as ctx

        if not ctx.hot_restage_enabled():
            return self._fit_stage(data_fn, epochs, on_epoch_end, None)
        env = init()
        monitor = ctx.StageMonitor(env) if env.store_endpoint else None
        try:
            while True:
                try:
                    return self._fit_stage(
                        data_fn, epochs, on_epoch_end, monitor
                    )
                except _RestageRequested:
                    self._hot_restage(monitor)
        finally:
            if monitor is not None:
                monitor.close()

    def _hot_restage(self, monitor) -> None:
        """Adopt the new generation in-process, or exit for a respawn."""
        import sys as _sys

        from edl_tpu.train import context as ctx

        env = ctx.current_env()
        grace = float(os.environ.get("EDL_HOT_GRACE", "20"))
        try:
            cluster = monitor.wait_for_my_stage(env.pod_id, timeout=grace)
            if cluster is None:
                raise RuntimeError(
                    "no published generation includes this pod"
                )
            # confirm the handoff BEFORE jax.distributed re-init: the
            # launcher's deadline exists to catch workers wedged in dead
            # collectives, which can never reach this line — while the
            # re-init barrier legitimately blocks on slow joiners (a cold
            # pod's interpreter+import start) for longer than any sane
            # wedge deadline. initialize() has its own timeout; a failure
            # there exits via HOT_RESTAGE_EXIT below.
            monitor.mark_adopted(env.pod_id, env.rank_in_pod, cluster.stage)
            new_env = ctx.reinit_for_stage(
                cluster, env.pod_id, env.rank_in_pod
            )
            monitor.arm(new_env.stage)
            # jitted eval steps compiled under the old backend are dead
            self._eval_step = None
            self._masked_eval_step = None
        except Exception as exc:
            print(
                "elastic-trainer: hot restage failed (%s); requesting "
                "respawn" % exc,
                file=_sys.stderr,
            )
            _sys.exit(ctx.HOT_RESTAGE_EXIT)

    def _drain_exit(self, health, mngr, state, epoch: int, step: int, env):
        """Honor a preemption notice between steps: emergency checkpoint
        within the notice's budget (best effort — an unfinished save is
        quarantined by restore-side fallback), record the drain, and leave
        with the clean ``DRAINED_EXIT`` code the launcher expects."""
        from edl_tpu.train import context as ctx

        # drain operation trace (keyed by pod id, same derivation as the
        # launcher's root): the emergency save and drained records below
        # stitch under the pod's drain op
        obs_trace.begin_process_op("drain", env.pod_id)
        obs_goodput.enter("drain", cause="preempt")
        budget = health.drain_budget_left()
        if mngr is not None and env.world_size == 1:
            # Orbax saves are COLLECTIVE across jax.distributed processes:
            # a single draining pod of a multi-pod stage cannot checkpoint
            # alone (its peers are not draining and will never join the
            # save), so the partial-drain case keeps the last periodic
            # version and relies on the proactive restage. A full-job
            # notice drains every pod, which stop-resume handles pod by
            # pod; the single-process world (and the chaos trainee, which
            # saves per-rank) get the exact bounded-lost-work snapshot.
            # epoch-1: this epoch is NOT complete — resume replays it from
            # the start with the (further-advanced) emergency state, the
            # same contract as being killed mid-epoch, minus the lost steps
            status = TrainStatus(
                epoch=epoch - 1,
                step=int(state.step),
                world_size=env.world_size,
                meta={"emergency": True, "mid_epoch": epoch},
            )
            mngr.emergency_save(state, status, budget)
        elif mngr is not None:
            # the multi-pod partial-drain gap, closed: this pod cannot
            # checkpoint alone (the save is collective), but it CAN make
            # the checkpoints it already holds survive its departure —
            # a peer replica push is per-pod and non-collective
            # (checkpoint/replicate.py; no-op without a local tier)
            mngr.emergency_replicate(budget)
        _M_DRAINS.inc()
        health.record_drained(step)
        if env.is_rank0 and self._log:
            print(
                "elastic-trainer: preemption notice honored at epoch %d "
                "step %d (budget %.1fs); exiting drained" % (epoch, step, budget)
            )
        sys.exit(ctx.DRAINED_EXIT)

    def _fit_stage(
        self, data_fn: DataFn, epochs: int,
        on_epoch_end: Optional[Callable[[int, Dict], None]], monitor,
    ) -> TrainState:
        # `closing` owns every close of the stage: each is pushed at the line
        # that builds its owner and they run in reverse, every one even where
        # an earlier one raised. So the order of building is the rule of
        # closing: the checkpoint manager is built first and closes last; the
        # health monitor before the memory plane and the numerics probe, which
        # hold its store client and so close before it; the ladder after the
        # first step, so it stops before the memory plane its rungs harvest
        # into. `leaving` owns no close and unwinds before any of them: the
        # mesh is left, then the census thread is told to drop.
        with contextlib.ExitStack() as closing, contextlib.ExitStack() as leaving:
            stage = self._open_stage(closing, leaving)
            for epoch in range(stage.start_epoch, epochs):
                metrics, steps, t_epoch = self._run_steps(
                    stage, epoch, data_fn, monitor
                )
                self._end_epoch(stage, epoch, metrics, steps, t_epoch, on_epoch_end)
            if stage.mngr is not None:
                stage.mngr.wait()
            obs_goodput.close(cause="complete")
            if stage.census is not None:
                # bounded, and here alone: a stage that is leaving for a
                # resize or on an exception waits for no telemetry
                stage.census.join(timeout=CENSUS_JOIN_S)
            return stage.state

    def _open_stage(self, closing, leaving) -> _Stage:
        """The stage from ``init()`` to a step that is ready to run: mesh,
        planes (each one's close pushed on ``closing`` where it is built),
        state, restore, the jitted step, the start barrier. Returns inside
        the mesh, which ``leaving`` holds."""
        from edl_tpu.train import context as ctx

        env = init()
        t_setup = time.monotonic()  # train_setup trace segment starts here
        tracer = obs_trace.get_tracer()
        # a stage traces its step anew: its shapes are noted anew
        tracer.reset_notes()
        stage = _Stage(
            env=env, mesh=make_mesh(self._mesh_axes), closing=closing,
            tracer=tracer, retired=RetireClock(tracer),
        )
        if self._ckpt_dir:
            stage.mngr = CheckpointManager(
                self._ckpt_dir, async_save=self._async_save
            )
            closing.callback(stage.mngr.close)
        # health plane: drain-notice watch + step heartbeats. Best-effort
        # by design — a job without a store (or a store that is down right
        # now) trains exactly as before, it just cannot drain gracefully.
        store = None  # its store client, which the planes below share
        if env.store_endpoint and env.job_id:
            try:
                stage.health = ctx.HealthMonitor(env)
                closing.callback(stage.health.close)
                store = stage.health.store_client
            except Exception as exc:  # noqa: BLE001
                print(
                    "elastic-trainer: health monitor unavailable (%s); "
                    "continuing without graceful drain" % exc,
                    file=sys.stderr,
                )
        # memory plane: compile-time plan + census/watermarks + OOM
        # forensics, per stage
        try:
            stage.mem_plane = obs_memory.MemoryPlane(
                stage=env.stage, rank=env.global_rank,
                client=store, job_id=env.job_id or "",
                expect_donation=True,  # make_train_step donates state
            )
            closing.callback(stage.mem_plane.close)
        except Exception as exc:  # noqa: BLE001 — memory plane is telemetry
            print(
                "elastic-trainer: memory plane unavailable (%s); "
                "continuing without it" % exc,
                file=sys.stderr,
            )
        # numerics plane: fused bundle + throttled host export. Shares
        # the health plane's store client for the cross-replica digest
        # exchange when one exists.
        if obs_numerics.enabled():
            stage.probe = obs_numerics.NumericsProbe(
                rank=env.global_rank, client=store, job_id=env.job_id or "",
            )
            closing.callback(stage.probe.close)
        leaving.callback(stage.census_dropped.set)
        leaving.enter_context(stage.mesh)
        # peek the checkpointed status FIRST: adjust callbacks are
        # contractually given (restored_status_or_None, world) so e.g.
        # epoch-aware lr schedules survive stop-resume
        peeked = stage.mngr.read_status() if stage.mngr is not None else None
        overrides = {}
        if self._adjusts is not None:
            overrides = self._adjusts.resolve(peeked, env.world_size)
        # one jitted program whose outputs are born on the mesh: no leaf is
        # ever committed to device 0 alone (it would clash with mesh-placed
        # args at jit time and checkpoint restore), under fsdp the full model
        # is on no device, and on a mesh that spans processes every process
        # runs the same program
        with tracer.span("state_init") as init_span:
            stage.state = jax.block_until_ready(
                create_state(
                    self._model, jax.random.PRNGKey(self._seed),
                    self._sample_input, self._make_tx(overrides),
                    shardings=_state_shardings(stage.mesh, self._fsdp),
                    **self._init_kwargs,
                )
            )
            leaves = jax.tree.leaves(stage.state)
            init_span.args = {
                "leaves": len(leaves), "bytes": sum(x.nbytes for x in leaves),
            }
        if stage.mngr is not None:
            stage.state, status = stage.mngr.restore(stage.state)
            if status and stage.probe is not None:
                # arm the resume-continuity check against the checkpoint's
                # stamped numerics fingerprint
                stage.probe.expect((status.meta or {}).get("numerics"))
            if status:
                stage.start_epoch = status.next_epoch()
                if env.is_rank0 and self._log:
                    print("elastic-trainer: resumed at epoch %d (world=%d%s)" % (
                        stage.start_epoch, env.world_size,
                        "".join(", %s=%s" % kv for kv in sorted(overrides.items())),
                    ))
        stage.step = make_train_step(
            self._loss, self._apply_kwargs, numerics=obs_numerics.enabled(),
        )
        stage.sharding = batch_sharding(stage.mesh, self._batch_axis)
        worker_barrier("elastic-trainer-start")
        # restage-trace segment: state build + restore + stage barrier (the
        # restore nests under it as its own span)
        tracer.record("train_setup", t_setup, time.monotonic() - t_setup)
        # goodput: everything from here until the first completed step is
        # attributed to compile (jit trace + XLA compile, or persistent-cache
        # load)
        obs_goodput.enter("compile", cause="first_step")
        # profiling plane: windowed MFU/roofline/HBM gauges (armed with the
        # step's cost analysis after the first step) + store-driven on-demand
        # jax.profiler windows. EDL_PROFILE_DIR keeps its historical meaning —
        # ONE env-armed window for the whole fit (the reference profiles
        # batches 100-105, train_with_fleet.py:524-534) — now riding the same
        # controller as store requests.
        stage.step_telemetry = obs_profile.StepTelemetry()
        closing.callback(stage.step_telemetry.close)
        try:
            stage.capture = obs_profile.CaptureController(
                env, telemetry=stage.step_telemetry
            )
            closing.callback(stage.capture.close)
            profile_dir = os.environ.get("EDL_PROFILE_DIR")
            if profile_dir:
                stage.capture.arm_local(profile_dir, start_after=10, steps=5)
        except Exception as exc:  # noqa: BLE001 — profiling is best-effort
            print(
                "elastic-trainer: capture plane unavailable (%s); "
                "continuing without it" % exc,
                file=sys.stderr,
            )
        return stage

    def _run_steps(self, stage: _Stage, epoch: int, data_fn: DataFn, monitor):
        """One epoch's steps: ``(the last step's metrics, steps run, when the
        epoch began)`` for ``_end_epoch``. What is in the ``while`` runs every
        step; what a stage does once is ``_after_first_step``."""
        tracer, probe, mem_plane = stage.tracer, stage.probe, stage.mem_plane
        metrics: Dict[str, Any] = {}
        batches = data_fn(epoch)
        if self._batch_size is not None:
            batches = (
                b for b, _ in batched(batches, self._batch_size, drop_remainder=True)
            )
        step_idx = 0
        t_epoch = time.monotonic()
        t_prev = t_epoch
        # explicit iterator: the time blocked in next() is the input
        # pipeline's fault (data_wait), the dispatch interval after it is the
        # step's (train) — the split the goodput ledger exists to make
        batch_iter = iter(prefetch_to_device(
            batches, depth=self._depth, sharding=stage.sharding, epoch=epoch,
        ))
        stage.retired.start_epoch()
        while True:
            if stage.first_step_done:
                obs_goodput.enter("data_wait")
            try:
                with tracer.span("data_wait", epoch=epoch, step=step_idx):
                    device_batch = next(batch_iter)
            except StopIteration:
                break
            if stage.first_step_done:
                obs_goodput.enter("train")
            if stage.health is not None and stage.health.drain_notice:
                # drain beats restage: this pod is leaving the job, not
                # joining the next generation
                self._drain_exit(
                    stage.health, stage.mngr, stage.state, epoch,
                    stage.steps_done, stage.env,
                )
            if monitor is not None and monitor.restage_pending:
                # between steps, never inside compiled code; the in-flight
                # step's work is simply dropped (same loss as a stop-resume
                # kill)
                raise _RestageRequested()
            # host cost of one dispatch: long when the runtime's queue is full
            with tracer.span("step_dispatch", epoch=epoch, step=step_idx):
                # under the memory plane's guard RESOURCE_EXHAUSTED leaves a
                # forensics bundle (census + device memory profile + the plan
                # + an fsync'd `oom` instant) before propagating into
                # drain/restage
                with (
                    mem_plane.oom_guard(step=stage.steps_done, epoch=epoch)
                    if mem_plane is not None
                    else contextlib.nullcontext()
                ):
                    stage.state, metrics = stage.step(stage.state, device_batch)
            # pop BEFORE any aggregation/printing: the bundle is device arrays
            # for the probe, not a scalar metric. No host sync here — the
            # probe fetches on its own throttle.
            bundle = metrics.pop(obs_numerics.METRICS_KEY, None)
            if probe is not None:
                fetched = probe.on_step(stage.steps_done, bundle, epoch=epoch)
                if fetched is not None:
                    stage.retired.mark(
                        fetched[0], fetched[1], epoch=epoch, gauges=fetched[2]
                    )
            # dispatch to dispatch: the loop runs ahead of the device, so this
            # is the host's interval, not the step's (RetireClock has that)
            t_now = time.monotonic()
            dt = t_now - t_prev
            if probe is None:
                # no wait for the device inside an epoch, so the runtime's
                # own queue paces the dispatches
                _M_STEP_SECONDS.observe(dt)
            _M_STEPS.inc()
            if stage.first_step_done:
                tracer.record("train_step", t_prev, dt, epoch=epoch, step=step_idx)
            else:
                self._after_first_step(
                    stage, epoch, step_idx, t_prev, dt, device_batch
                )
            stage.step_telemetry.observe_step(dt)
            if mem_plane is not None:
                # throttled census + watermark sample (EDL_MEM_CENSUS_EVERY;
                # metadata only, never a host sync on the step path)
                mem_plane.on_step(stage.steps_done)
            t_prev = t_now
            step_idx += 1
            stage.steps_done += 1
            if t_now - stage.last_flight >= 1.0:
                # throttled black-box marker: bounds a killed worker's open
                # goodput interval to <= 1 s
                stage.last_flight = t_now
                obs_events.record(
                    "train_heartbeat", step=stage.steps_done, epoch=epoch
                )
            if stage.health is not None:
                stage.health.heartbeat(stage.steps_done, dt)
            if stage.capture is not None:
                # store-driven profiler window state machine; the sync makes
                # the closing trace contain the device work it claims to
                stage.capture.on_step(
                    sync=lambda m=metrics: jax.block_until_ready(m)
                )
        return metrics, step_idx, t_epoch

    def _after_first_step(
        self, stage: _Stage, epoch: int, step_idx: int,
        t_prev: float, dt: float, device_batch,
    ) -> None:
        """Once a stage, when its first step has returned: what the ring
        holds of that step (its ``train_step`` in its place), then the step
        lowered once more for the cost model and the memory plan, the census
        thread and the AOT ladder."""
        tracer, env, mem_plane = stage.tracer, stage.env, stage.mem_plane
        # restage trace: the first completed step is the operation's closing
        # segment (jit trace + compile or cache load), recorded while the op
        # context is still live so it stitches — then the restage window ends
        _record_step_launch(tracer)
        tracer.record("first_step", t_prev, dt, epoch=epoch)
        obs_trace.end_process_op()
        tracer.record("train_step", t_prev, dt, epoch=epoch, step=step_idx)
        # the stage's cold-start cost: jit trace + compile (or
        # persistent-cache load)
        _M_FIRST_STEP.set(dt)
        stage.first_step_done = True
        obs_goodput.enter("train", cause="first_step")
        # one more jax trace of the step, shared by what reads the program:
        # XLA's cost analysis arms the MFU/roofline gauges; its compile (a
        # persistent-cache hit, no second XLA compile) gives the memory plane
        # THIS stage's plan and obs_profile.step_phases() the names. Its own
        # span: set-up time after `first_step` ends (milliseconds on the chip:
        # jax's in-process caches hand trace, lowering and executable back)
        with tracer.span("step_relower") as relower:
            lowered, compiled = _lower_step(
                stage.step, stage.state, device_batch,
                compile=mem_plane is not None,
            )
            stage.step_telemetry.set_cost(obs_profile.step_cost(lowered))
            plan = None
            if compiled is not None:
                plan = mem_plane.harvest(compiled, world=env.world_size)
                obs_profile.set_step_executable(compiled)
            relower.args["compiled"] = compiled is not None
        if compiled is not None:
            # the census of the compiled step: its text and a pass over it
            # take seconds, so on a thread of its own (a stage that trains to
            # its end waits for it); the stage's notes as they stand now,
            # before the ladder's thread traces other worlds
            stage.census = threading.Thread(
                target=_publish_step_census,
                args=(tracer, tracer.notes(), plan, env, stage.census_dropped),
                name="edl-step-census", daemon=True,
            )
            stage.census.start()
        # steady state reached: speculatively compile the N±1/N±2 neighbor
        # worlds into the persistent cache on a low-priority thread
        # (train/aot.py) so the NEXT resize re-jits from a cache load instead
        # of a compile
        if env.compile_cache_dir:
            stage.ladder = self._start_ladder(
                env, stage.step, stage.state, device_batch, mem_plane=mem_plane
            )
            if stage.ladder is not None:
                stage.closing.callback(stage.ladder.close)

    def _end_epoch(
        self, stage: _Stage, epoch: int, metrics: Dict[str, Any], steps: int,
        t_epoch: float, on_epoch_end: Optional[Callable[[int, Dict], None]],
    ) -> None:
        """The end of an epoch: the device drains, what was sown becomes
        gauges, the epoch is printed and recorded, then the caller's callback
        and the save."""
        env, tracer = stage.env, stage.tracer
        if stage.first_step_done:
            # the epoch-end device sync below is step work, not input wait
            obs_goodput.enter("train")
        if metrics:
            # the device drains: every step of the epoch has retired when
            # this returns
            with tracer.span("epoch_sync", epoch=epoch, step=steps - 1):
                jax.block_until_ready(metrics)
            t_synced = time.monotonic()
            # what the model sows (aux_loss, moe_load_max) and what the loss
            # head names as its ``gauges``, as gauges: the values have just
            # been waited for
            sown = {
                name: np.asarray(metrics[name])
                for name in (*stage.state.sown, *getattr(self._loss, "gauges", ()))
                if name in metrics
            }
            stage.retired.mark(
                stage.steps_done - 1, t_synced, epoch=epoch, gauges=sown
            )
            obs_numerics.publish_sown(sown)
        if env.is_rank0 and self._log and metrics:
            print("epoch %d %s" % (epoch, " ".join(
                "%s %.4f" % (k, float(np.asarray(v)))
                for k, v in sorted(metrics.items())
                if np.asarray(v).ndim == 0
            )))
        if not metrics and env.is_rank0 and self._log:
            print(
                "epoch %d produced no full batches "
                "(fewer than batch_size records?)" % epoch
            )
        _M_EPOCHS.inc()
        tracer.record(
            "train_epoch", t_epoch, time.monotonic() - t_epoch,
            epoch=epoch, steps=steps,
        )
        if on_epoch_end is not None:
            with tracer.span("epoch_end_hook", epoch=epoch):
                on_epoch_end(epoch, metrics)
        if stage.mngr is not None:
            stage.mngr.save(
                stage.state, TrainStatus(epoch=epoch, step=int(stage.state.step))
            )

    def _start_ladder(self, env, step, state, device_batch, mem_plane=None):
        """Arm the AOT resize ladder for this stage (best-effort)."""
        from edl_tpu.train import aot

        if not aot.aot_enabled():
            return None
        try:
            worlds = aot.neighbor_worlds(
                env.world_size, env.nproc_per_node,
                env.min_nodes, env.max_nodes,
            )
            if not worlds:
                return None
            compile_for = aot.make_neighbor_compiler(
                step, state, device_batch,
                mesh_axes=self._mesh_axes, batch_axis=self._batch_axis,
                devices_per_proc=aot.devices_per_process(env),
                # each rung's executable was compiled anyway — its
                # memory plan is free, and publishing it is what lets
                # the scale plane fit-gate THAT world before choosing it
                on_compiled=(
                    mem_plane.harvest_rung if mem_plane is not None else None
                ),
            )
            return aot.AotLadder(env, compile_for, worlds=worlds).start()
        except Exception as exc:  # noqa: BLE001 — speculation must not gate training
            print(
                "elastic-trainer: aot ladder unavailable (%s); resizes "
                "will compile on arrival" % exc,
                file=sys.stderr,
            )
            return None

    def evaluate(self, state: TrainState, data_fn: Callable[[], Iterable]):
        """Run one evaluation pass and return sample-weighted mean metrics.

        ``data_fn()`` yields records (when ``batch_size`` is set) or
        ready host batches, like ``fit``'s per-epoch data. The final
        ragged batch is NOT dropped: ``batched``'s pad+mask keeps shapes
        static and the metric mean weights each batch by its valid-row
        count, so eval covers every record exactly once — the part the
        reference leaves to Paddle's test loop (train_with_fleet.py's
        test pass).
        """
        from edl_tpu.train.step import make_eval_step, make_masked_eval_step

        mesh = make_mesh(self._mesh_axes)
        if self._eval_step is None:
            self._eval_step = make_eval_step(self._loss, self._apply_kwargs)
            self._masked_eval_step = make_masked_eval_step(
                self._loss, self._apply_kwargs
            )
        eval_step = self._eval_step
        masked_eval_step = self._masked_eval_step
        pending = []  # (device metrics, n_valid): fetched once at the end

        with mesh:
            sharding = batch_sharding(mesh, self._batch_axis)
            batches = data_fn()
            if self._batch_size is not None:
                pairs = batched(batches, self._batch_size)
            else:
                pairs = ((b, None) for b in batches)
            # full batches ride the same overlapped transfer pipeline as
            # fit; the (single, final) ragged batch is set aside
            ragged = []

            def full_batches():
                for b, m in pairs:
                    if m is not None and not m.all():
                        ragged.append((b, m))
                    else:
                        yield b

            for placed in prefetch_to_device(
                full_batches(), depth=self._depth, sharding=sharding
            ):
                n = float(jax.tree.leaves(placed)[0].shape[0])
                # no host sync inside the loop: batch N+1 dispatches while
                # batch N computes; everything is fetched once at the end
                pending.append((eval_step(state, placed), n))

            for host_batch, mask in ragged:
                # padded tail stays at the STATIC batch shape (no per-process
                # shape divergence under sharded params); pad rows are
                # excluded by the mask inside the jitted step, and the
                # batch's weight is the global valid-row count it returns
                placed = shard_batch(mesh, host_batch, self._batch_axis)
                mask_dev = shard_batch(mesh, np.asarray(mask), self._batch_axis)
                pending.append(masked_eval_step(state, placed, mask_dev))
        totals: Dict[str, float] = {}
        weight = 0.0
        for metrics, n_valid in pending:
            n_valid = float(np.asarray(n_valid))
            for name, v in metrics.items():
                arr = np.asarray(v)  # blocks; all compute already queued
                if arr.ndim == 0:
                    totals[name] = totals.get(name, 0.0) + float(arr) * n_valid
            weight += n_valid
        return {name: v / max(weight, 1.0) for name, v in totals.items()}
