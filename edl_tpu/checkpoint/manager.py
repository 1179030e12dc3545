"""Sharded checkpoint/resume across topology change.

Capability parity with the reference's checkpoint contract — the *only*
state carried across an elastic resize (reference
example/collective/resnet50/train_with_fleet.py:422-428, 563-570:
``fleet.save_check_point/load_check_point`` with ``TrainStatus(epoch)``,
rank-0 saves per epoch, atomic write-temp-then-rename with incrementing
version per doc/fault_tolerance.md:19-28) — rebuilt on Orbax:

- arrays are saved **sharded** from every host and restored under *any*
  new mesh/sharding (the template's shardings win), so resume across a
  4→8 or 8→4 host resize needs no gather/re-scatter step — this is where
  the TPU-native design beats the reference, whose resume is
  whole-checkpoint-per-rank;
- atomicity and version counting are Orbax's finalize protocol (same
  temp-then-rename semantics the reference documents);
- ``TrainStatus`` (epoch/step/world size + free-form meta) rides along as
  JSON, exactly the role of the reference's ``TrainStatus`` + the
  step-level offsets its WIP ``DataCheckpoint`` sketches
  (python/edl/collective/data_reader.py:63-84).
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from edl_tpu.chaos.plane import fault_point as _fault_point
from edl_tpu.obs import events as obs_events
from edl_tpu.obs import goodput as obs_goodput
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import numerics as obs_numerics
from edl_tpu.obs import trace as obs_trace
from edl_tpu.utils.log import get_logger

logger = get_logger("checkpoint.manager")

_FP_SAVE = _fault_point(
    "ckpt.save",
    "before a checkpoint save: kill (crash mid-save -> torn temp dirs, "
    "the finalize protocol must keep the previous version good) or delay",
)
_FP_RESTORE = _fault_point(
    "ckpt.restore", "before a checkpoint restore: delay (slow storage)"
)
_FP_EMERGENCY = _fault_point(
    "ckpt.emergency",
    "before an emergency (drain-notice) checkpoint: delay (slow storage "
    "eats the drain budget) or kill (preemption lands mid-save; the torn "
    "version must quarantine on restore)",
)

_M_SAVE_SECONDS = obs_metrics.histogram(
    "edl_ckpt_save_seconds", "checkpoint save blocking time"
)
_M_RESTORE_SECONDS = obs_metrics.histogram(
    "edl_ckpt_restore_seconds", "checkpoint restore time"
)
_M_SAVES = obs_metrics.counter("edl_ckpt_saves_total", "checkpoints saved")
_M_RESTORES = obs_metrics.counter(
    "edl_ckpt_restores_total",
    "checkpoints restored, by source tier (local/peer/durable)",
)
_M_SAVE_BYTES = obs_metrics.counter(
    "edl_ckpt_save_bytes_total", "logical array bytes written to checkpoints"
)
_M_RESTORE_BYTES = obs_metrics.counter(
    "edl_ckpt_restore_bytes_total", "logical array bytes restored from checkpoints"
)
_M_SAVE_SIZE = obs_metrics.histogram(
    "edl_ckpt_save_size_bytes", "logical size of each saved checkpoint",
    buckets=obs_metrics.SIZE_BUCKETS,
)
_M_RESTORE_FALLBACKS = obs_metrics.counter(
    "edl_ckpt_restore_fallbacks_total",
    "unreadable checkpoint versions skipped during restore",
)
_M_EMERGENCY_SECONDS = obs_metrics.histogram(
    "edl_train_emergency_ckpt_seconds",
    "wall time of drain-notice emergency checkpoints (save + bounded wait)",
)
_M_EMERGENCY = obs_metrics.counter(
    "edl_ckpt_emergency_saves_total",
    "emergency checkpoint actions on a drain notice, by outcome "
    "(skipped/failed/finished/unfinished/replicated/replicate_failed)",
)


def _tree_bytes(tree) -> int:
    """Logical (unsharded) byte size of a state pytree; best-effort."""
    total = 0
    try:
        for leaf in jax.tree.leaves(tree):
            nbytes = getattr(leaf, "nbytes", None)
            if nbytes is not None:
                total += int(nbytes)
    except Exception:  # noqa: BLE001 — metrics must not fail a save
        pass
    return total


@dataclasses.dataclass
class TrainStatus:
    """Progress metadata carried inside every checkpoint."""

    epoch: int = -1
    step: int = 0
    world_size: int = 1
    sample_offset: int = 0  # samples consumed within the current epoch
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def next_epoch(self) -> int:
        return self.epoch + 1

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "TrainStatus":
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls) if f.name in d})


def abstract_like(tree):
    """Abstract (shape/dtype/sharding) template of a live state pytree.

    Build the template from a *freshly initialized* state on the new mesh:
    its shardings describe where restored arrays should land, which is what
    makes cross-topology resume automatic.
    """

    def to_abstract(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        return x

    return jax.tree.map(to_abstract, tree)


class CheckpointManager:
    """Epoch/step-versioned sharded checkpoints with retention — and,
    when a pod-local tier is armed, a multi-tier restore ladder.

    ``save`` is collective (all hosts write their shards; Orbax finalizes
    atomically); ``restore`` reshards onto the template's mesh. A missing
    or empty directory restores to ``(template-as-is, None)`` so first
    launch and resume share one code path — mirroring the reference's
    ``load_check_point`` returning a fresh ``TrainStatus`` when no
    checkpoint exists (train_with_fleet.py:428).

    **Checkpoint tiers** (DESIGN.md "Checkpoint tiers & peer
    replication"). With ``local_dir`` set (or ``EDL_CKPT_LOCAL_DIR`` in
    the env — the launcher derives a per-pod path from
    ``EDL_CKPT_LOCAL_BASE``), saves land in the pod-LOCAL tier at disk
    speed; a background :class:`~edl_tpu.checkpoint.replicate.Replicator`
    then pushes the finalized shards to K ring-successor peers and
    mirrors them into ``path``, which demotes to the durable backstop.
    ``restore`` walks the ladder — local dir → peer replicas (assembled
    from the ``ckpt/replicas/`` manifests) → durable tier — so a killed
    pod's replacement recovers with zero shared-FS reads whenever the
    surviving peers hold a complete replica. Restores are attributed per
    tier (``edl_ckpt_restores_total{tier}``, the goodput ``ckpt_restore``
    cause, and the flight record's ``tier`` field). Without a local
    tier, ``path`` is the single durable tier and behavior is exactly
    the classic one (restores labeled ``tier="durable"``).
    """

    def __init__(
        self,
        path: str,
        max_to_keep: int = 3,
        async_save: bool = False,
        local_dir: Optional[str] = None,
    ) -> None:
        import orbax.checkpoint as ocp

        self._ocp = ocp
        if local_dir is None:
            local_dir = os.environ.get("EDL_CKPT_LOCAL_DIR", "")
        path = os.path.abspath(os.fspath(path))
        if local_dir:
            self.path = os.path.abspath(os.fspath(local_dir))
            self.durable_path: Optional[str] = path
            self._tier = "local"
        else:
            self.path = path
            self.durable_path = None
            self._tier = "durable"
        self._async = async_save
        options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            create=True,
            enable_async_checkpointing=async_save,
        )
        self._mngr = ocp.CheckpointManager(self.path, options=options)
        # the saver-side replication plane (peer push + durable mirror);
        # None unless the local tier AND the worker env contract are armed
        self._replicator = None
        if self.durable_path is not None:
            from edl_tpu.checkpoint import replicate as _replicate

            self._replicator = _replicate.make_replicator(
                self.path, durable_path=self.durable_path
            )

    # -- save --------------------------------------------------------------

    def save(self, state, status: TrainStatus, step: Optional[int] = None) -> int:
        ocp = self._ocp
        if step is None:
            step = int(status.step)
        if _FP_SAVE.armed:
            _FP_SAVE.fire(step=step)
        t0 = time.monotonic()
        # goodput: the BLOCKING portion of the save is checkpoint cost,
        # not train time (async saves return early by design).
        # child_span: inside a live operation (a drain's emergency save,
        # a restage) the save stitches to it; standalone it roots its own
        # ckpt_save trace — the operation-root classification of DESIGN.md
        # "Distributed tracing"
        status_doc = status.to_dict()
        try:
            # resize continuity sentinel: the manifest carries a
            # {step, loss, param_norm} numerics fingerprint — restore
            # re-derives the norm (quarantining mismatches) and the
            # restaged worker's probe asserts loss continuity against it.
            # Its own span: the stamp fetches every parameter (a device
            # sync), which the ckpt_save span below leaves out while
            # edl_ckpt_save_seconds counts it
            with obs_trace.span("ckpt_stamp", step=step, epoch=status.epoch):
                status_doc = obs_numerics.stamp_fingerprint(
                    status_doc, state, step
                )
        except Exception as exc:  # noqa: BLE001 — the stamp must never fail a save
            logger.warning("numerics fingerprint stamp failed: %s", exc)
        with obs_trace.child_span("ckpt_save", step=str(step)):
            with obs_goodput.phase("ckpt_save"):
                self._mngr.save(
                    step,
                    args=ocp.args.Composite(
                        state=ocp.args.StandardSave(state),
                        status=ocp.args.JsonSave(status_doc),
                    ),
                )
            dt = time.monotonic() - t0  # async saves: the blocking portion
            _M_SAVE_SECONDS.observe(dt)
            _M_SAVES.inc()
            nbytes = _tree_bytes(state)
            _M_SAVE_BYTES.inc(nbytes)
            _M_SAVE_SIZE.observe(nbytes)
            obs_events.record(
                "ckpt_save", step=step, seconds=round(dt, 4), bytes=nbytes
            )
        if self._replicator is not None:
            # sync saves are finalized here; async ones finalize in the
            # background — the replicator re-checks until the step dir
            # appears, so an async-save job replicates DURING training,
            # not at the one wait() the trainer issues at job end
            self._replicator.note_save(step)
        return step

    def wait(self) -> None:
        self._mngr.wait_until_finished()
        if self._replicator is not None:
            latest = self._mngr.latest_step()
            if latest is not None:
                self._replicator.note_save(int(latest))

    def emergency_save(
        self, state, status: TrainStatus, budget_s: float, step: Optional[int] = None
    ) -> Tuple[Optional[int], bool]:
        """Best-effort checkpoint on a preemption notice, bounded by
        ``budget_s``: rides the normal (possibly async) save path, then
        waits for finalization only as long as the budget allows. Returns
        ``(step, finished)``; ``finished=False`` means the save may still
        be in flight when the process exits — a torn version is exactly
        what the restore-side quarantine absorbs, so an unfinished
        emergency save degrades to the previous periodic checkpoint, never
        to a wedged restore.

        A step already covered by the newest finalized version is skipped
        (nothing to save: the drain loses zero work) and reported as
        ``(latest, True)``.
        """
        if step is None:
            step = int(status.step)
        t0 = time.monotonic()
        latest = self.latest_step()
        if latest is not None and step <= latest:
            _M_EMERGENCY.inc(outcome="skipped")
            return latest, True
        if _FP_EMERGENCY.armed:
            _FP_EMERGENCY.fire(step=step)
        with obs_goodput.phase("ckpt_save", cause="emergency"):
            try:
                self.save(state, status, step=step)
            except Exception as exc:  # noqa: BLE001 — a failed emergency save
                # must not turn the drain into a crash: the previous periodic
                # version is still good, and DRAINED_EXIT must still happen
                logger.warning("emergency checkpoint at step %d failed: %s", step, exc)
                _M_EMERGENCY.inc(outcome="failed")
                _M_EMERGENCY_SECONDS.observe(time.monotonic() - t0)
                obs_events.record(
                    "ckpt_emergency", fsync=True, step=step, outcome="failed"
                )
                return None, False
            remaining = budget_s - (time.monotonic() - t0)
            finished = self._wait_within(max(0.0, remaining))
        dt = time.monotonic() - t0
        _M_EMERGENCY_SECONDS.observe(dt)
        _M_EMERGENCY.inc(outcome="finished" if finished else "unfinished")
        obs_trace.get_tracer().instant(
            "ckpt_emergency", step=str(step),
            finished=str(finished).lower(),
        )
        obs_events.record(
            "ckpt_emergency", fsync=True, step=step,
            outcome="finished" if finished else "unfinished",
            seconds=round(dt, 4), budget_s=budget_s,
        )
        logger.info(
            "emergency checkpoint at step %d %s in %.2fs (budget %.1fs)",
            step, "finalized" if finished else "still in flight", dt, budget_s,
        )
        return step, finished

    def _wait_within(self, timeout_s: float) -> bool:
        """``wait()`` bounded by a timeout (Orbax exposes none): run the
        wait in a daemon thread and join with the budget. On timeout the
        finalization keeps running in the background — the caller exits
        anyway, and restore-side fallback owns the torn-version case."""
        import threading

        done = threading.Event()

        def _wait():
            try:
                self._mngr.wait_until_finished()
            except Exception as exc:  # noqa: BLE001
                logger.warning("emergency checkpoint finalize failed: %s", exc)
            finally:
                done.set()

        t = threading.Thread(target=_wait, name="edl-ckpt-emergency", daemon=True)
        t.start()
        return done.wait(timeout_s)

    def emergency_replicate(self, budget_s: float) -> bool:
        """Per-pod, NON-COLLECTIVE emergency durability: push the newest
        finalized local step to peer holders inside ``budget_s``.

        This closes the multi-pod-drain gap: a single draining pod of a
        multi-pod stage cannot run :meth:`emergency_save` (Orbax saves
        are collective — its peers will never join), but it CAN make the
        checkpoints it already holds survive its departure, because a
        replica push involves nobody's cooperation but one peer's.
        Returns True when at least one peer acked a complete copy."""
        if self._replicator is None or not self._replicator.peers_armed:
            return False  # mirror-only configs have no peers to push to
        t0 = time.monotonic()
        # an async save may still be finalizing: give it a slice of the
        # budget so the NEWEST version is what survives
        if self._async:
            self._wait_within(max(0.0, budget_s * 0.5))
            latest = self._mngr.latest_step()
            if latest is not None:
                self._replicator.note_save(int(latest))
        ok = self._replicator.flush(
            max(0.5, budget_s - (time.monotonic() - t0))
        )
        _M_EMERGENCY.inc(outcome="replicated" if ok else "replicate_failed")
        obs_events.record(
            "ckpt_emergency_repl", fsync=True,
            outcome="ok" if ok else "failed",
            seconds=round(time.monotonic() - t0, 4), budget_s=budget_s,
        )
        logger.info(
            "emergency replication %s in %.2fs (budget %.1fs)",
            "complete" if ok else "FAILED",
            time.monotonic() - t0, budget_s,
        )
        return ok

    # -- restore -----------------------------------------------------------

    def latest_step(self) -> Optional[int]:
        return self._mngr.latest_step()

    def _candidates(self, step: Optional[int]) -> list:
        """Versions to try, newest first. An explicit ``step`` pins the
        list to that one version (the caller asked for it specifically)."""
        if step is not None:
            return [step]
        return sorted(self._mngr.all_steps(), reverse=True)

    def read_status(self, step: Optional[int] = None) -> Optional[TrainStatus]:
        """Read the latest TrainStatus WITHOUT restoring model state —
        cheap (json only), for decisions that must happen before the
        optimizer/state exist (e.g. status-aware hyper-parameter
        adjustment on resume). Unreadable versions fall back like
        :meth:`restore`."""
        ocp = self._ocp
        candidates = self._candidates(step)
        if not candidates:
            return None
        last_exc: Optional[Exception] = None
        for s in candidates:
            try:
                restored = self._mngr.restore(
                    s, args=ocp.args.Composite(status=ocp.args.JsonRestore())
                )
                return TrainStatus.from_dict(restored["status"])
            except Exception as exc:  # noqa: BLE001 — any torn version falls back
                last_exc = exc
                if step is None:
                    _M_RESTORE_FALLBACKS.inc()
                    logger.warning(
                        "checkpoint status at step %d unreadable (%s); "
                        "falling back to the previous version", s, exc,
                    )
        raise last_exc

    def restore(
        self, template, step: Optional[int] = None
    ) -> Tuple[Any, Optional[TrainStatus]]:
        """Restore onto ``template``'s shardings; (template, None) when
        every tier is empty.

        A torn/corrupt newest version (crash mid-upload, bad disk) must
        not take the job down when an older good version exists: with no
        explicit ``step``, unreadable versions are skipped newest-to-
        oldest with a warning (counted in
        ``edl_ckpt_restore_fallbacks_total``).

        With a local tier armed, restore walks the TIER LADDER,
        freshness first: candidate steps are gathered from the local
        dir, the complete PEER replicas advertised in ``ckpt/replicas/``
        manifests, and the DURABLE backstop, then tried newest step
        first with ties preferring the cheapest read (local → peer →
        durable). Peer steps are assembled shard-by-shard into the local
        tier (digest-verified, atomic step-dir rename); durable steps
        are copied in; an assembled/copied version that still fails
        Orbax's restore quarantines via the ``.corrupt`` rename path
        like any torn version and the walk continues. Only when every
        tier is exhausted does the last error propagate — that is real
        data loss, not a recoverable fault. An explicit ``step`` pins
        the restore to the primary tier, as before.
        """
        candidates = self._candidates(step)
        if _FP_RESTORE.armed and (candidates or self.durable_path):
            _FP_RESTORE.fire(step=candidates[0] if candidates else -1)
        last_exc: List[Optional[Exception]] = [None]
        bad: list = []
        if step is not None:
            out = self._try_candidates(
                template, candidates, True, self._tier, last_exc, bad
            )
            if out is not None:
                return out
            raise last_exc[0]
        if self.durable_path is None:
            # classic single-tier plane: exactly the pre-ladder behavior
            out = self._try_candidates(
                template, candidates, False, self._tier, last_exc, bad
            )
            if out is not None:
                return out
            if last_exc[0] is not None:
                raise last_exc[0]
            return template, None
        return self._restore_ladder(template, candidates, last_exc, bad)

    def _restore_ladder(
        self, template, local_steps, last_exc, bad
    ) -> Tuple[Any, Optional[TrainStatus]]:
        """Freshness-FIRST tier walk: candidate steps are gathered from
        every tier and tried newest step first regardless of tier (a
        stale peer replica must never shadow a newer durable version —
        e.g. a push that failed while the background mirror landed);
        ties prefer the cheapest read: local → peer → durable."""
        from edl_tpu.checkpoint import replicate as _replicate

        # ONE store client for the whole walk: recovery is when the
        # control plane is most likely degraded, and per-attempt 5s
        # connect timeouts would eat the downtime budget reconnecting
        peer_client = None
        peer_steps: List[int] = []
        if self._peer_tier_enabled():
            try:
                from edl_tpu.store.client import connect_store

                peer_client = connect_store(
                    os.environ.get("EDL_STORE_ENDPOINT", ""), timeout=5.0
                )
                peer_steps = _replicate.peer_complete_steps(
                    client=peer_client,
                    job_id=os.environ.get("EDL_JOB_ID", ""),
                )
            except Exception as exc:  # noqa: BLE001 — a tier, not a gate
                logger.warning("peer-tier peek failed: %s", exc)
        try:
            durable_steps = _replicate.finalized_steps(self.durable_path)
            plan: List[Tuple[int, str]] = []
            for s in sorted(
                {*local_steps, *peer_steps, *durable_steps}, reverse=True
            ):
                if s in local_steps:
                    plan.append((s, self._tier))
                if s in peer_steps:
                    plan.append((s, "peer"))
                if s in durable_steps:
                    plan.append((s, "durable"))
            for s, tier in plan:
                if tier == "peer":
                    if self._assemble_peer(s, peer_client) is None:
                        continue
                    self._reload()
                elif tier == "durable":
                    if not self._copy_from_durable(s):
                        continue
                    self._reload()
                out = self._try_candidates(
                    template, [s], False, tier, last_exc, bad
                )
                if out is not None:
                    return out
                if bad:
                    # quarantine NOW: the same step may exist in the next
                    # tier, and the torn copy must not squat on its name
                    # (nor shadow it as latest_step for future saves)
                    self._purge(bad)
                    bad[:] = []
                    self._reload()
        finally:
            if peer_client is not None:
                try:
                    peer_client.close()
                except Exception:  # noqa: BLE001
                    pass
        if last_exc[0] is not None:
            raise last_exc[0]
        return template, None

    def _try_candidates(
        self, template, candidates, pinned: bool, tier: str, last_exc, bad
    ) -> Optional[Tuple[Any, Optional[TrainStatus]]]:
        """One tier's restore attempt over ``candidates`` (newest
        first); returns the restored pair or None with ``last_exc[0]``/
        ``bad`` updated for the caller's ladder bookkeeping."""
        ocp = self._ocp
        for s in candidates:
            t0 = time.monotonic()
            try:
                # child_span: stitches into a live restage/drain trace
                # (the worker-side restore hop of the critical path), or
                # roots a standalone ckpt_restore trace. A failed attempt
                # records too, so fallback laps are visible in the trace.
                with obs_trace.child_span(
                    "ckpt_restore", step=str(s), tier=tier
                ):
                    with obs_goodput.phase("ckpt_restore", cause=tier):
                        restored = self._mngr.restore(
                            s,
                            args=ocp.args.Composite(
                                state=ocp.args.StandardRestore(abstract_like(template)),
                                status=ocp.args.JsonRestore(),
                            ),
                        )
                # re-derive the manifest's numerics fingerprint: bytes
                # Orbax accepted but the trainer never saved (torn or
                # tampered state) quarantine exactly like a torn version
                fp = ((restored.get("status") or {}).get("meta") or {}).get(
                    "numerics"
                )
                fp_ok, fp_detail = obs_numerics.verify_fingerprint(
                    restored["state"], fp
                )
                if not fp_ok:
                    raise RuntimeError(
                        "numerics fingerprint mismatch: %s" % fp_detail
                    )
            except Exception as exc:  # noqa: BLE001 — any torn version falls back
                last_exc[0] = exc
                if not pinned:
                    _M_RESTORE_FALLBACKS.inc()
                    bad.append(s)
                    logger.warning(
                        "checkpoint step %d unreadable (%s); falling back "
                        "to the previous version/tier", s, exc,
                    )
                continue
            dt = time.monotonic() - t0
            _M_RESTORE_SECONDS.observe(dt)
            _M_RESTORES.inc(tier=tier)
            _M_RESTORE_BYTES.inc(_tree_bytes(restored["state"]))
            obs_events.record(
                "ckpt_restore", fsync=True, step=s, tier=tier,
                seconds=round(dt, 4), fallbacks=len(bad),
            )
            self._purge(bad)
            if tier != self._tier:
                logger.info(
                    "restored step %d from the %s tier", s, tier
                )
            return restored["state"], TrainStatus.from_dict(restored["status"])
        return None

    def _reload(self) -> None:
        reload_fn = getattr(self._mngr, "reload", None)
        if reload_fn is not None:
            reload_fn()  # a tier landed a new step dir: drop cached lists

    def _peer_tier_enabled(self) -> bool:
        from edl_tpu.checkpoint import replicate as _replicate

        return (
            self.durable_path is not None
            and _replicate.replica_count() > 0
            and bool(os.environ.get("EDL_STORE_ENDPOINT"))
            and bool(os.environ.get("EDL_JOB_ID"))
        )

    def _assemble_peer(
        self, step: Optional[int] = None, client=None
    ) -> Optional[int]:
        from edl_tpu.checkpoint import replicate as _replicate

        try:
            return _replicate.assemble_from_peers(
                self.path,
                client=client,
                endpoint=os.environ.get("EDL_STORE_ENDPOINT", ""),
                job_id=os.environ.get("EDL_JOB_ID", ""),
                step=step,
            )
        except Exception as exc:  # noqa: BLE001 — a tier, never a gate
            logger.warning("peer-tier assembly failed: %s", exc)
            return None

    def _copy_from_durable(self, s: int) -> bool:
        """Land durable version ``s`` in the local tier (tmp dir +
        atomic rename) so one Orbax manager serves every tier."""
        import shutil

        src = os.path.join(self.durable_path, str(s))
        dst = os.path.join(self.path, str(s))
        if os.path.isdir(dst):
            return True
        tmp = os.path.join(self.path, ".durable-%d-%d" % (s, os.getpid()))
        try:
            shutil.rmtree(tmp, ignore_errors=True)
            shutil.copytree(src, tmp)
            os.replace(tmp, dst)
            return True
        except OSError as exc:
            logger.warning(
                "durable-tier copy of step %d failed: %s", s, exc
            )
            shutil.rmtree(tmp, ignore_errors=True)
            return False

    def _purge(self, bad_steps) -> None:
        """QUARANTINE versions that failed to restore (rename the step dir
        to ``<step>.corrupt``): left in place they would shadow the good
        version as ``latest_step`` and collide with post-resume re-saves
        of the same step numbers. A rename — never a delete — because the
        failure might be the READER's (template/sharding mismatch,
        transient storage error), and destroying the newest checkpoint on
        a reader-side fault would turn a recoverable incident into data
        loss. Operators can inspect or restore the quarantined dir."""
        for s in bad_steps:
            src = os.path.join(self.path, str(s))
            if not os.path.isdir(src):
                continue
            # unique destination: the SAME step can be torn again after a
            # resume re-saved it (second crash mid-save) — a taken
            # .corrupt name must not silently leave the bad version live
            dst = "%s.corrupt" % src
            n = 0
            while os.path.exists(dst):
                n += 1
                dst = "%s.corrupt.%d" % (src, n)
            try:
                os.replace(src, dst)
                reload_fn = getattr(self._mngr, "reload", None)
                if reload_fn is not None:
                    reload_fn()  # drop any cached step list
                logger.warning(
                    "quarantined unreadable checkpoint version %d -> %s",
                    s, dst,
                )
            except OSError as exc:
                logger.warning(
                    "could not quarantine unreadable checkpoint %d: %s", s, exc
                )

    def all_steps(self):
        return sorted(self._mngr.all_steps())

    def close(self) -> None:
        if self._replicator is not None:
            self._replicator.close()
        self._mngr.close()

    def __enter__(self) -> "CheckpointManager":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
