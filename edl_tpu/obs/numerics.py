"""Numerics observability plane: is the job still *learning*?

Every observer before this one watches the control plane — goodput
prices wall-clock, traces price RPCs, the checkpoint ledger prices
durability — but a resize that corrupts optimizer state, a bit-flipped
gradient, or silently diverged dp replicas is invisible until an
offline convergence run hours later. This module watches the *model*:

- :func:`device_bundle` — a pure-jnp scalar bundle fused into the
  jitted train step (loss, global grad norm, param norm, update ratio,
  non-finite element count, optional half-batch grad norms for the
  gradient-noise-scale estimate). Everything stays on device as 0-d
  f32 arrays; nothing here reads the host clock or environment.
- :class:`NumericsProbe` — the host half. It swaps the freshly
  computed bundle into a one-deep buffer every step and only
  device-transfers every ``EDL_NUMERICS_EVERY`` steps, and then it
  fetches the *previous* step's bundle — whose computation has had a
  full step to retire — so the probe never adds a sync stall to the
  hot path. Published values land as ``edl_train_*`` gauges, flight
  records (``numerics`` / ``nonfinite`` / ``loss_spike`` instants for
  ``edl-timeline``), a windowed gradient-noise-scale estimate
  (McCandlish et al., *An Empirical Model of Large-Batch Training*:
  the small-batch/large-batch norm trick over the two half-batch
  gradients the step already averaged), and a cross-replica parameter
  digest published through the store so ``edl_train_replica_divergence``
  reads the relative spread across dp replicas *at the same step*.
- the **resize continuity sentinel** — :func:`stamp_fingerprint` puts
  a ``{step, loss, param_norm}`` fingerprint into the checkpoint
  manifest at save, :func:`verify_fingerprint` re-derives the param
  norm at restore (a mismatched candidate is quarantined like any
  corrupt checkpoint), and :meth:`NumericsProbe.expect` asserts
  post-resume loss continuity within ``EDL_NUMERICS_LOSS_TOL`` —
  flight-recorded as ``numerics_resume`` so the chaos invariant
  ``numerics_continuous`` can gate worker-kill/preempt-drain drills.

Knobs: ``EDL_NUMERICS`` (``0`` disables the plane), ``EDL_NUMERICS_EVERY``
(device->host transfer cadence, steps), ``EDL_NUMERICS_GNS`` (``0``
skips the half-batch gradient pass), ``EDL_NUMERICS_FP_TOL``
(fingerprint param-norm relative tolerance), ``EDL_NUMERICS_LOSS_TOL``
(post-resume loss-continuity relative tolerance).
"""

from __future__ import annotations

import collections
import json
import math
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from edl_tpu.obs import events as obs_events
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import trace as obs_trace
from edl_tpu.utils.log import get_logger

logger = get_logger("obs.numerics")

#: reserved key the fused probe smuggles its bundle through in the train
#: step's metrics dict — the loop pops it before metrics aggregation
METRICS_KEY = "_numerics"

ENV_ENABLED = "EDL_NUMERICS"
ENV_EVERY = "EDL_NUMERICS_EVERY"
ENV_GNS = "EDL_NUMERICS_GNS"
ENV_FP_TOL = "EDL_NUMERICS_FP_TOL"
ENV_LOSS_TOL = "EDL_NUMERICS_LOSS_TOL"

DEFAULT_EVERY = 8
DEFAULT_FP_TOL = 1e-4       # fingerprint param-norm relative tolerance
DEFAULT_LOSS_TOL = 0.5      # post-resume loss-continuity relative tolerance

_GNS_WINDOW = 32            # (g2, s) pairs retained for the windowed GNS
_SPIKE_HISTORY = 64         # published losses retained for spike detection
_SPIKE_MIN_HISTORY = 6      # finite points required before a z is trusted
_SPIKE_Z = 4.0              # host-side twin of the loss-spike monitor rule
_DIGEST_SERVICE = "numerics"

# newest (step, device-bundle) any probe in this process has seen —
# fingerprint_for_save reads the loss out of it at checkpoint time (a
# save is already a sync point, so the one device_get is free)
_LATEST: Optional[Tuple[int, Dict[str, Any]]] = None
_LATEST_LOCK = threading.Lock()


def enabled() -> bool:
    return os.environ.get("EDL_NUMERICS", "1") != "0"


def _reset() -> None:
    """Forget cross-probe module state (tests)."""
    global _LATEST
    with _LATEST_LOCK:
        _LATEST = None


# -- device side (pure jnp: traced inside the jitted train step) ----------


def _inexact_leaves(tree) -> List[Any]:
    return [
        leaf
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.inexact)
    ]


def _sq_norm(tree) -> jnp.ndarray:
    """Global squared L2 norm over the inexact leaves, f32 accumulation."""
    leaves = _inexact_leaves(tree)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    total = jnp.zeros((), jnp.float32)
    for leaf in leaves:
        total = total + jnp.sum(jnp.square(leaf.astype(jnp.float32)))
    return total


def _nonfinite_count(tree) -> jnp.ndarray:
    leaves = _inexact_leaves(tree)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    total = jnp.zeros((), jnp.float32)
    for leaf in leaves:
        total = total + jnp.sum((~jnp.isfinite(leaf)).astype(jnp.float32))
    return total


def device_bundle(
    loss,
    grads,
    params,
    new_params,
    halves: Optional[Tuple[Any, Any]] = None,
    batch: Optional[int] = None,
) -> Dict[str, jnp.ndarray]:
    """The per-step scalar bundle, computed on device inside the jitted
    step: a dict of 0-d f32 arrays (plus the 2-vector ``half_sq`` when
    the GNS half-gradients are available). ``params`` is the pre-update
    tree, ``new_params`` post-update; the new-param norm doubles as the
    cross-replica digest (bitwise-deterministic per step on identical
    replicas)."""
    loss32 = jnp.asarray(loss, jnp.float32)
    old_sq = _sq_norm(params)
    delta = jax.tree_util.tree_map(
        lambda new, old: new.astype(jnp.float32) - old.astype(jnp.float32)
        if jnp.issubdtype(new.dtype, jnp.inexact)
        else jnp.zeros((), jnp.float32),
        new_params,
        params,
    )
    bundle = {
        "loss": loss32,
        "grad_norm": jnp.sqrt(_sq_norm(grads)),
        "param_norm": jnp.sqrt(_sq_norm(new_params)),
        "update_ratio": jnp.sqrt(_sq_norm(delta))
        / jnp.maximum(jnp.sqrt(old_sq), 1e-12),
        "nonfinite": _nonfinite_count(grads)
        + (~jnp.isfinite(loss32)).astype(jnp.float32),
    }
    if halves is not None:
        g1, g2 = halves
        bundle["half_sq"] = jnp.stack([_sq_norm(g1), _sq_norm(g2)])
        bundle["batch"] = jnp.asarray(0 if batch is None else batch, jnp.float32)
    return bundle


def publish_sown(values: Dict[str, Any]) -> None:
    """``edl_train_<name>`` gauges for the step metrics a model sows (an
    expert layer's ``aux_loss`` and ``moe_load_max``): called with host
    values the train loop already holds — the probe's throttled fetch, the
    epoch's end — never with a device array that would have to be waited
    for."""
    for name, value in values.items():
        obs_metrics.gauge(
            "edl_train_" + name, "sown by the model: %s, last fetched step" % name
        ).set(float(value))


def gns_estimates(big_sq: float, small_sq: float, batch: float) -> Tuple[float, float]:
    """One-step unbiased estimators from McCandlish et al. appendix A:
    given ``|G_big|^2`` at batch ``B`` and the mean half-batch
    ``|G_small|^2`` at ``B/2``, return ``(|G|^2 estimate, tr(Sigma)
    estimate)``; the noise scale is ``mean(s) / mean(g2)`` over a
    window of these pairs (each pair alone is far too noisy)."""
    # g2 = (B_big*big - B_small*small) / (B_big - B_small), B_small = B/2
    g2 = 2.0 * big_sq - small_sq
    # s = (small - big) / (1/B_small - 1/B_big) = B * (small - big)
    s = batch * (small_sq - big_sq)
    return g2, s


# -- fingerprints (the resize continuity sentinel) ------------------------


def host_param_norm(state) -> float:
    """Host recompute of the global param L2 norm (f64 accumulation) —
    the save-time and restore-time sides of the fingerprint run the
    exact same math, so equality is bitwise up to float64 summation."""
    tree = getattr(state, "params", state)
    total = 0.0
    for leaf in jax.tree_util.tree_leaves(tree):
        dt = getattr(leaf, "dtype", None)
        if dt is None or not jnp.issubdtype(dt, jnp.inexact):
            continue
        arr = np.abs(np.asarray(jax.device_get(leaf))).astype(np.float64)
        total += float(np.sum(np.square(arr)))
    return math.sqrt(total)


def latest_loss() -> Optional[float]:
    """The newest loss any probe in this process has buffered (one
    device_get of a 0-d scalar; None when no probe has run or the
    value is non-finite — ``json`` cannot carry Infinity portably and
    a non-finite stamp could never gate continuity anyway)."""
    with _LATEST_LOCK:
        latest = _LATEST
    if latest is None:
        return None
    try:
        loss = float(jax.device_get(latest[1]["loss"]))
    except (RuntimeError, KeyError):
        # RuntimeError is jax's "Array has been deleted" — the buffered
        # bundle's loss was donated into a later step before this read.
        # That exact state (not arbitrary breakage) reads as no loss.
        return None
    return loss if math.isfinite(loss) else None


def fingerprint_for_save(state, step: int) -> Dict[str, Any]:
    return {
        "step": int(step),
        "param_norm": host_param_norm(state),
        "loss": latest_loss(),
    }


def stamp_fingerprint(status_doc: Dict, state, step: int) -> Dict:
    """Return a copy of the checkpoint status document carrying the
    numerics fingerprint under ``meta.numerics`` (no-op when the plane
    is disabled)."""
    if not enabled():
        return status_doc
    doc = dict(status_doc)
    meta = dict(doc.get("meta") or {})
    meta["numerics"] = fingerprint_for_save(state, step)
    doc["meta"] = meta
    return doc


def verify_fingerprint(state, fingerprint, tol: Optional[float] = None) -> Tuple[bool, str]:
    """Re-derive the restored state's param norm and compare against the
    stamped one. A mismatch means the bytes Orbax handed back are not
    the bytes the trainer saved — the caller treats the candidate like
    any other corrupt checkpoint (fallback + quarantine)."""
    if not fingerprint or not enabled():
        return True, "no fingerprint"
    want = fingerprint.get("param_norm") if isinstance(fingerprint, dict) else None
    if want is None:
        return True, "fingerprint has no param_norm"
    if tol is None:
        tol = float(os.environ.get("EDL_NUMERICS_FP_TOL", DEFAULT_FP_TOL))
    have = host_param_norm(state)
    if not math.isfinite(have):
        return False, "restored param norm is non-finite (%r)" % have
    rel = abs(have - float(want)) / max(abs(float(want)), 1e-12)
    if rel > tol:
        return False, (
            "param norm %.9g vs stamped %.9g at step %s (rel %.3g > %.3g)"
            % (have, float(want), fingerprint.get("step"), rel, tol)
        )
    return True, "param norm match (rel %.3g)" % rel


# -- host side ------------------------------------------------------------


class NumericsProbe:
    """Host half of the plane: throttled device->host transfer, metric
    export, GNS/digest/spike derivation, and the resume-continuity
    check. One instance per training process; not thread-safe beyond
    the module-level latest-bundle buffer (the train loop is the only
    caller)."""

    def __init__(
        self,
        every: Optional[int] = None,
        rank: int = 0,
        client=None,
        job_id: str = "",
    ) -> None:
        if every is None:
            every = int(os.environ.get("EDL_NUMERICS_EVERY", DEFAULT_EVERY))
        self.every = max(1, int(every))
        self.rank = int(rank)
        self._client = client
        self._job = job_id
        self._loss_tol = float(os.environ.get("EDL_NUMERICS_LOSS_TOL", DEFAULT_LOSS_TOL))
        self._calls = 0
        self._held: Optional[Tuple[int, Dict[str, Any]]] = None
        self._last_pub_step: Optional[int] = None
        self._gns_win: collections.deque = collections.deque(maxlen=_GNS_WINDOW)
        self._loss_hist: collections.deque = collections.deque(maxlen=_SPIKE_HISTORY)
        self._expected: Optional[Dict] = None
        self._gauges: Dict[str, obs_metrics.Gauge] = {}
        self._nonfinite: Optional[obs_metrics.Counter] = None
        self._closed = False
        self.published = 0  # publishes performed (tests assert throttling)

    # -- step ingestion ---------------------------------------------------

    def on_step(
        self,
        step: int,
        bundle: Optional[Dict[str, Any]],
        epoch: Optional[int] = None,
    ) -> Optional[Tuple[int, float, Dict[str, Any]]]:
        """Buffer this step's device bundle; publish on the throttle
        cadence. Publishing fetches the *previous* buffered bundle —
        already retired by a full step of device work — except on the
        very first call, which publishes synchronously so the plane is
        armed with real data the moment training produces any (a
        registered-but-never-set gauge would render 0.0 and trip the
        grad-stall rule during a long first-step compile).

        A call that fetched returns ``(step, monotonic time, sown)``: the
        step whose bundle came back, the moment it did — the step loop's
        one proof that a numbered step has retired on the device — and what
        the model sowed in that step, as the same fetch brought it.
        ``epoch`` only labels the ``numerics_fetch`` span."""
        if self._closed or bundle is None:
            return None
        self._calls += 1
        prev = self._held
        self._held = (int(step), bundle)
        global _LATEST
        with _LATEST_LOCK:
            _LATEST = self._held
        if self._calls == 1:
            return self._publish(int(step), bundle, epoch)
        if self._calls % self.every == 0 and prev is not None:
            return self._publish(prev[0], prev[1], epoch)
        return None

    def close(self) -> None:
        """Flush the held bundle (the final step's numbers must not be
        lost to the throttle) and stop accepting steps."""
        if self._closed:
            return
        self._closed = True
        if self._held is not None:
            self._publish(self._held[0], self._held[1])

    def expect(self, fingerprint: Optional[Dict]) -> None:
        """Arm the post-resume continuity check: at the next publish the
        observed loss is compared against the checkpoint's stamped loss
        and the verdict is flight-recorded as ``numerics_resume`` (the
        ``numerics_continuous`` chaos invariant reads these). A None /
        loss-less fingerprint arms nothing."""
        if isinstance(fingerprint, dict):
            self._expected = fingerprint

    # -- publication ------------------------------------------------------

    def _gauge(self, name: str, help_text: str) -> obs_metrics.Gauge:
        g = self._gauges.get(name)
        if g is None:
            g = obs_metrics.gauge(name, help_text)
            self._gauges[name] = g
        return g

    def _publish(
        self, step: int, bundle: Dict[str, Any], epoch: Optional[int] = None
    ) -> Optional[Tuple[int, float, Dict[str, Any]]]:
        if step == self._last_pub_step:
            return None
        self._last_pub_step = step
        label = {} if epoch is None else {"epoch": epoch}
        try:
            # the step loop's one wait for the device
            with obs_trace.span("numerics_fetch", step=step, **label):
                vals = jax.device_get(bundle)
        except Exception as exc:  # noqa: BLE001 — a deleted buffer must not kill the loop
            logger.warning("numerics fetch failed at step %d: %s", step, exc)
            return None
        fetched = (step, time.monotonic(), dict(vals.get("sown", {})))
        self.published += 1
        loss = float(vals["loss"])
        grad_norm = float(vals["grad_norm"])
        param_norm = float(vals["param_norm"])
        update_ratio = float(vals["update_ratio"])
        nonfinite = int(vals["nonfinite"])

        self._gauge("edl_train_loss", "training loss, last published step").set(loss)
        self._gauge(
            "edl_train_grad_norm", "global gradient L2 norm, last published step"
        ).set(grad_norm)
        self._gauge(
            "edl_train_param_norm",
            "global parameter L2 norm (the cross-replica digest)",
        ).set(param_norm)
        self._gauge(
            "edl_train_update_ratio",
            "|param update| / |params|, last published step",
        ).set(update_ratio)
        if self._nonfinite is None:
            # the counter registers with the gauges (renders 0 from the
            # first publish) so the nan-detected rate rule sees the
            # 0 -> N jump instead of a series born already at N
            self._nonfinite = obs_metrics.counter(
                "edl_train_nonfinite_total",
                "non-finite elements seen in gradients/loss",
            )
        if nonfinite > 0:
            self._nonfinite.inc(nonfinite)
            obs_events.record(
                "nonfinite", fsync=True, step=step, count=nonfinite, loss=loss
            )

        publish_sown(vals.get("sown", {}))
        gns = self._update_gns(vals)
        divergence = self._update_divergence(step, param_norm)
        self._check_spike(step, loss)
        self._resolve_expected(step, loss)
        obs_events.record(
            "numerics",
            step=step,
            loss=loss,
            grad_norm=grad_norm,
            param_norm=param_norm,
            update_ratio=update_ratio,
            nonfinite=nonfinite,
            gns=gns,
            divergence=divergence,
        )
        return fetched

    def _update_gns(self, vals) -> Optional[float]:
        half_sq = vals.get("half_sq")
        if half_sq is None:
            return None
        batch = float(vals.get("batch", 0.0))
        big_sq = float(vals["grad_norm"]) ** 2
        small_sq = float(np.mean(np.asarray(half_sq, dtype=np.float64)))
        if batch < 2 or not (math.isfinite(big_sq) and math.isfinite(small_sq)):
            return None
        self._gns_win.append(gns_estimates(big_sq, small_sq, batch))
        mean_g2 = sum(p[0] for p in self._gns_win) / len(self._gns_win)
        mean_s = sum(p[1] for p in self._gns_win) / len(self._gns_win)
        if mean_g2 <= 1e-12:
            return None  # all signal is noise: no stable estimate yet
        gns = mean_s / mean_g2
        self._gauge(
            "edl_train_grad_noise_scale",
            "windowed gradient-noise-scale estimate (McCandlish et al.)",
        ).set(gns)
        return gns

    def _update_divergence(self, step: int, param_norm: float) -> Optional[float]:
        """Publish this replica's digest and read the spread across dp
        replicas *at the same step* (digests from different steps are
        incomparable: params move every step). Best-effort: a dead
        store reads as no divergence signal, never as a stall."""
        if self._client is None or not self._job:
            return None
        prefix = "/%s/%s/digest/" % (self._job, _DIGEST_SERVICE)
        try:
            self._client.put(
                prefix + str(self.rank),
                json.dumps({"step": step, "digest": param_norm}).encode(),
            )
            rows, _rev = self._client.range(prefix)
        except Exception as exc:  # noqa: BLE001
            logger.warning("digest exchange failed: %s", exc)
            return None
        digests = []
        for _key, value, _c, _m in rows:
            try:
                doc = json.loads(value)
            except ValueError:
                continue
            if doc.get("step") == step:
                digests.append(float(doc.get("digest", 0.0)))
        if len(digests) < 2:
            return None  # peers not at this step yet: nothing comparable
        spread = (max(digests) - min(digests)) / max(abs(max(digests)), 1e-12)
        self._gauge(
            "edl_train_replica_divergence",
            "relative spread of the param digest across dp replicas",
        ).set(spread)
        return spread

    def _check_spike(self, step: int, loss: float) -> None:
        """Host-side twin of the ``loss-spike`` monitor rule, so the
        flight recorder carries the instant even when no monitor is
        scraping this process (edl-timeline overlays these)."""
        hist = [v for v in self._loss_hist if math.isfinite(v)]
        if math.isfinite(loss):
            self._loss_hist.append(loss)
        if len(hist) < _SPIKE_MIN_HISTORY:
            return
        mean = sum(hist) / len(hist)
        var = sum((v - mean) ** 2 for v in hist) / len(hist)
        std = max(math.sqrt(var), 0.05 * abs(mean), 1e-12)
        z = (loss - mean) / std if math.isfinite(loss) else float("inf")
        if z > _SPIKE_Z:
            obs_events.record(
                "loss_spike", fsync=True, step=step, loss=loss,
                z=(z if math.isfinite(z) else 1e30), mean=mean,
            )

    def _resolve_expected(self, step: int, loss: float) -> None:
        if self._expected is None:
            return
        fp = self._expected
        self._expected = None
        want = fp.get("loss")
        if want is None:
            ok = math.isfinite(loss)
            rel = None
            detail = "no stamped loss; observed %s" % ("finite" if ok else "non-finite")
        elif not math.isfinite(loss):
            ok, rel = False, None
            detail = "post-resume loss is non-finite"
        else:
            rel = (loss - float(want)) / max(abs(float(want)), 1e-9)
            ok = rel <= self._loss_tol
            detail = "rel %.3g vs tol %.3g" % (rel, self._loss_tol)
        obs_events.record(
            "numerics_resume",
            fsync=True,
            step=step,
            ok=ok,
            expected_loss=want,
            actual_loss=loss if math.isfinite(loss) else None,
            rel=rel,
            ref_step=fp.get("step"),
            detail=detail,
        )
        if not ok:
            logger.warning(
                "resume continuity FAILED at step %d: %s (ckpt step %s)",
                step, detail, fp.get("step"),
            )
