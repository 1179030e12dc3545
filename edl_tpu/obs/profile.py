"""Profiling plane: live MFU/roofline/HBM telemetry + on-demand capture.

PR 6 closed the loop from measurement to *alert* ("the monitor fired");
this module closes the remaining gap to *explanation* ("here is the
on-device profile of the window that fired"). Three pieces, all worker-
side unless noted:

**Cost model** (pure functions, no jax import). The peak-FLOPs and
HBM-bandwidth tables and the :func:`roofline` estimator behind the live
gauges below. (The benchmark keeps its own peaks, keyed by the exact
``device_kind``: ``benchmark/peaks.json``.)

**Live telemetry** (:class:`StepTelemetry`). At stage start the training
loop extracts XLA's own FLOPs / bytes-accessed estimate for one step
(:func:`step_cost` — ``Lowered.cost_analysis()``, a trace without an XLA
compile) and feeds it here; every completed step then updates a sliding
window, exported as

- ``edl_train_step_flops`` / ``edl_train_flops_total`` — the cost model's
  FLOPs for one step, and their cumulative dispatch counter (the
  ``mfu-degraded`` rate rule watches the counter);
- ``edl_train_mfu_ratio`` — windowed model-FLOPs utilization:
  FLOPs/step over the window's *median* step time, against the chip's
  peak (sampled at scrape time, like ``edl_goodput_ratio``; the median
  keeps a checkpoint pause or the compile-heavy first step out of the
  denominator);
- ``edl_train_roofline_mfu_ceiling`` / ``edl_train_arithmetic_intensity``
  — what this program shape *admits* on this chip, so a scraped MFU
  reads as "x of achievable", not "x of a number the memory system
  forbids";
- ``edl_device_hbm_bytes_in_use`` / ``edl_device_hbm_bytes_limit`` —
  from ``device.memory_stats()``, which is absent/None on CPU backends
  and older runtimes: the gauges then simply don't export (guarded, no
  crash).

Unknown device kinds take ``EDL_PEAK_FLOPS`` (override for new chips);
pure-CPU backends fall back to a nominal debug peak so the plumbing is
drivable off-TPU — a CPU "MFU" is a plumbing signal, not a measurement.

**On-demand capture** (:class:`CaptureController`). Workers watch the
job's ``profile/request`` store key; a request (``edl-profile
--request``, or the monitor's auto-capture) makes every worker run one
bounded ``jax.profiler`` trace window — the same window plumbing
``EDL_PROFILE_DIR`` always armed, now store-driven — then publish
``profile/result/{pod}`` with the artifact path and a capture-window
summary (step ms, MFU, HBM). Captures are flight-recorded (fsync'd) so
``edl-timeline`` overlays the profile window on the goodput lanes.

**Alert-triggered snapshots** (:class:`AutoCapture`). The monitor's
``on_fire`` hook: a ``goodput-degraded`` or ``mfu-degraded`` firing
auto-requests one capture, bounded by a per-job cooldown and a
max-captures cap — a flapping rule must not fill a disk with traces.
"""

from __future__ import annotations

import json
import os
import re
import tempfile
import threading
import time
from collections import deque
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from edl_tpu.obs import events as obs_events
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.utils.log import get_logger

logger = get_logger("obs.profile")

PROFILE_SERVICE = "profile"
REQUEST_NAME = "request"
RESULT_PREFIX = "result/"

# -- the cost model -----------------------------------------------------------

# peak dense bf16 FLOP/s per chip, by jax device_kind substring
PEAK_BF16_FLOPS = [
    ("v6", 918e12),   # Trillium
    ("v5p", 459e12),
    ("v5", 197e12),   # v5e / v5 lite
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]

# HBM bandwidth per chip (bytes/s), same substring keys — for the
# roofline ceiling reported alongside MFU
HBM_BW = [
    ("v6", 1640e9),
    ("v5p", 2765e9),
    ("v5", 819e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
]

# pure-CPU debug fallback: no published "peak" exists, but the live MFU
# plumbing must be drivable on the CPU rigs every tier-1 drill runs on —
# the exported ratio is then a plumbing signal, not a measurement
CPU_NOMINAL_PEAK_FLOPS = 1e11
CPU_NOMINAL_HBM_BW = 50e9


def peak_flops(device_kind: str) -> Optional[float]:
    """Peak dense bf16 FLOP/s for a jax ``device_kind`` (None if unknown;
    ``EDL_PEAK_FLOPS`` overrides for chips the table predates)."""
    override = os.environ.get("EDL_PEAK_FLOPS")
    if override:
        try:
            return float(override)
        except ValueError:
            logger.warning("EDL_PEAK_FLOPS=%r is not a number; ignored", override)
    kind = device_kind.lower()
    for tag, peak in PEAK_BF16_FLOPS:
        if tag in kind:
            return peak
    if "cpu" in kind:
        return CPU_NOMINAL_PEAK_FLOPS
    return None


def hbm_bandwidth(device_kind: str) -> Optional[float]:
    """HBM bandwidth (bytes/s) for a jax ``device_kind`` (None if
    unknown; ``EDL_HBM_BW`` overrides)."""
    override = os.environ.get("EDL_HBM_BW")
    if override:
        try:
            return float(override)
        except ValueError:
            logger.warning("EDL_HBM_BW=%r is not a number; ignored", override)
    kind = device_kind.lower()
    for tag, bw in HBM_BW:
        if tag in kind:
            return bw
    if "cpu" in kind:
        return CPU_NOMINAL_HBM_BW
    return None


def normalize_cost(cost) -> Dict:
    """XLA cost analysis as one flat dict (some backends return a
    one-element list); {} when unavailable."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost) if isinstance(cost, dict) else {}


def cost_flops(cost: Dict) -> Optional[float]:
    try:
        return float(cost.get("flops", 0.0)) or None
    except (TypeError, ValueError):
        return None


def cost_bytes(cost: Dict) -> Optional[float]:
    try:
        return (
            float(cost.get("bytes accessed", cost.get("bytes_accessed", 0.0)))
            or None
        )
    except (TypeError, ValueError):
        return None


def step_cost(step_fn, *args, **kwargs) -> Dict:
    """XLA's cost analysis for one call of a jitted ``step_fn`` at the
    given arguments — via ``Lowered.cost_analysis()``, i.e. a jax trace
    but NO XLA compile (the compile already happened, or will, through
    the jit cache). Accepts an already-``Lowered`` object directly (the
    train loop lowers once for this and the memory plan). Returns {} on
    any failure: the cost model is telemetry, never a correctness
    dependency."""
    try:
        lowered = (
            step_fn if hasattr(step_fn, "cost_analysis")
            else step_fn.lower(*args, **kwargs)
        )
        return normalize_cost(lowered.cost_analysis())
    except Exception as exc:  # noqa: BLE001 — backend/API drift degrades to no cost
        logger.debug("step cost extraction failed: %s", exc)
        return {}


# -- the step program's phases ------------------------------------------------

PHASES = ("forward", "backward", "optimizer", "numerics", "other")

# `%fusion.12 = f32[8]{0} fusion(...), calls=%fused_computation.12,
#  metadata={op_name="jit(step)/optimizer/add" ...}`
_HLO_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s")
_HLO_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_HLO_MATMUL = re.compile(r"\s(?:convolution|dot)\(")

# the running stage's compiled step and, once asked for, its tables
_step_executable = None
_step_phases: Optional[Dict[str, str]] = None
_step_scopes: Dict[Tuple[str, ...], Dict[str, str]] = {}


def phase_of(op_name: str) -> str:
    """The phase a jax ``op_name`` belongs to, by the scopes
    ``train/step.py`` enters: jax writes the forward pass as
    ``jvp(forward)`` and what it derives from it (the backward pass,
    recomputation included) as ``transpose(jvp(forward))``; the
    half-batch averaging that finishes the gradient counts as backward."""
    parts = op_name.split(";", 1)[0].split("/")
    if "numerics" in parts:
        return "numerics"
    if "optimizer" in parts:
        return "optimizer"
    if "transpose(jvp(forward))" in parts or "grad_mean" in parts:
        return "backward"
    if "jvp(forward)" in parts or "forward" in parts:
        return "forward"
    return "other"


def op_names_of_hlo(text: str) -> Dict[str, str]:
    """``{instruction name: jax op_name}`` from an optimised HLO module's
    text: every instruction by its own ``op_name``, and one that has none
    (a fusion, a call) by that of the root of the computation it calls."""
    own: Dict[str, str] = {}      # instruction -> op_name
    calls: Dict[str, str] = {}    # instruction without one -> computation
    roots: Dict[str, str] = {}    # computation -> its root instruction
    computation = None
    for line in text.splitlines():
        started = _HLO_COMPUTATION.match(line)
        if started:
            computation = started.group(1)
            continue
        found = _HLO_INSTRUCTION.match(line)
        if not found:
            continue
        name = found.group(2)
        if found.group(1) and computation is not None:
            roots[computation] = name
        op_name = _HLO_OP_NAME.search(line)
        if op_name:
            own[name] = op_name.group(1)
        else:
            called = _HLO_CALLS.search(line)
            if called:
                calls[name] = called.group(1)
    for name, computation in calls.items():
        root = roots.get(computation)
        if root in own:
            own[name] = own[root]
    return own


def update_passes_of_hlo(text: str) -> List[str]:
    """The fusions that are the optimizer's pass over a leaf: those whose
    body holds instructions of the ``optimizer`` scope and no matmul. Such a
    pass reads ``g, p, m, v`` and writes ``p, m, v``; the half-batch mean and
    the numerics bundle's norms ride in it, and XLA names the fusion after
    one of the norms' reduces. A fusion that still holds the matmul that
    produces its gradient is not one: its time is the matmul's."""
    updates, matmuls = set(), set()   # computations holding either
    calls: Dict[str, str] = {}        # instruction -> computation
    computation = None
    for line in text.splitlines():
        started = _HLO_COMPUTATION.match(line)
        if started:
            computation = started.group(1)
            continue
        found = _HLO_INSTRUCTION.match(line)
        if not found:
            continue
        called = _HLO_CALLS.search(line)
        if called:
            calls[found.group(2)] = called.group(1)
        if _HLO_MATMUL.search(line):
            matmuls.add(computation)
        op_name = _HLO_OP_NAME.search(line)
        if op_name and phase_of(op_name.group(1)) == "optimizer":
            updates.add(computation)
    return [
        name for name, computation in calls.items()
        if computation in updates and computation not in matmuls
    ]


def phases_of_hlo(text: str) -> Dict[str, str]:
    """``{instruction name: phase}`` from an optimised HLO module's text
    (:func:`op_names_of_hlo`, then :func:`phase_of`; the optimizer's passes,
    :func:`update_passes_of_hlo`, under ``optimizer`` whatever name they
    took). {} when nothing maps to ``forward``: the executable then predates
    the scopes (a compile cache older than them handed it back), and a table
    of ``other`` would pass for a measurement."""
    table = {
        name: phase_of(op_name)
        for name, op_name in op_names_of_hlo(text).items()
    }
    table.update((name, "optimizer") for name in update_passes_of_hlo(text))
    return table if "forward" in table.values() else {}


# the expert layer's device-side names (models/moe.py:DroplessMoE)
MOE_SCOPES = ("moe_route", "moe_experts", "moe_combine")


def scopes_of_hlo(text: str, scopes: Sequence[str]) -> Dict[str, str]:
    """``{instruction name: scope}`` for the instructions whose op_name
    has one of ``scopes`` (``jax.named_scope`` names) as a component, in
    the forward pass, its recomputation or its transpose alike; the
    innermost of them where scopes nest. Instructions under none are left
    out."""
    wanted = set(scopes)
    table: Dict[str, str] = {}
    for name, op_name in op_names_of_hlo(text).items():
        for part in reversed(op_name.split(";", 1)[0].split("/")):
            if part in wanted:
                table[name] = part
                break
    return table


def set_step_executable(compiled) -> None:
    """Keep the running stage's compiled step (the one ``Compiled`` the
    train loop makes for the memory plan) for :func:`step_phases`."""
    global _step_executable, _step_phases
    _step_executable, _step_phases = compiled, None
    _step_scopes.clear()


def step_phases() -> Dict[str, str]:
    """``{HLO instruction name: "forward" | "backward" | "optimizer" |
    "numerics" | "other"}`` for the step executable of the running
    stage. A device trace names an event by its HLO instruction
    (``fusion.124``), not by its scope: this is the join. Parsed from
    the compiled step's text on first call, never inside ``fit``; {}
    without a step, or where its names are missing (see
    :func:`phases_of_hlo`)."""
    global _step_phases
    if _step_phases is None and _step_executable is not None:
        try:
            _step_phases = phases_of_hlo(_step_executable.as_text())
        except Exception as exc:  # noqa: BLE001 — telemetry: a backend without text reads as no table
            logger.warning("step phases unavailable: %s", exc)
            _step_phases = {}
    return dict(_step_phases or {})


def step_scopes(scopes: Sequence[str] = MOE_SCOPES) -> Dict[str, str]:
    """:func:`scopes_of_hlo` for the step executable of the running stage:
    which of a device trace's events ran under which of ``scopes`` — by
    default the expert layer's ``moe_route`` / ``moe_experts`` /
    ``moe_combine``; the models also name ``moe_shared``, the Mamba-2
    mixer's ``ssm_proj`` / ``ssm_conv`` / ``ssm_scan`` / ``ssm_gate``, the
    gated-delta-rule mixer's ``gdn_proj`` / ``gdn_conv`` / ``gdn_scan`` /
    ``gdn_gate``, the gated short convolution's ``sconv_proj`` /
    ``sconv_conv``, a sparse-attention layer's ``dsa_index`` / ``dsa_select`` /
    ``attn_sparse`` / ``dsa_target``, the Kimi-delta-attention mixer's
    ``kda_proj`` / ``kda_conv`` / ``kda_scan`` / ``kda_gate``, a latent-attention
    layer's ``mla_proj`` / ``attn_mla`` and, in a model of windowed and full
    attention layers, ``attn_window`` / ``attn_full`` / ``attn_gate``. A caller asks for the
    scopes of one mixer or layer at a time; an instruction under two of the
    asked scopes counts under the innermost. {} without a step or for a
    model that enters none of ``scopes``."""
    key = tuple(scopes)
    if key not in _step_scopes and _step_executable is not None:
        try:
            _step_scopes[key] = scopes_of_hlo(_step_executable.as_text(), key)
        except Exception as exc:  # noqa: BLE001 — telemetry: a backend without text reads as no table
            logger.warning("step scopes unavailable: %s", exc)
            _step_scopes[key] = {}
    return dict(_step_scopes.get(key, {}))


def roofline(cost, device_kind: str, peak: float, mfu: Optional[float] = None) -> Dict:
    """XLA-cost-model roofline for one compiled step: arithmetic
    intensity (FLOPs / HBM bytes) against the chip's compute/bandwidth
    ratio gives the MFU CEILING this program shape admits — so a
    measured MFU reads as "x of the achievable", not "x of a number the
    memory system may forbid". Uses XLA's own flops and bytes-accessed
    estimates; returns {} when either is unavailable. Pass the measured
    ``mfu`` to also get ``mfu_of_ceiling``."""
    cost = normalize_cost(cost)
    flops = cost_flops(cost)
    bytes_accessed = cost_bytes(cost)
    bw = hbm_bandwidth(device_kind)
    if not (flops and bytes_accessed and bw and peak):
        return {}
    ai = flops / bytes_accessed  # FLOPs per HBM byte
    ridge = peak / bw            # FLOPs per byte needed to be compute-bound
    ceiling = min(1.0, ai / ridge)
    out = {
        "step_hbm_gb": round(bytes_accessed / 1e9, 2),
        "arithmetic_intensity": round(ai, 1),
        "roofline_mfu_ceiling": round(ceiling, 3),
        "bound": "compute" if ai >= ridge else "memory",
    }
    if mfu is not None and ceiling:
        out["mfu_of_ceiling"] = round(mfu / ceiling, 3)
    return out


def device_memory_stats_full(device) -> Optional[Dict[str, float]]:
    """The richer ``device.memory_stats()`` dict the memory plane reads:
    always ``bytes_in_use``/``bytes_limit``, plus ``peak_bytes_in_use``
    and ``bytes_reserved`` when the backend provides them (TPU runtimes
    do; the peak is the allocator's own process-lifetime high-water mark
    — the memory plane layers its per-stage resettable watermark on
    top). None when the backend has no memory stats at all (CPU
    backends, older runtimes return None or omit the method) or reports
    no recognizable key. Never raises."""
    try:
        stats = device.memory_stats()
    except Exception:  # noqa: BLE001 — older runtimes raise instead of None
        return None
    if not isinstance(stats, dict):
        return None
    in_use = stats.get("bytes_in_use")
    limit = stats.get("bytes_limit", stats.get("bytes_reservable_limit"))
    if in_use is None and limit is None:
        return None
    out = {
        "bytes_in_use": float(in_use or 0.0),
        "bytes_limit": float(limit or 0.0),
    }
    for key in ("peak_bytes_in_use", "bytes_reserved"):
        v = stats.get(key)
        if v is not None:
            out[key] = float(v)
    return out


def device_memory_stats(device) -> Optional[Tuple[float, float]]:
    """``(bytes_in_use, bytes_limit)`` — the 2-tuple shim over
    :func:`device_memory_stats_full` the pre-memory-plane callers (HBM
    gauges, snapshots) keep using. Never raises."""
    stats = device_memory_stats_full(device)
    if stats is None:
        return None
    return stats["bytes_in_use"], stats["bytes_limit"]


# -- live telemetry -----------------------------------------------------------


class StepTelemetry:
    """Windowed MFU / roofline / HBM gauges for one training stage.

    Created per stage by the training loop (and the chaos trainee's
    audited miniature); :meth:`set_cost` arms it with the step's cost
    analysis and the device, :meth:`observe_step` is called once per
    completed step. Scrape-time gauges are bound through
    :func:`~edl_tpu.obs.metrics.bind_gauges` so :meth:`close` releases
    them — a restaged stage must not leave the old stage's closures in
    the process-global registry.
    """

    def __init__(
        self,
        registry: Optional[obs_metrics.MetricsRegistry] = None,
        window_s: float = 60.0,
    ) -> None:
        self._reg = (
            registry if registry is not None else obs_metrics.default_registry()
        )
        self._window_s = window_s
        self._lock = threading.Lock()
        # (monotonic ts, dt) of completed steps; maxlen bounds memory at
        # high step rates against the time-based trim
        self._steps: deque = deque(maxlen=4096)
        self._last_ts: Optional[float] = None
        self._flops_per_step: Optional[float] = None
        self._peak: Optional[float] = None
        self._ceiling: Optional[float] = None
        self._device = None
        self._m_flops_total = self._reg.counter(
            "edl_train_flops_total",
            "cost-model FLOPs dispatched by completed train steps",
        )
        self._binding: Optional[obs_metrics.GaugeBinding] = None

    # -- arming ------------------------------------------------------------

    def set_cost(self, cost, device=None) -> Dict:
        """Arm the telemetry with one step's XLA cost analysis and the
        device it runs on; returns the roofline dict (possibly {}).
        Safe to call with a {} cost: only the HBM gauges (if the device
        has memory stats) are exported then."""
        cost = normalize_cost(cost)
        if device is None:
            try:
                import jax

                device = jax.devices()[0]
            except Exception:  # noqa: BLE001 — no backend: gauges stay unexported
                device = None
        kind = getattr(device, "device_kind", "") or ""
        flops = cost_flops(cost)
        peak = peak_flops(kind) if kind else None
        roof = roofline(cost, kind, peak) if peak else {}
        specs = []
        with self._lock:
            self._device = device
            self._flops_per_step = flops
            self._peak = peak
            self._ceiling = roof.get("roofline_mfu_ceiling")
        if flops:
            specs.append((
                "edl_train_step_flops",
                "cost-model FLOPs for one train step (fwd+bwd+update)",
                lambda: self._flops_per_step or 0.0,
            ))
        if flops and peak:
            specs.append((
                "edl_train_mfu_ratio",
                "windowed model-FLOPs utilization: FLOPs/step over the "
                "window's median step time, against peak (CPU backends "
                "report vs a nominal debug peak)",
                self.window_mfu,
            ))
        if roof:
            specs.append((
                "edl_train_roofline_mfu_ceiling",
                "MFU ceiling the step's arithmetic intensity admits on "
                "this chip's roofline",
                lambda: self._ceiling or 0.0,
            ))
            ai = roof.get("arithmetic_intensity", 0.0)
            specs.append((
                "edl_train_arithmetic_intensity",
                "cost-model FLOPs per HBM byte for one train step",
                lambda ai=ai: ai,
            ))
        if device is not None and device_memory_stats(device) is not None:
            # guarded: memory_stats is None/absent on CPU backends and
            # older runtimes — then these two gauges simply don't exist
            specs.append((
                "edl_device_hbm_bytes_in_use",
                "device HBM bytes currently allocated",
                lambda: (device_memory_stats(self._device) or (0.0, 0.0))[0],
            ))
            specs.append((
                "edl_device_hbm_bytes_limit",
                "device HBM capacity visible to the allocator",
                lambda: (device_memory_stats(self._device) or (0.0, 0.0))[1],
            ))
        if self._binding is not None:
            self._binding.release()
        self._binding = obs_metrics.bind_gauges(specs, self._reg) if specs else None
        return roof

    # -- per-step ----------------------------------------------------------

    def observe_step(
        self, dt: Optional[float] = None, ts: Optional[float] = None
    ) -> None:
        """Record one completed step: ``dt`` is its dispatch-to-dispatch
        wall time (derived from the previous call when omitted; both
        injectable for tests). Advances the FLOPs counter and the MFU
        window."""
        now = time.monotonic() if ts is None else ts
        with self._lock:
            if dt is None:
                dt = (now - self._last_ts) if self._last_ts is not None else 0.0
            self._last_ts = now
            if dt > 0:
                self._steps.append((now, float(dt)))
                horizon = now - self._window_s
                while self._steps and self._steps[0][0] < horizon:
                    self._steps.popleft()
            flops = self._flops_per_step
        if flops:
            self._m_flops_total.inc(flops)

    def window_mfu(self, now: Optional[float] = None) -> float:
        """Windowed MFU: FLOPs/step over the MEDIAN step time of the
        window, against peak. The median (not the span) makes one
        checkpoint pause, the compile-heavy first step, or a clock
        anomaly an outlier instead of the denominator; 0.0 until two
        steps have landed — and 0.0 again once the whole window has
        aged out (a wedged worker must read as degraded at scrape
        time, not keep exporting its last healthy ratio forever)."""
        if now is None:
            now = time.monotonic()
        with self._lock:
            flops, peak = self._flops_per_step, self._peak
            if not (flops and peak) or len(self._steps) < 2:
                return 0.0
            if now - self._steps[-1][0] > self._window_s:
                return 0.0  # nothing stepped for a full window: stale
            dts = sorted(dt for _ts, dt in self._steps)
        median = dts[len(dts) // 2]
        if median <= 0:
            return 0.0
        return flops / median / peak

    def hbm_in_use(self) -> Optional[float]:
        with self._lock:
            device = self._device
        stats = device_memory_stats(device) if device is not None else None
        return stats[0] if stats else None

    def snapshot(self) -> Dict:
        """Current telemetry as plain data (capture summaries, tests)."""
        with self._lock:
            doc = {
                "step_flops": self._flops_per_step,
                "peak_flops": self._peak,
                "roofline_mfu_ceiling": self._ceiling,
            }
        doc["mfu"] = round(self.window_mfu(), 4)
        hbm = self.hbm_in_use()
        if hbm is not None:
            doc["hbm_bytes_in_use"] = hbm
        return {k: v for k, v in doc.items() if v is not None}

    def close(self) -> None:
        if self._binding is not None:
            self._binding.release()
            self._binding = None


# -- on-demand capture --------------------------------------------------------


def profile_prefix(job_id: str) -> str:
    return "/%s/%s/" % (job_id, PROFILE_SERVICE)


def request_capture(
    client,
    job_id: str,
    steps: int = 5,
    reason: str = "manual",
    request_id: Optional[str] = None,
    out_dir: Optional[str] = None,
) -> str:
    """Publish a capture request every worker of the job will honor;
    returns the request id (monotonic-ish, unique per requester)."""
    rid = request_id or "%d.%d" % (int(time.time() * 1000), os.getpid())
    doc = {"id": rid, "steps": int(steps), "reason": reason, "ts": time.time()}
    if out_dir:
        doc["dir"] = out_dir
    client.put(profile_prefix(job_id) + REQUEST_NAME, json.dumps(doc).encode())
    return rid


def read_results(
    client, job_id: str, request_id: Optional[str] = None
) -> Dict[str, Dict]:
    """Published capture results ``{pod[.rank]: summary}``, optionally
    filtered to one request id."""
    out: Dict[str, Dict] = {}
    prefix = profile_prefix(job_id) + RESULT_PREFIX
    try:
        rows, _rev = client.range(prefix)
    except Exception as exc:  # noqa: BLE001 — a dead store reads as no results
        logger.warning("profile result read failed: %s", exc)
        return out
    for key, value, _c, _m in rows:
        try:
            doc = json.loads(value)
        except ValueError:
            continue
        if request_id is None or doc.get("id") == request_id:
            out[key[len(prefix):]] = doc
    return out


class CaptureController:
    """Worker-side state machine for store-driven profiler windows.

    The training loop calls :meth:`on_step` once per completed step; the
    controller starts a ``jax.profiler`` trace when a new
    ``profile/request`` appears (or when the legacy ``EDL_PROFILE_DIR``
    window armed via :meth:`arm_local` comes due), stops it after the
    requested number of steps, and publishes ``profile/result/{pod}``
    with the artifact path and the window summary. Everything is
    best-effort and exception-contained: profiling must never take down
    the step loop it observes.
    """

    def __init__(
        self,
        env,
        telemetry: Optional[StepTelemetry] = None,
        client=None,
        registry: Optional[obs_metrics.MetricsRegistry] = None,
    ) -> None:
        self._env = env
        self._telemetry = telemetry
        reg = registry if registry is not None else obs_metrics.default_registry()
        self._m_captures = reg.counter(
            "edl_profile_captures_total",
            "completed profiler capture windows, by trigger",
        )
        self._lock = threading.Lock()
        self._pending: Optional[Dict] = None
        self._done_ids: set = set()
        self._local: Optional[Dict] = None
        self._steps_until_local = 0
        self._tracing: Optional[Dict] = None
        self._calls = 0
        self._owns_client = False
        self._client = client
        self._watch = None
        if self._client is None and getattr(env, "store_endpoint", ""):
            try:
                from edl_tpu.store.client import connect_store

                self._client = connect_store(env.store_endpoint, timeout=2.0)
                self._owns_client = True
            except Exception as exc:  # noqa: BLE001
                logger.warning("capture controller has no store: %s", exc)
        if self._client is not None and getattr(env, "job_id", ""):
            # seed the done-set with the request this worker already
            # answered in a previous incarnation: a restaged worker must
            # not re-run a capture its published result proves done. The
            # seed is best-effort — a store blip here must not leave the
            # worker deaf to requests for the whole stage, so the watch
            # below is installed regardless.
            try:
                prior = self._client.get(
                    profile_prefix(env.job_id) + RESULT_PREFIX + self._result_name()
                )
                if prior:
                    self._done_ids.add(json.loads(prior).get("id"))
            except Exception as exc:  # noqa: BLE001 — unseeded is recoverable
                logger.warning("capture done-set seed unavailable: %s", exc)
            try:
                from edl_tpu.discovery.registry import Registry

                self._registry = Registry(self._client, env.job_id)
                self._watch = self._registry.watch_service(
                    PROFILE_SERVICE, on_change=self._on_change
                )
            except Exception as exc:  # noqa: BLE001
                logger.warning("capture request watch unavailable: %s", exc)
                self._watch = None

    # -- request intake ----------------------------------------------------

    def _result_name(self) -> str:
        pod = getattr(self._env, "pod_id", "") or "pod"
        rank = int(getattr(self._env, "rank_in_pod", 0) or 0)
        return pod if rank == 0 else "%s.%d" % (pod, rank)

    def _on_change(self, snapshot) -> None:
        meta = snapshot.get(REQUEST_NAME)
        if meta is None:
            return
        try:
            doc = json.loads(meta.value)
        except ValueError:
            return
        rid = doc.get("id")
        with self._lock:
            if not rid or rid in self._done_ids:
                return
            self._pending = doc

    def arm_local(self, out_dir: str, start_after: int = 10, steps: int = 5) -> None:
        """The legacy env-armed window (``EDL_PROFILE_DIR``): one capture
        of ``steps`` steps beginning after ``start_after`` completed
        steps, published like a store request (when a store is around)."""
        with self._lock:
            self._local = {
                "id": "local.%d" % os.getpid(), "steps": int(steps),
                "reason": "env", "dir": out_dir,
            }
            self._steps_until_local = int(start_after)

    @property
    def tracing(self) -> bool:
        with self._lock:
            return self._tracing is not None

    # -- the per-step hook -------------------------------------------------

    def on_step(self, sync: Optional[Callable[[], None]] = None) -> None:
        """Advance the state machine by one completed step. ``sync`` is
        called (e.g. ``block_until_ready`` on the step's metrics) before
        a window closes, so the trace contains the device work it
        claims to."""
        try:
            self._on_step(sync)
        except Exception as exc:  # noqa: BLE001 — never take down the step loop
            logger.warning("capture controller step failed: %s", exc)
            with self._lock:
                self._tracing = None

    def _on_step(self, sync) -> None:
        with self._lock:
            self._calls += 1
            tracing = self._tracing
            if tracing is None:
                request = None
                if self._pending is not None:
                    request, self._pending = self._pending, None
                    if request.get("id") in self._done_ids:
                        # a result publication (any pod's) re-fires the
                        # service watch, and _on_change may re-arm a
                        # request THIS worker was still tracing at the
                        # time — done is done, never capture it twice
                        request = None
                if request is None and (
                    self._local is not None
                    and self._calls > self._steps_until_local
                ):
                    request, self._local = self._local, None
                if request is None:
                    return
        if tracing is not None:
            tracing["steps_seen"] += 1
            if tracing["steps_seen"] >= tracing["want"]:
                self._finish(tracing, sync)
            return
        self._begin(request)

    def _begin(self, request: Dict) -> None:
        out_dir = request.get("dir") or os.environ.get(
            "EDL_PROFILE_OUT",
            os.path.join(tempfile.gettempdir(), "edl_profile"),
        )
        job = getattr(self._env, "job_id", "") or "job"
        rid = str(request.get("id", "r"))
        trace_dir = os.path.join(
            out_dir, job, rid.replace("/", "_"), self._result_name()
        )
        os.makedirs(trace_dir, exist_ok=True)
        import jax

        jax.profiler.start_trace(trace_dir)
        tracing = {
            "id": rid,
            "want": max(1, int(request.get("steps", 5))),
            "steps_seen": 0,
            "reason": str(request.get("reason", "manual")),
            "dir": trace_dir,
            "t0": time.time(),
            "t0_mono": time.monotonic(),
        }
        with self._lock:
            self._tracing = tracing
        obs_events.record(
            "profile", fsync=True, phase="start", id=rid, dir=trace_dir,
            reason=tracing["reason"],
        )
        logger.info(
            "profiler capture %s started (%d steps) -> %s",
            rid, tracing["want"], trace_dir,
        )

    def _finish(self, tracing: Dict, sync) -> None:
        if sync is not None:
            try:
                sync()
            except Exception:  # noqa: BLE001 — a failed sync still stops the trace
                pass
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception as exc:  # noqa: BLE001
            logger.warning("profiler stop_trace failed: %s", exc)
        t1, t1_mono = time.time(), time.monotonic()
        span = max(1e-9, t1_mono - tracing["t0_mono"])
        doc = {
            "id": tracing["id"],
            "pod": getattr(self._env, "pod_id", "") or "",
            "rank": int(getattr(self._env, "global_rank", 0) or 0),
            "reason": tracing["reason"],
            "dir": tracing["dir"],
            "steps": tracing["steps_seen"],
            "t0": tracing["t0"],
            "t1": t1,
            "step_ms": round(span / tracing["steps_seen"] * 1e3, 3),
        }
        if self._telemetry is not None:
            doc.update(
                {
                    k: v
                    for k, v in self._telemetry.snapshot().items()
                    if k in ("mfu", "hbm_bytes_in_use", "step_flops",
                             "roofline_mfu_ceiling")
                }
            )
        with self._lock:
            self._done_ids.add(tracing["id"])
            self._tracing = None
        self._m_captures.inc(trigger=tracing["reason"])
        obs_events.record(
            "profile", fsync=True, phase="done", id=tracing["id"],
            dir=tracing["dir"], steps=doc["steps"], t0=tracing["t0"],
            step_ms=doc["step_ms"], reason=tracing["reason"],
            mfu=doc.get("mfu"),
        )
        job = getattr(self._env, "job_id", "")
        if self._client is not None and job:
            key = profile_prefix(job) + RESULT_PREFIX + self._result_name()
            try:  # fire-and-forget, like every telemetry writer
                self._client.put(key, json.dumps(doc).encode())
            except Exception as exc:  # noqa: BLE001
                logger.warning("profile result not published: %s", exc)
        logger.info(
            "profiler capture %s done: %d steps, %.2f ms/step -> %s",
            tracing["id"], doc["steps"], doc["step_ms"], tracing["dir"],
        )

    def close(self) -> None:
        with self._lock:
            tracing, self._tracing = self._tracing, None
        if tracing is not None:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001
                pass
        if self._watch is not None:
            try:
                self._watch.cancel()
            except Exception:  # noqa: BLE001
                pass
        if self._owns_client and self._client is not None:
            self._client.close()
            self._client = None


# -- alert-triggered snapshots ------------------------------------------------


class AutoCapture:
    """Monitor-side ``on_fire`` action: one capture request per alert
    firing, bounded by a per-job cooldown and a lifetime cap.

    Pass an instance as ``Monitor(..., on_fire=AutoCapture(client, job))``
    (``tools/edl_monitord.py --auto-capture`` wires it). Only the rules
    in ``rules`` trigger; everything is fire-and-forget.
    """

    DEFAULT_RULES = ("goodput-degraded", "mfu-degraded")

    def __init__(
        self,
        client,
        job_id: str,
        rules: Iterable[str] = DEFAULT_RULES,
        cooldown_s: float = 300.0,
        max_captures: int = 5,
        steps: int = 5,
        registry: Optional[obs_metrics.MetricsRegistry] = None,
    ) -> None:
        self._client = client
        self._job_id = job_id
        self._rules = frozenset(rules)
        self._cooldown_s = cooldown_s
        self._max = max_captures
        self._steps = steps
        self._last_ts: Optional[float] = None
        self._count = 0
        self._lock = threading.Lock()
        reg = registry if registry is not None else obs_metrics.default_registry()
        self._m_requests = reg.counter(
            "edl_monitor_capture_requests_total",
            "profiler captures auto-requested on alert firings, by rule",
        )

    def __call__(self, rule, doc: Dict) -> None:
        name = getattr(rule, "name", str(rule))
        if name not in self._rules:
            return
        now = float(doc.get("ts") or time.time())
        with self._lock:
            if self._count >= self._max:
                logger.info(
                    "auto-capture cap reached (%d); %s firing not captured",
                    self._max, name,
                )
                return
            if self._last_ts is not None and now - self._last_ts < self._cooldown_s:
                return
            # the slot and cooldown commit only on a successful request:
            # alerts tend to fire exactly when the store is in trouble,
            # and N transient put failures must not spend the lifetime
            # cap without ever producing a capture
            try:
                rid = request_capture(
                    self._client, self._job_id, steps=self._steps, reason=name
                )
            except Exception as exc:  # noqa: BLE001 — never take down the monitor
                logger.warning("auto-capture request failed: %s", exc)
                return
            self._last_ts = now
            self._count += 1
        self._m_requests.inc(rule=name)
        logger.warning(
            "auto-capture %s requested on %s firing (%d/%d used)",
            rid, name, self._count, self._max,
        )
