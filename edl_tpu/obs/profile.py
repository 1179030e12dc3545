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

**The step program's own account** (:class:`HloProgram`). One parse of the
running stage's step executable puts every instruction under one part
(:data:`STEP_PARTS`) and one pass (:data:`PHASES`): :func:`step_phases`,
:func:`step_scopes` and :func:`step_parts` are the joins of a device
trace's events to the program's names, and once a stage
:func:`publish_step_census` counts the instructions that run (matmuls,
Pallas kernel calls by kernel, loops, collectives) into one
``step_program`` instant, ``edl_train_step_program_count{what}`` and a
flight record, on a thread of its own (``train/loop.py``).

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


# -- the step program's phases and parts --------------------------------------

PHASES = ("forward", "backward", "optimizer", "numerics", "other")

# The one table of a step's parts: ``(a component of a jax op_name, the part
# it reads under)``, and an instruction takes the innermost component that is
# listed. First the device scopes the models enter (``jax.named_scope``), each
# a part of its own: the expert layer's (models/moe.py), the Mamba-2 mixer's
# (models/mamba.py), the gated-delta-rule and Kimi-delta-attention mixers'
# (models/gated_delta.py), the gated short convolution's (models/short_conv.py),
# a sparse-attention layer's (ops/sparse_attention.py), a latent-attention
# layer's, a multi-token-prediction module's (``mtp`` is what its block does
# outside the block's own scopes), in a model of windowed and full attention
# layers each kind's, and a block-diffusion step's attention call
# (models/transformer.py). Then the flax module names, for what has no scope:
# ``attn`` is an attention layer's projections, rotation and QK norms, a mixer's
# own name what it does outside its scopes. :func:`part_of` adds the parts that
# are no component: ``attn_kernel`` (a Pallas call right under ``attn``),
# ``block`` (a layer's residual adds, under ``layer_<n>`` and no module),
# ``loss`` (forward or backward, under no module or scope at all), a tied
# ``head`` (a matmul under the model and no module) and ``other``.
_STEP_SCOPES = (
    "moe_route", "moe_experts", "moe_combine", "moe_shared", "moe_latent",
    "ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate",
    "gdn_proj", "gdn_conv", "gdn_scan", "gdn_gate",
    "kda_proj", "kda_conv", "kda_scan", "kda_gate",
    "sconv_proj", "sconv_conv",
    "dsa_index", "dsa_select", "dsa_target", "attn_sparse",
    "attn_window", "attn_full", "attn_gate", "attn_mla", "mla_proj",
    "attn_block_diffusion",
    "mtp", "mtp_join", "mtp_head",
    "embed", "attn", "mlp", "moe", "mamba", "gdn", "kda", "sconv",
)
STEP_PARTS = tuple((name, name) for name in _STEP_SCOPES) + (
    ("ln1", "norm"), ("ln2", "norm"), ("ln1_post", "norm"),
    ("ln2_post", "norm"), ("ln_f", "norm"), ("lm_head", "head"),
)
_PART_OF_COMPONENT = dict(STEP_PARTS)
#: a ``while`` / ``conditional``: its body's instructions are placed
#: themselves, so whoever sums by part leaves this one out and counts a loop once
CONTAINER = "container"

# `%fusion.12 = f32[8]{0} fusion(...), calls=%fused_computation.12,
#  metadata={op_name="jit(step)/optimizer/add" ...}`
_HLO_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s")
_HLO_COMPUTATION = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_HLO_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
# a `while`'s, a `conditional`'s, a `call`'s computations: they run as written
_HLO_CONTROL = re.compile(
    r"(?:condition|body|to_apply|true_computation|false_computation)=%?([\w.\-]+)"
)
_HLO_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_HLO_LAYER = re.compile(r"layer_\d+$")
_PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
_MATMULS = ("dot", "convolution")
# the parts that name no module or scope with a matmul of its own: one found
# there is in a layer the table does not list (``block``: under a ``layer_<n>``
# in a module of no listed name) or under no name at all (``loss``, ``other``)
_UNPLACED = ("other", "block", "loss")
_CONTAINERS = ("while", "conditional")
_COLLECTIVES = frozenset(
    kind + suffix
    for kind in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                 "collective-permute")
    for suffix in ("", "-start")
)


class _StepSlot:
    """The running stage's compiled step and, once asked for, its parse. Whoever
    asks first makes the parse, outside :data:`_step_lock`; whoever asks
    meanwhile waits for ``parsed``, not for the lock, so the next stage's
    :func:`set_step_executable` never waits behind a text of seconds."""

    __slots__ = ("compiled", "program", "claimed", "parsed")

    def __init__(self, compiled) -> None:
        self.compiled = compiled
        self.program: Optional["HloProgram"] = None
        self.claimed = False
        self.parsed = threading.Event()


_step: Optional[_StepSlot] = None
_step_lock = threading.Lock()  # held to swap or claim the slot, never to parse


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


def part_of(op_name: str, kernel: bool = False) -> str:
    """The part a jax ``op_name`` belongs to: the innermost of its components
    that :data:`STEP_PARTS` lists (a Pallas call, ``kernel``, right under
    ``attn`` reads ``attn_kernel``); failing one, ``block`` under a
    ``layer_<n>``, ``loss`` in the forward or the backward pass under no
    name, ``head`` for a matmul under the model alone (a tied head), else
    ``other``."""
    components = op_name.split(";", 1)[0].split("/")
    for component in reversed(components):
        part = _PART_OF_COMPONENT.get(component)
        if part is not None:
            return "attn_kernel" if kernel and part == "attn" else part
    if any(_HLO_LAYER.match(c) for c in components):
        return "block"
    if phase_of(op_name) not in ("forward", "backward"):
        return "other"
    # the loss is what the forward pass runs under no name at all: every
    # component before the primitive is a transform jax wrote (``jit(..)``,
    # ``jvp(..)``, ``transpose(..)``), none a module or a scope
    named = {c for c in components[:-1] if "(" not in c}
    if not named:
        return "loss"
    # a matmul right under the model, in no module of its own: a tied head,
    # the embedding's matrix once more (``TransformerLM(tie_embeddings=)``)
    tied = len(named) == 1 and components[-1] == "dot_general"
    return "head" if tied else "other"


class HloProgram:
    """What one pass over an optimised HLO module's text keeps of every
    instruction, and the tables read off it: the join of a device trace's
    events (named by HLO instruction) to the program's own names. The pass is
    a regex over every line, seconds for a step of a hundred thousand
    instructions: made once an executable, off the step loop's thread."""

    def __init__(self, text: str) -> None:
        self.own: Dict[str, str] = {}          # instruction -> its own op_name
        self.opcode: Dict[str, str] = {}       # instruction -> opcode
        self.home: Dict[str, str] = {}         # instruction -> its computation
        self.calls: Dict[str, str] = {}        # instruction -> `calls=` computation
        self.roots: Dict[str, str] = {}        # computation -> root instruction
        self.kernels: set = set()              # the Pallas custom calls
        self.matmuls: set = set()              # computations holding a dot / convolution
        self.updates: set = set()              # computations holding `optimizer` instructions
        self.run: set = set()                  # computations that run as written
        computation = None
        for line in text.splitlines():
            started = _HLO_COMPUTATION.match(line)
            if started:
                computation = started.group(2)
                if started.group(1):
                    self.run.add(computation)
                continue
            found = _HLO_INSTRUCTION.match(line)
            if not found:
                continue
            name = found.group(2)
            self.home[name] = computation
            if found.group(1) and computation is not None:
                self.roots[computation] = name
            # the opcode follows the shape; a Pallas call's line is its whole
            # kernel, so look no further than the head of the line
            # (the match ends on the blank after ``=``, which the opcode's
            # pattern starts from)
            at, until = found.end(), found.end() + 4096
            opcode = _HLO_OPCODE.search(line, at - 1, until)
            opcode = self.opcode[name] = opcode.group(1) if opcode else ""
            if opcode in _MATMULS:
                self.matmuls.add(computation)
            op_name = _HLO_OP_NAME.search(line)
            if op_name:
                self.own[name] = op_name.group(1)
                if phase_of(op_name.group(1)) == "optimizer":
                    self.updates.add(computation)
            if opcode == "custom-call":
                if line.find(_PALLAS_TARGET, at, until) >= 0:
                    self.kernels.add(name)
                continue
            called = _HLO_CALLS.search(line)
            if called:
                self.calls[name] = called.group(1)
                if opcode != "fusion":  # an async start, a call
                    self.run.add(called.group(1))
            if opcode in _CONTAINERS or opcode == "call":
                self.run.update(_HLO_CONTROL.findall(line))
                branches = _HLO_BRANCHES.search(line)
                if branches:
                    self.run.update(
                        b.strip().lstrip("%") for b in branches.group(1).split(",")
                    )
        self._tables: Dict[object, Dict] = {}
        #: what the parse cost, where :func:`step_program` made it
        self.cost: Dict[str, float] = {}

    def _once(self, key, make):
        if key not in self._tables:
            self._tables[key] = make()
        return self._tables[key]

    def op_names(self) -> Dict[str, str]:
        """``{instruction name: jax op_name}``: every instruction by its own
        ``op_name``, and one that has none (a fusion, a call) by that of the
        root of the computation it calls."""
        def make():
            names = dict(self.own)
            for name, computation in self.calls.items():
                root = self.roots.get(computation)
                if name not in self.own and root in self.own:
                    names[name] = self.own[root]
            return names
        return self._once("op_names", make)

    def update_passes(self) -> List[str]:
        """The fusions that are the optimizer's pass over a leaf: those whose
        body holds instructions of the ``optimizer`` scope and no matmul."""
        return [
            name for name, computation in self.calls.items()
            if computation in self.updates and computation not in self.matmuls
        ]

    def phases(self) -> Dict[str, str]:
        def make():
            table = {
                name: phase_of(op_name)
                for name, op_name in self.op_names().items()
            }
            table.update((name, "optimizer") for name in self.update_passes())
            return table if "forward" in table.values() else {}
        return self._once("phases", make)

    def scopes(self, scopes: Sequence[str]) -> Dict[str, str]:
        def make():
            wanted = set(scopes)
            table: Dict[str, str] = {}
            for name, op_name in self.op_names().items():
                for part in reversed(op_name.split(";", 1)[0].split("/")):
                    if part in wanted:
                        table[name] = part
                        break
            return table
        return self._once(tuple(scopes), make)

    def parts(self) -> Dict[str, Tuple[str, str]]:
        """``{instruction: (part, pass)}`` for EVERY instruction: one without
        a name of its own or of its root reads ``("other", "other")``, a
        ``while`` / ``conditional`` :data:`CONTAINER` under its own pass. {}
        where :meth:`phases` is (an executable older than the scopes)."""
        def make():
            phases = self.phases()
            if not phases:
                return {}
            names = self.op_names()
            table = {}
            for name, opcode in self.opcode.items():
                op_name = names.get(name)
                if opcode in _CONTAINERS:
                    part = CONTAINER
                elif op_name is None:
                    part = "other"
                else:
                    part = part_of(op_name, name in self.kernels)
                table[name] = (part, phases.get(name, "other"))
            return table
        return self._once("parts", make)

    def census(self) -> Dict:
        """The instructions that run as written (the entry computation's, a
        loop's body's, a conditional's branches': not those inside a fusion or
        a reducer), counted: ``{"totals": {what: count}, "parts": {"part/pass":
        {what: count}}, "kernels": {"kernel/pass": count}}``. ``what`` is
        ``instructions``; ``matmuls`` (a ``dot`` or ``convolution``, a fusion
        that holds one, a Pallas call: each once); ``kernel_calls`` (the
        Pallas calls, by the name a trace gives them: the instruction's less
        its number); ``loops`` and ``conditionals``; ``collectives``;
        ``fused_dw`` (matmul fusions that also hold ``optimizer``
        instructions); ``unplaced_matmuls`` (matmuls whose part is ``other``,
        ``block`` or ``loss``: in a module the table does not list, or under
        no name; one right under the model reads ``head``, a tied head's, and
        is not seen here).
        Static: both branches of a conditional count, a loop's body once. A
        loop or a conditional is counted under the part its own name gives
        (``kda_scan``'s carry, ``moe_experts``' group search), where
        :meth:`parts` calls it a container."""
        parts, names = self.parts(), self.op_names()
        totals = dict.fromkeys(
            ("instructions", "matmuls", "kernel_calls", "loops", "conditionals",
             "collectives", "fused_dw", "unplaced_matmuls"), 0,
        )
        by_part: Dict[str, Dict[str, int]] = {}
        kernels: Dict[str, int] = {}
        for name, opcode in self.opcode.items():
            if self.home[name] not in self.run or opcode == "parameter":
                continue
            part, phase = parts.get(name, ("other", "other"))
            if part == CONTAINER:  # counted, not timed: under the part it serves
                part = part_of(names.get(name, ""))
            fused = self.calls.get(name) if opcode == "fusion" else None
            counts = {
                "instructions": 1,
                "matmuls": opcode in _MATMULS or fused in self.matmuls
                or name in self.kernels,
                "kernel_calls": name in self.kernels,
                "loops": opcode == "while",
                "conditionals": opcode == "conditional",
                "collectives": opcode in _COLLECTIVES,
                "fused_dw": fused in self.matmuls and fused in self.updates,
            }
            counts["unplaced_matmuls"] = counts["matmuls"] and part in _UNPLACED
            row = by_part.setdefault("%s/%s" % (part, phase), {})
            for what, n in counts.items():
                if n:
                    totals[what] += 1
                    row[what] = row.get(what, 0) + 1
            if name in self.kernels:
                key = "%s/%s" % (re.sub(r"\.\d+$", "", name), phase)
                kernels[key] = kernels.get(key, 0) + 1
        return {"totals": totals, "parts": by_part, "kernels": kernels}


def op_names_of_hlo(text: str) -> Dict[str, str]:
    """``{instruction name: jax op_name}`` from an optimised HLO module's
    text (:meth:`HloProgram.op_names`)."""
    return HloProgram(text).op_names()


def update_passes_of_hlo(text: str) -> List[str]:
    """The fusions that are the optimizer's pass over a leaf: those whose
    body holds instructions of the ``optimizer`` scope and no matmul. Such a
    pass reads ``g, p, m, v`` and writes ``p, m, v``; the half-batch mean and
    the numerics bundle's norms ride in it, and XLA names the fusion after
    one of the norms' reduces. A fusion that still holds the matmul that
    produces its gradient is not one: its time is the matmul's."""
    return HloProgram(text).update_passes()


def phases_of_hlo(text: str) -> Dict[str, str]:
    """``{instruction name: phase}`` from an optimised HLO module's text
    (:func:`op_names_of_hlo`, then :func:`phase_of`; the optimizer's passes,
    :func:`update_passes_of_hlo`, under ``optimizer`` whatever name they
    took). {} when nothing maps to ``forward``: the executable then predates
    the scopes (a compile cache older than them handed it back), and a table
    of ``other`` would pass for a measurement."""
    return HloProgram(text).phases()


# the expert layer's device-side names (models/moe.py:DroplessMoE)
MOE_SCOPES = ("moe_route", "moe_experts", "moe_combine")


def scopes_of_hlo(text: str, scopes: Sequence[str]) -> Dict[str, str]:
    """``{instruction name: scope}`` for the instructions whose op_name
    has one of ``scopes`` (``jax.named_scope`` names) as a component, in
    the forward pass, its recomputation or its transpose alike; the
    innermost of them where scopes nest. Instructions under none are left
    out."""
    return HloProgram(text).scopes(scopes)


def parts_of_hlo(text: str) -> Dict[str, Tuple[str, str]]:
    """``{instruction name: (part, pass)}`` for every instruction of an
    optimised HLO module's text: ``pass`` is :func:`phases_of_hlo`'s phase (a
    fusion where its root is, an optimizer's pass under ``optimizer``;
    ``other`` without a name), ``part`` :func:`part_of` its op_name, so what
    ``jax.checkpoint`` runs again reads under the part it recomputes and the
    ``backward`` pass. {} where :func:`phases_of_hlo` is."""
    return HloProgram(text).parts()


def set_step_executable(compiled) -> None:
    """Keep the running stage's compiled step (the one ``Compiled`` the
    train loop makes for the memory plan) for :func:`step_phases`,
    :func:`step_scopes` and :func:`step_parts`."""
    global _step
    with _step_lock:
        _step = _StepSlot(compiled) if compiled is not None else None


def executable_text(compiled) -> str:
    """The optimised HLO text of a ``Compiled``, got so that another thread
    can run meanwhile: ``Compiled.as_text()`` is one call that holds the GIL
    from the executable's modules to their text (3.4 s for a ResNet-50 step on
    a v5e, during which the step loop dispatched nothing: my probe, PR 47),
    where ``hlo_modules()`` alone, nearly all of it, releases the GIL and
    ``to_string()`` holds it for 0.05 s. The same text, as jax joins it."""
    executable = compiled.runtime_executable()
    if hasattr(executable, "hlo_modules"):
        return "\n\n".join(m.to_string() for m in executable.hlo_modules())
    return compiled.as_text()


def step_program() -> Optional[HloProgram]:
    """The one parse of the running stage's step executable that the three
    tables and the census share: made by whoever asks first (the census's
    thread, in a stage that trains), never inside the step loop. None without
    a step, or where the backend has no text. The lock is held to claim the
    parse, not across it: a stage that ends meanwhile swaps the slot and this
    parse is its own slot's, thrown away with it."""
    with _step_lock:
        slot = _step
        mine = slot is not None and not slot.claimed
        if mine:
            slot.claimed = True
    if slot is None:
        return None
    if not mine:
        slot.parsed.wait()
        return slot.program
    try:
        t0 = time.monotonic()
        text = executable_text(slot.compiled)
        t1 = time.monotonic()
        program = slot.program = HloProgram(text)
        program.cost = {
            "text_bytes": len(text), "text_s": t1 - t0,
            "parse_s": time.monotonic() - t1,
        }
    except Exception as exc:  # noqa: BLE001 — telemetry: a backend without text reads as no table
        logger.warning("step program unavailable: %s", exc)
        program = slot.program = HloProgram("")
    finally:
        slot.parsed.set()  # after the except: a waiter reads what it left
    return program


def step_phases() -> Dict[str, str]:
    """``{HLO instruction name: "forward" | "backward" | "optimizer" |
    "numerics" | "other"}`` for the step executable of the running
    stage. A device trace names an event by its HLO instruction
    (``fusion.124``), not by its scope: this is the join. Read off
    :func:`step_program`; {} without a step, or where its names are
    missing (see :func:`phases_of_hlo`)."""
    program = step_program()
    return dict(program.phases()) if program is not None else {}


def step_scopes(scopes: Sequence[str] = MOE_SCOPES) -> Dict[str, str]:
    """:func:`scopes_of_hlo` for the step executable of the running stage:
    which of a device trace's events ran under which of ``scopes``, by
    default the expert layer's; every scope a model enters is in
    :data:`STEP_PARTS`. A caller asks for the scopes of one mixer or layer at
    a time; an instruction under two of the asked scopes counts under the
    innermost. {} without a step or for a model that enters none of
    ``scopes``. (:func:`step_parts` is the same join for the whole step.)"""
    program = step_program()
    return dict(program.scopes(scopes)) if program is not None else {}


def step_parts() -> Dict[str, Tuple[str, str]]:
    """:func:`parts_of_hlo` for the step executable of the running stage:
    every instruction under one part of :data:`STEP_PARTS` (or ``attn_kernel``,
    ``block``, ``loss``, ``other``, :data:`CONTAINER`) and one pass of
    :data:`PHASES`, so a device trace's seconds sum by part to the step with
    a loop counted once. {} without a step."""
    program = step_program()
    return dict(program.parts()) if program is not None else {}


_M_STEP_PROGRAM = obs_metrics.gauge(
    "edl_train_step_program_count",
    "the running stage's compiled step, counted once a stage, by what: "
    "matmuls, unplaced_matmuls, kernel_calls, loops (HloProgram.census) and "
    "plain_fallbacks (the stage's call-site shapes that took the plain form "
    "where a kernel form exists)",
)
#: the totals that are gauges, each read by a listed metric of the benchmark
#: (``step_unplaced_share``, ``step_kernel_calls``, ``step_loops``,
#: ``step_plain_fallbacks``); the census's other totals are the instant's and
#: the flight record's alone
STEP_PROGRAM_GAUGES = (
    "matmuls", "unplaced_matmuls", "kernel_calls", "loops", "plain_fallbacks",
)


def publish_step_census(
    tracer, notes: Sequence[tuple] = (), plan: Optional[Dict] = None, **stage
) -> Optional[Dict]:
    """The census of the running stage's step (:meth:`HloProgram.census`),
    out three ways: one ``step_program`` instant in ``tracer``'s ring (the
    totals as arguments, the by-part and by-kernel tables and the memory
    ``plan``'s kinds as one argument each), the totals that a metric reads
    (:data:`STEP_PROGRAM_GAUGES`) as ``edl_train_step_program_count{what}`` and
    one ``step_program`` flight record. ``notes`` is what the stage had noted
    when its first step ended (``SpanTracer.notes()``): those with
    ``path="plain"`` are the ``plain_fallbacks``, listed in the instant with
    their ``why``. Called on a thread of its own (``train/loop.py``): the text
    of a large executable and the pass over it take seconds. None without a
    step or its names."""
    program = step_program()
    if program is None or not program.parts():
        return None
    t0 = time.monotonic()
    census = program.census()
    fallbacks = [
        dict(args, note=name) for name, args in notes if args.get("path") == "plain"
    ]
    totals = dict(census["totals"], plain_fallbacks=len(fallbacks))
    for what in STEP_PROGRAM_GAUGES:
        _M_STEP_PROGRAM.set(float(totals[what]), what=what)
    cost = dict(program.cost, census_s=time.monotonic() - t0)
    tracer.instant(
        "step_program", parts=census["parts"], kernels=census["kernels"],
        fallbacks=fallbacks, plan=dict(plan or {}), **totals, **cost, **stage,
    )
    obs_events.record("step_program", **totals, **cost, **stage)
    return census


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
