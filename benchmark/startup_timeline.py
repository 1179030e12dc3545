"""What the readers of a worker's start share: the ring's spans inside the
first ``first_step`` span, by which of jax's own compile phases they are.

The program turns jax's trace / lower / backend-compile events into the spans
``jit_trace`` / ``jit_lower`` / ``jit_compile`` (one a traced, lowered or
compiled function, nested ones included: a ``jit_trace`` of an inner ``jit``
lies inside the step's own, and a lowering rule may trace). The seconds of
one phase are those of its outermost spans on ``first_step``'s own thread, cut
to ``first_step``'s interval: every moment is counted once and under the phase
that encloses it, so the three phases sum to no more than ``first_step``. A
program without these spans (an older commit) gives every reader nothing to
read.
"""

from __future__ import annotations

PHASES = ("jit_trace", "jit_lower", "jit_compile")


def first_span(run, name):
    """The ring's first complete span called ``name``, or None."""
    for ev in run.tracer_events:
        if ev.get("name") == name and ev.get("ph") == "X":
            return ev
    return None


def phase_s_inside(run, parent, phase):
    """Seconds of ``phase``'s outermost spans inside the span ``parent``, on
    its thread."""
    start, end = parent["ts"], parent["ts"] + parent["dur"]
    spans = sorted(
        ((e["ts"], e["ts"] + e["dur"], e["name"]) for e in run.tracer_events
         if e.get("name") in PHASES and e.get("ph") == "X"
         and e.get("tid") == parent.get("tid")
         and e["ts"] < end and e["ts"] + e["dur"] > start),
        # an enclosing span first where two start together
        key=lambda s: (s[0], -s[1]),
    )
    seconds, covered = 0.0, start
    for t0, t1, name in spans:
        if t1 <= covered:
            continue  # nested in a span already counted
        if name == phase:
            seconds += min(t1, end) - max(t0, covered)
        covered = t1
    return seconds / 1e6


def first_step_phase_s(run, phase):
    """``phase_s_inside`` the first ``first_step``. None where the ring holds
    no ``first_step`` or no span of ``phase`` at all; 0.0 where it holds some
    and none lies in the first step."""
    parent = first_span(run, "first_step")
    if parent is None or first_span(run, phase) is None:
        return None
    return phase_s_inside(run, parent, phase)
