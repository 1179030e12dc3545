"""A worker's start as one tiled timeline, read from the program's span ring:
the OS's start of the process -> the end of the first epoch, each moment under
the outermost program span that covers it, and what no span covers.

The interval runs from the start of ``process_boot`` (``edl_tpu/obs/trace.py``:
the process's start as ``/proc`` has it) to the end of the last span of epoch 0
on the same thread, the one that imported the program and runs the loop. The
tiles are the complete spans of that thread inside it, ``CONTAINERS`` left out
(their children tile them). Seconds are counted by the rule of
``startup_timeline.phase_s_inside``, lent to other names than jax's three
phases; the holes are walked here, each with the names of the tiles before and
after it, and sum to the interval less the tiles. A ring without
``process_boot`` (an older commit, or no ``/proc``) gives every reader nothing.

    python3 benchmark/setup_timeline.py <ring export>

prints a start by hand from a ``SpanTracer.export()`` file: the tiles in order,
the holes, and inside them ``package_import`` by package, ``model_trace`` by
part and layer and ``kernel_trace`` by kernel (SETUP_TIMELINE.md).
"""

from __future__ import annotations

import json
import os
import sys
import types

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import startup_timeline  # noqa: E402

#: spans that enclose a stretch their children account for
CONTAINERS = ("train_epoch", "worker_boot")


def seconds_inside(run, parent, name, among=None):
    """Seconds of the spans called ``name`` inside the span ``parent``, on its
    thread, each moment counted once and under the outermost of the spans
    called one of ``among`` (``name`` alone by default): the rule
    ``startup_timeline.phase_s_inside`` holds for jax's phases, handed the
    ring with ``name`` in one phase's place and the others of ``among`` in
    another's."""
    among = (name,) if among is None else among
    counted, other = startup_timeline.PHASES[:2]
    events = [
        dict(e, name=counted if e["name"] == name else other)
        for e in run.tracer_events
        if e.get("ph") == "X" and e.get("name") in among
    ]
    return startup_timeline.phase_s_inside(
        types.SimpleNamespace(tracer_events=events), parent, counted
    )


def interval(run):
    """The start as one span-like dict (``ts``, ``dur``, ``tid``):
    ``process_boot``'s start -> the end of the last span of epoch 0 on its
    thread. None without either."""
    boot = startup_timeline.first_span(run, "process_boot")
    if boot is None:
        return None
    ends = [
        e["ts"] + e["dur"] for e in run.tracer_events
        if e.get("ph") == "X" and e.get("tid") == boot.get("tid")
        and e.get("args", {}).get("epoch") == 0
    ]
    if not ends:
        return None
    return {"name": "setup", "ts": boot["ts"], "dur": max(ends) - boot["ts"],
            "tid": boot.get("tid")}


def tile_spans(run, whole):
    """The thread's complete spans that overlap ``whole``, containers left
    out, an enclosing span first where two start together."""
    start, end = whole["ts"], whole["ts"] + whole["dur"]
    return sorted(
        (e for e in run.tracer_events
         if e.get("ph") == "X" and e.get("tid") == whole.get("tid")
         and e.get("name") not in CONTAINERS
         and e["ts"] < end and e["ts"] + e["dur"] > start),
        key=lambda e: (e["ts"], -(e["ts"] + e["dur"])),
    )


def tiles_and_holes(run, whole):
    """``(tiles, holes)``: the outermost spans in order, and the stretches of
    ``whole`` between them as ``{"start_s", "seconds", "before", "after"}``,
    seconds after ``whole``'s start and the neighbours' names (None at an
    end)."""
    start, end = whole["ts"], whole["ts"] + whole["dur"]
    tiles, holes, covered, before = [], [], start, None

    def hole(until, after):
        if until > covered:
            holes.append({"start_s": (covered - start) / 1e6,
                          "seconds": (until - covered) / 1e6,
                          "before": before, "after": after})

    for ev in tile_spans(run, whole):
        if ev["ts"] + ev["dur"] <= covered:
            continue  # nested in a tile
        hole(min(ev["ts"], end), ev["name"])
        tiles.append(ev)
        covered, before = max(covered, ev["ts"] + ev["dur"]), ev["name"]
    hole(end, None)
    return tiles, holes


def tile_seconds(run, whole):
    """``{name: seconds}`` of the tiles, in the order they first appear."""
    tiles, _ = tiles_and_holes(run, whole)
    names = list(dict.fromkeys(ev["name"] for ev in tiles))
    return {name: seconds_inside(run, whole, name, names) for name in names}


def ring_s(run):
    whole = interval(run)
    return None if whole is None else whole["dur"] / 1e6


def unplaced_s(run):
    """The holes' sum: the interval less its tiles."""
    whole = interval(run)
    if whole is None:
        return None
    return sum(h["seconds"] for h in tiles_and_holes(run, whole)[1])


def before_train_setup(run):
    """The start up to the first ``train_setup``: where a worker boots."""
    whole = interval(run)
    setup = startup_timeline.first_span(run, "train_setup")
    if whole is None or setup is None:
        return None
    return dict(whole, dur=setup["ts"] - whole["ts"])


def model_trace_s(run):
    """Seconds of the outermost ``model_trace`` spans inside the first
    ``first_step`` less the ``kernel_trace`` inside them: the model's own
    Python under the step's trace. None without either span."""
    parent = startup_timeline.first_span(run, "first_step")
    if parent is None or startup_timeline.first_span(run, "model_trace") is None:
        return None
    both = ("model_trace", "kernel_trace")
    kernels_inside = (
        seconds_inside(run, parent, "kernel_trace")
        - seconds_inside(run, parent, "kernel_trace", both)
    )
    return seconds_inside(run, parent, "model_trace") - kernels_inside


# -- by hand ------------------------------------------------------------------


def _by(run, whole, name, key):
    """``{key(args): [spans, seconds]}`` of every span called ``name`` on the
    thread inside ``whole``, nested ones too, in order of first appearance."""
    out = {}
    for ev in tile_spans(run, whole):
        if ev["name"] == name:
            entry = out.setdefault(key(ev.get("args", {})), [0, 0.0])
            entry[0] += 1
            entry[1] += ev["dur"] / 1e6
    return out


def report(run):
    whole = interval(run)
    if whole is None:
        return "no process_boot span, or no span of epoch 0 on its thread"
    tiles, holes = tiles_and_holes(run, whole)
    lines = ["setup_ring_s %.3f  setup_unplaced_s %.3f  (%d spans in the ring)" % (
        ring_s(run), unplaced_s(run), len(run.tracer_events)), "", "tiles, in order:"]
    first = {}
    for ev in tiles:
        first.setdefault(ev["name"], (ev["ts"] - whole["ts"]) / 1e6)
    for name, seconds in tile_seconds(run, whole).items():
        count = sum(1 for ev in tiles if ev["name"] == name)
        lines.append("  +%8.3f s  %8.3f s  %s%s" % (
            first[name], seconds, name, " x%d" % count if count > 1 else ""))
    lines += ["", "holes:"] + [
        "  +%8.3f s  %8.3f s  %s -> %s" % (
            h["start_s"], h["seconds"], h["before"], h["after"])
        for h in sorted(holes, key=lambda h: -h["seconds"]) if h["seconds"] >= 0.001
    ]
    sections = (
        (whole, "package_import", "by package (nested ones inside the outer)",
         lambda a: "%s (%s modules)" % (a.get("package"), a.get("modules"))),
        (whole, "backend_init", "by platform", lambda a: a.get("platform")),
    ) + tuple(
        (parent, name, title, key)
        for parent in (startup_timeline.first_span(run, "train_setup"),
                       startup_timeline.first_span(run, "first_step"))
        if parent is not None
        for name, title, key in (
            ("model_trace", "by part, layer, mixer and ffn (x2: the Python ran again)",
             lambda a: " ".join(str(a[k]) for k in ("part", "layer", "mixer", "ffn")
                                if a.get(k) is not None)),
            ("kernel_trace", "by kernel", lambda a: a.get("kernel")),
        )
    )
    for parent, name, title, key in sections:
        found = _by(run, parent, name, key)
        if found:
            lines += ["", "%s inside %s, %s: %.3f s in %d spans" % (
                name, parent["name"], title, sum(s for _, s in found.values()),
                sum(n for n, _ in found.values()))]
            lines += ["  %8.3f s  x%-3d %s" % (s, n, k) for k, (n, s) in found.items()]
    model = model_trace_s(run)
    if model is not None:
        lines += ["", "step_trace_model_s %.3f (model_trace in first_step less kernel_trace inside)" % model]
    return "\n".join(lines)


def main(argv):
    if len(argv) != 1:
        raise SystemExit(__doc__)
    with open(argv[0]) as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    print(report(types.SimpleNamespace(tracer_events=events)))


if __name__ == "__main__":
    main(sys.argv[1:])
