"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time, the step program's device time and gaps,
time per device operation, collectives not hidden behind compute, and the
idle gaps by what the host was doing.

Every PR computes these the same way, from the same file. It knows nothing
of a model, a cell or a metric: a layer metric's own reader picks what it
needs out of the dict ``reduce`` returns.

    python3 benchmark/reduce_trace.py --dump <trace dir or file>

prints planes, lines and the first event names of a trace, for a first look
by hand.
"""

from __future__ import annotations

import glob
import os
import statistics
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all")
HOST_PREFIX = "bench:"        # the harness's own TraceAnnotations
# the program's spans that say what the host was doing (obs/trace.py ring)
PROGRAM_SPANS = ("ckpt_save", "ckpt_restore", "first_step", "train_step")


class NoDevicePlane(RuntimeError):
    """The trace holds no device plane: it was not taken on an accelerator."""


def find_xplane(trace_dir):
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return paths[-1]


def short_name(text):
    """``fusion.12`` from ``%fusion.12 = bf16[...] fusion(...)``: the device
    lines name an event by its whole HLO instruction."""
    name = text.split(" = ", 1)[0]
    return name[1:] if name.startswith("%") else name


def load(path, host_prefix=HOST_PREFIX):
    """The trace as plain data: ``{"start_ns": wall clock at time zero or
    None, "planes": [{"name", "lines": [{"name", "events": [(name, start_ns,
    duration_ns, text)]}]}]}``. Event times count from the start of the
    profile. ``text`` is the event's full name where ``name`` is a shortened
    HLO instruction. Of the host's events (millions, most of them the
    runtime's own) only those whose name starts with ``host_prefix`` are
    kept; None keeps all."""
    from jax.profiler import ProfileData

    if path.endswith(".textproto"):
        with open(path) as f:
            data = ProfileData.from_serialized_xspace(
                ProfileData.text_proto_to_serialized_xspace(f.read())
            )
    else:
        data = ProfileData.from_file(path)
    out = {"start_ns": None, "planes": []}
    for plane in data.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            if "profile_start_time" in stats:
                out["start_ns"] = int(stats["profile_start_time"])
        lines = []
        for line in plane.lines:
            events = []
            on_host = plane.name.startswith("/host:")
            for e in line.events:
                text = e.name
                if on_host:
                    if host_prefix is not None and not text.startswith(host_prefix):
                        continue
                    events.append((text, float(e.start_ns), float(e.duration_ns), ""))
                else:
                    events.append((short_name(text), float(e.start_ns),
                                   float(e.duration_ns), text[:400]))
            lines.append({"name": line.name, "events": events})
        out["planes"].append({"name": plane.name, "lines": lines})
    return out


# -- interval arithmetic (all in ns) -----------------------------------------


def union(intervals):
    """Sorted, merged copy of ``[(start, end), ...]``."""
    merged = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def subtract(intervals, holes):
    """The part of ``intervals`` (merged) that no interval of ``holes``
    (merged) covers."""
    out = []
    for s, e in intervals:
        cursor = s
        for hs, he in holes:
            if he <= cursor or hs >= e:
                continue
            if hs > cursor:
                out.append((cursor, hs))
            cursor = max(cursor, he)
            if cursor >= e:
                break
        if cursor < e:
            out.append((cursor, e))
    return out


def gaps(busy, lo, hi):
    """The idle intervals of ``[lo, hi]`` given merged busy intervals."""
    return subtract([(lo, hi)], busy)


def is_collective(name):
    return any(c in name for c in COLLECTIVES)


# -- the reduction -------------------------------------------------------------


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line
    return None


def device_planes(trace):
    return [
        p for p in trace["planes"]
        if p["name"].startswith("/device:") and _line(p, OPS_LINE) is not None
    ]


def host_spans(trace, tracer_events):
    """What the host was doing, on the trace's clock: the harness's own
    annotations from the host plane, and the program's spans (and the
    harness's, where it could only time them from outside) brought over
    through the wall clock the profile started at."""
    spans = []
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for name, start, dur, _ in line["events"]:
                if name.startswith(HOST_PREFIX):
                    spans.append((name, start, start + dur))
    if trace["start_ns"] is not None:
        for ev in tracer_events or ():
            name = ev.get("name", "")
            if ev.get("ph") == "X" and (
                name in PROGRAM_SPANS or name.startswith(HOST_PREFIX)
            ):
                start = ev["ts"] * 1e3 - trace["start_ns"]
                spans.append((ev["name"], start, start + ev["dur"] * 1e3))
    return spans


def label_gap(gap, spans):
    """The shortest host span that covers at least half of the gap."""
    s, e = gap
    best = None
    for name, hs, he in spans:
        overlap = min(e, he) - max(s, hs)
        if overlap * 2 >= (e - s) and (best is None or he - hs < best[1]):
            best = (name, he - hs)
    return best[0] if best else "no_host_span"


def step_intervals(plane, lo, hi):
    """``[(start, end)]`` of the step program's events on a device plane
    inside ``[lo, hi]``: of the programs on the modules line, the one that
    took most time."""
    modules = _line(plane, MODULES_LINE)
    by_name = {}
    for name, s, d, _ in modules["events"] if modules else ():
        if s + d > lo and s < hi:
            by_name.setdefault(name, []).append((s, s + d))
    if not by_name:
        return []
    return sorted(by_name[max(by_name, key=lambda n: total(by_name[n]))])


def reduce(path, window_ns=None, tracer_events=None, top=10):
    """The numbers of one trace. The window runs from the start of the first
    step program (what comes before it is the profiler starting) to the
    moment the harness stopped the trace, where ``window_ns`` gives it on
    the wall clock (so that the sync, the callback and the save that end
    the traced epoch are inside), else to the end of the last step."""
    trace = load(path)
    planes = device_planes(trace)
    if not planes:
        raise NoDevicePlane("the trace holds no device plane with an %r line" % OPS_LINE)
    ops_each = [
        union([(s, s + d) for _, s, d, _ in _line(p, OPS_LINE)["events"]])
        for p in planes
    ]
    lo = min(ops[0][0] for ops in ops_each if ops)
    hi = max(ops[-1][1] for ops in ops_each if ops)
    stop = None
    if window_ns and window_ns[1] and trace["start_ns"] is not None:
        lo = max(lo, window_ns[0] - trace["start_ns"])
        stop = window_ns[1] - trace["start_ns"]
        hi = min(hi, stop)

    # device 0: the step program, its operations, collectives, idle gaps
    first = planes[0]
    steps = step_intervals(first, lo, hi)
    if steps:
        lo = max(lo, steps[0][0])
        hi = max(stop, steps[-1][1]) if stop is not None else steps[-1][1]
    busy_each = [total(clip(ops, lo, hi)) for ops in ops_each]
    busy_s = sum(busy_each) / len(busy_each) / 1e9
    window_s = (hi - lo) / 1e9

    op_events = [
        (n, max(s, lo), min(s + d, hi), st)
        for n, s, d, st in _line(first, OPS_LINE)["events"] if s + d > lo and s < hi
    ]
    op_seconds, op_text = {}, {}
    for name, s, e, text in op_events:
        op_seconds[name] = op_seconds.get(name, 0.0) + (e - s) / 1e9
        op_text.setdefault(name, text)
    collective = union([(s, e) for n, s, e, _ in op_events if is_collective(n)])
    compute = union([(s, e) for n, s, e, _ in op_events if not is_collective(n)])
    exposed = subtract(collective, compute)
    busy = clip(ops_each[0], lo, hi)

    # a step's device time is the time an operation ran inside its program's
    # event, not the event's length: under the profiler a program can stand
    # for seconds waiting for an input transfer (PERF.md section 6, PR 22)
    inside = [i for i in (clip(busy, s, e) for s, e in steps) if i]
    busy_ms = [total(i) / 1e6 for i in inside]
    gap_ms = [(b[0][0] - a[-1][1]) / 1e6 for a, b in zip(inside, inside[1:])]

    spans = host_spans(trace, tracer_events)
    idle = {}
    for gap in gaps(busy, lo, hi):
        label = label_gap(gap, spans)
        idle[label] = idle.get(label, 0.0) + (gap[1] - gap[0]) / 1e9

    def ranked(table):
        return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:top]]

    by_kind = {}  # fusion.12 and fusion.13 are both "fusion"
    for name, seconds in op_seconds.items():
        by_kind[name.split(".")[0]] = by_kind.get(name.split(".")[0], 0.0) + seconds
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "devices": len(planes),
        "steps": len(busy_ms),
        "step_busy_ms_median": statistics.median(busy_ms) if busy_ms else None,
        "step_gap_ms_median": statistics.median(gap_ms) if gap_ms else None,
        "step_busy_s_total": sum(busy_ms) / 1e3,
        "op_seconds": op_seconds,
        "op_text": op_text,
        "collective_s": total(collective) / 1e9,
        "collective_exposed_s": total(exposed) / 1e9,
        "breakdown": {"device_ops": ranked(op_seconds), "idle_gaps": ranked(idle)},
        "summary": {
            "busy_s_each": [b / 1e9 for b in busy_each], "window_s": window_s,
            "steps": len(busy_ms), "step_busy_ms": busy_ms, "step_gaps_ms": gap_ms,
            "step_event_ms": [(e - s) / 1e6 for s, e in steps],
            "by_kind": ranked(by_kind),
            "collective_s": total(collective) / 1e9,
            "collective_exposed_s": total(exposed) / 1e9,
        },
    }


def seconds_matching(reduced, patterns):
    """Device seconds of the operations whose HLO instruction holds every
    string of ``patterns``; None when there is none."""
    hit = [
        seconds for name, seconds in reduced["op_seconds"].items()
        if all(p in reduced["op_text"][name] for p in patterns)
    ]
    return sum(hit) if hit else None


def dump(path, events_per_line=12):
    if os.path.isdir(path):
        path = find_xplane(path)
    trace = load(path, host_prefix=None)
    print("trace %s  start_ns %s" % (path, trace["start_ns"]))
    for plane in trace["planes"]:
        print("PLANE %r  lines %d" % (plane["name"], len(plane["lines"])))
        for line in plane["lines"]:
            events = line["events"]
            print("  LINE %r  events %d" % (line["name"], len(events)))
            seen = {}
            for name, s, d, text in events:
                row = seen.setdefault(name, [0, 0.0, s, text])
                row[0] += 1
                row[1] += d
            ranked = sorted(seen.items(), key=lambda kv: -kv[1][1])
            shown = ranked[:events_per_line] + [
                kv for kv in ranked[events_per_line:]
                if "custom-call" in kv[1][3] or kv[0].startswith(HOST_PREFIX)
            ]
            for name, (n, dur, s, text) in shown:
                print("     %-40s n=%-5d total_ms=%-10.3f first_start_ns=%-14.0f %s"
                      % (name[:40], n, dur / 1e6, s, text[:160]))


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--dump":
        dump(sys.argv[2])
    else:
        sys.exit(__doc__)
