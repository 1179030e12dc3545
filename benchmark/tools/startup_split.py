"""A worker's start, span by span, from the ring a run exported.

    EDL_TRACE_DIR=chiprun_out/startup/<cell> python3 benchmark/run.py --workload <cell> ...
    python3 benchmark/tools/startup_split.py chiprun_out/startup/<cell>/*.trace.json

One JSON object a file: ``state_init``, ``first_step`` and ``step_relower``
with the seconds of jax's trace / lower / compile phases inside each
(``benchmark/startup_timeline.py``: outermost spans, each moment once), the
program's own spans inside ``first_step``, the persistent cache's reads, and
the Pallas bodies a start traces (``kernel_trace``, by kernel). This is
PERF.md section 5's set-up table, one row a cell; the four ``step_*_s``
metrics of ``BENCHMARK.json`` are the same numbers from a run's own line.
"""

import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import startup_timeline  # noqa: E402

CHILDREN = ("data_wait", "step_dispatch", "step_launch", "numerics_fetch",
            "cache_load")


def inside(events, parent, name):
    start, end = parent["ts"], parent["ts"] + parent["dur"]
    return [e for e in events if e.get("name") == name and e.get("ph") == "X"
            and start <= e["ts"] and e["ts"] + e["dur"] <= end + 1]


def split(events):
    run = types.SimpleNamespace(tracer_events=events)
    out = {}
    for name in ("state_init", "first_step", "step_relower"):
        parent = startup_timeline.first_span(run, name)
        if parent is None:
            continue
        row = {"s": parent["dur"] / 1e6}
        for phase in startup_timeline.PHASES:
            row[phase] = startup_timeline.phase_s_inside(run, parent, phase)
            row[phase + "_n"] = len(inside(events, parent, phase))
        for child in CHILDREN:
            spans = inside(events, parent, child)
            if spans:
                row[child] = sum(e["dur"] for e in spans) / 1e6
        # dispatch encloses the phases and the cache's read lies in the compile
        row["unnamed"] = row["s"] - sum(
            row.get(k, 0.0)
            for k in startup_timeline.PHASES
            + ("data_wait", "step_launch", "numerics_fetch")
        )
        bodies = inside(events, parent, "kernel_trace")
        if bodies:
            row["kernel_trace"] = {"n": len(bodies),
                                   "s": sum(e["dur"] for e in bodies) / 1e6}
            for e in bodies:
                k = row["kernel_trace"].setdefault(
                    e["args"]["kernel"], {"n": 0, "s": 0.0})
                k["n"] += 1
                k["s"] += e["dur"] / 1e6
        out[name] = row
    first = startup_timeline.first_span(run, "first_step")
    if first is not None:
        later = [e for e in events if e.get("name") == "jit_compile"
                 and e["ts"] >= first["ts"] + first["dur"]]
        out["jit_compile_after_first_step"] = [
            [e["args"].get("fun"), e["dur"] / 1e6] for e in later]
    loads = [e for e in events if e.get("name") == "cache_load"]
    out["cache_load"] = {
        "n": len(loads), "s": sum(e["dur"] for e in loads) / 1e6,
        "missed": [e["args"].get("module") for e in loads
                   if not e["args"].get("hit")],
    }
    return out


if __name__ == "__main__":
    for path in sys.argv[1:]:
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        print(json.dumps({"file": path, **split(events)}))
