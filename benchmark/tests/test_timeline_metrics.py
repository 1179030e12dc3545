"""The readers of the program's own timeline (``benchmark/timeline.py`` and
the eight ``layer_metrics`` files built on it) on hand-made ``run`` objects:
ring events inside and outside the window's epochs, no events, no trace."""

import importlib.util
import json
import os
import types

import pytest

from benchmark import run as bench_run
from benchmark import timeline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def span(name, dur_ms, epoch, ph="X", **args):
    return {"name": name, "ph": ph, "ts": 0.0, "dur": dur_ms * 1e3,
            "args": dict(args, epoch=epoch)}


def retired(epoch, per_step=None):
    args = {} if per_step is None else {"seconds_per_step": per_step, "steps": 8}
    return span("step_retired", 0.0, epoch, ph="i", step=7, **args)


# epoch 0 warms up, epoch 1 is traced, epoch 2 is the window: 4 whole steps
EVENTS = [
    {"name": "process_name", "ph": "M", "pid": 1, "args": {"name": "proc"}},
    span("train_step", 900.0, 0, step=0), span("step_dispatch", 800.0, 0, step=0),
    span("feed_put", 70.0, 1, batch=0), retired(1, 0.5),
    # the window
    span("train_step", 3.0, 2, step=0), span("train_step", 3.0, 2, step=1),
    span("train_step", 3.0, 2, step=2), span("train_step", 807.0, 2, step=3),
    span("data_wait", 0.5, 2, step=0), span("data_wait", 0.5, 2, step=1),
    span("data_wait", 0.5, 2, step=2), span("data_wait", 0.5, 2, step=3),
    span("data_wait", 40.0, 2, step=4, error="StopIteration"),
    span("step_dispatch", 2.0, 2, step=0), span("step_dispatch", 2.0, 2, step=1),
    span("step_dispatch", 1.0, 2, step=2), span("step_dispatch", 5.0, 2, step=3),
    span("numerics_fetch", 800.0, 2, step=2),
    span("feed_put", 9.0, 2, batch=0), span("feed_put", 11.0, 2, batch=1),
    span("feed_put", 30.0, 2, batch=2),
    retired(2), retired(2, 0.100), retired(2, 0.102), retired(2, 0.300),
]
TRACE = {"steps": 2, "op_seconds": {
    "fusion.1": 0.040, "fusion.2": 0.060, "fusion.3": 0.010, "fusion.4": 0.004,
    "copy.1": 0.002, "fusion.99": 0.001,
}}
TABLE = {"fusion.1": "forward", "fusion.2": "backward", "fusion.3": "optimizer",
         "fusion.4": "numerics", "copy.1": "other"}


def make_run(events=EVENTS, trace=None):
    return types.SimpleNamespace(
        tracer_events=events, window_epochs=[2], window_steps=4, trace=trace,
    )


@pytest.mark.parametrize("name,want", [
    ("host_step_ms", 102.0),          # the median of the window's three marks
    ("dispatch_ms", 2.0),             # not the 800 ms of the warm-up epoch
    # (3 + 3 + 3 + 807) - (4 x 0.5 + 10 + 800) over 4 steps; the wait that
    # found the end of the feed is no step's child
    ("loop_self_ms", 1.0),
    ("feed_put_ms", 11.0),
])
def test_ring_readers_take_the_window_only(name, want):
    assert reader(name).read(make_run()) == pytest.approx(want)


@pytest.mark.parametrize("name", ["host_step_ms", "dispatch_ms", "loop_self_ms",
                                  "feed_put_ms"])
def test_ring_readers_find_nothing_where_there_is_nothing(name):
    assert reader(name).read(make_run(events=[])) is None
    outside = [e for e in EVENTS if e.get("args", {}).get("epoch") != 2]
    assert reader(name).read(make_run(events=outside)) is None
    # a program from before the spans still has train_step, and no child
    old = [e for e in EVENTS if e["name"] in ("process_name", "train_step")]
    assert reader(name).read(make_run(events=old)) is None


@pytest.mark.parametrize("phase,want", [
    ("forward", 20.0), ("backward", 30.0), ("optimizer", 5.0), ("numerics", 2.0),
])
def test_phase_readers_join_the_trace_to_the_programs_table(monkeypatch, phase, want):
    from edl_tpu.obs import profile

    read = reader("step_%s_ms" % phase).read
    monkeypatch.setattr(profile, "step_phases", lambda: dict(TABLE))
    assert read(make_run(trace=TRACE)) == pytest.approx(want)
    assert read(make_run(trace=None)) is None            # no device trace
    assert read(make_run(trace=dict(TRACE, steps=0))) is None
    monkeypatch.setattr(profile, "step_phases", lambda: {})
    assert read(make_run(trace=TRACE)) is None           # no table: no number
    monkeypatch.delattr(profile, "step_phases")
    assert read(make_run(trace=TRACE)) is None           # a program before it


def test_the_phase_readers_run_from_a_file_that_lists_them(tmp_path, monkeypatch):
    """``BENCHMARK.json`` does not list the four (``test_rehearse.py`` keeps by
    hand what a CPU may miss); ``with_phases`` makes the file that does, and
    ``run.py --benchmark`` then judges them in every cell."""
    from edl_tpu.obs import profile

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = [m["name"] for m in bench["per_layer"]]
    assert not set(timeline.PHASE_READERS) & set(listed)
    extended = timeline.with_phases(bench)
    assert [m["name"] for m in extended["per_layer"]] == listed + list(timeline.PHASE_READERS)
    assert all("workloads" not in m for m in extended["per_layer"][len(listed):])
    assert timeline.with_phases(extended) == extended
    os.symlink(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(extended))
    finder = bench_run.Finder(str(tmp_path / "BENCHMARK.json"))
    monkeypatch.setattr(profile, "step_phases", lambda: dict(TABLE))
    run = make_run(trace=TRACE)
    judged = {}
    for metric in finder.bench["per_layer"][len(listed):]:
        value = finder.module("layer_metrics", metric["name"]).read(run)
        assert bench_run.applies(metric, "resnet50_vd.dp4")
        judged[metric["name"]] = value
    assert judged == pytest.approx({"step_forward_ms": 20.0, "step_backward_ms": 30.0,
                                    "step_optimizer_ms": 5.0, "step_numerics_ms": 2.0})
