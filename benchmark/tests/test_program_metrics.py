"""The five readers of what the compiled step says of itself
(``step_plain_fallbacks``, ``step_kernel_calls``, ``step_loops``,
``step_unplaced_share`` from the census's gauge at the run's end,
``step_time_drift`` from the window's ``step_retired`` marks) on hand-made
``run`` objects: a run with no census gives nothing, zero counts give 0.0."""

import importlib.util
import json
import os
import types

import pytest

from benchmark import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
GAUGE = "edl_train_step_program_count"
COUNTERS = ("step_plain_fallbacks", "step_kernel_calls", "step_loops")
NEW = COUNTERS + ("step_unplaced_share", "step_time_drift")


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def census(**counts):
    return {GAUGE: {'{what="%s"}' % what: float(n) for what, n in counts.items()}}


def run_with(registry=None, marks=(), window_epochs=(2,)):
    events = [
        {"name": "step_retired", "ph": "i", "ts": float(i),
         "args": dict({"epoch": epoch, "step": 8 * i},
                      **({} if pace is None else
                         {"steps": 8, "seconds_per_step": pace}))}
        for i, (epoch, pace) in enumerate(marks)
    ]
    return types.SimpleNamespace(
        at_end={"registry": registry or {}}, tracer_events=events,
        window_epochs=list(window_epochs),
    )


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_census_or_the_marks_gives_nothing(name):
    assert reader(name).read(run_with()) is None
    # another gauge of the registry is not the census
    other = {"edl_train_hbm_plan_bytes": {'{kind="temp"}': 5e9}}
    assert reader(name).read(run_with(other)) is None


@pytest.mark.parametrize("name,what", zip(
    COUNTERS, ("plain_fallbacks", "kernel_calls", "loops")
))
def test_a_count_is_read_and_zero_is_a_number(name, what):
    full = census(instructions=15440, matmuls=486, kernel_calls=177, loops=45,
                  plain_fallbacks=2, unplaced_matmuls=0)
    want = {"plain_fallbacks": 2.0, "kernel_calls": 177.0, "loops": 45.0}[what]
    assert reader(name).read(run_with(full)) == want
    zero = reader(name).read(run_with(census(**{what: 0, "matmuls": 9})))
    assert zero == 0.0 and zero is not None
    # a census that lacks this count (an older program's) is nothing to read
    assert reader(name).read(run_with(census(matmuls=9))) is None


def test_the_unplaced_share_is_of_the_matmuls():
    read = reader("step_unplaced_share").read
    assert read(run_with(census(matmuls=222, unplaced_matmuls=3))) == pytest.approx(
        100 * 3 / 222
    )
    assert read(run_with(census(matmuls=486, unplaced_matmuls=0))) == 0.0
    assert read(run_with(census(matmuls=0, unplaced_matmuls=0))) is None
    assert read(run_with(census(matmuls=10))) is None


def test_the_drift_is_the_later_half_over_the_earlier():
    read = reader("step_time_drift").read
    # epoch 1 is the traced one; the window's first mark has no pace
    marks = [(1, 0.5), (2, None), (2, 0.100), (2, 0.102), (2, 0.110), (2, 0.112)]
    assert read(run_with(marks=marks)) == pytest.approx(100 * (0.111 / 0.101 - 1))
    # an odd count: the middle mark goes to the later half
    assert read(run_with(marks=marks[:-1])) == pytest.approx(100 * (0.106 / 0.100 - 1))
    steady = [(2, None)] + [(2, 0.25)] * 6
    assert read(run_with(marks=steady)) == 0.0
    # marks are taken in the ring's order of time, whatever the list's
    assert read(run_with(marks=marks)) == read(
        types.SimpleNamespace(
            tracer_events=list(reversed(run_with(marks=marks).tracer_events)),
            window_epochs=[2], at_end={"registry": {}},
        )
    )
    assert read(run_with(marks=[(2, None), (2, 0.1)])) is None   # one paced mark
    assert read(run_with(marks=[(1, 0.1), (1, 0.2), (1, 0.3)])) is None  # none in the window


def test_the_five_are_listed_with_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-5:] == list(NEW)
    cells = [c["name"] for c in bench["workloads"]]
    models = [c for c in cells if bench_run.find(
        bench["configs"], bench_run.find(bench["workloads"], c, "cell")["config"],
        "configuration")["name"] != "resnet50_vd"]
    for name in NEW:
        entry = listed[name]
        assert entry["better"] == "lower" and entry["source"] == "program_counter"
        assert entry["moves"] == "throughput"
        assert entry["workloads"] == (cells if name == "step_time_drift" else models)
    assert len(models) == 8 and len(cells) == 10
