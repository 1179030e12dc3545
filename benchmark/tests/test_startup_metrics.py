"""The four readers of a worker's start (``benchmark/startup_timeline.py`` and
``layer_metrics/step_{trace,lower,compile,relower}_s.py``) on hand-made rings:
jax's spans inside and outside ``first_step``, nested, on another thread, cut
by its ends; no ``first_step``; a program from before the spans."""

import importlib.util
import json
import os
import types

import pytest

from benchmark import startup_timeline
from benchmark.tools import startup_split

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAMES = ("step_trace_s", "step_lower_s", "step_compile_s", "step_relower_s")
LOOP, FEEDER = 7, 8


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def span(name, start_s, dur_s, tid=LOOP, **args):
    return {"name": name, "ph": "X", "ts": start_s * 1e6, "dur": dur_s * 1e6,
            "tid": tid, "args": args}


# state_init 0-3 s, first_step 10-20 s, step_relower 20-21.5 s, a recompile at 40 s
RING = [
    {"name": "process_name", "ph": "M", "pid": 1, "args": {"name": "proc"}},
    span("state_init", 0.0, 3.0),
    span("jit_trace", 0.1, 0.6, fun="init_state"),
    span("jit_lower", 0.8, 1.0, fun="jit_init_state"),
    span("jit_compile", 1.9, 1.0, fun="jit_init_state"),
    span("data_wait", 10.0, 0.5, epoch=0, step=0),
    # a helper jit before the step's own: trace, lower, compile, all short
    span("jit_trace", 10.6, 0.1, fun="convert_element_type"),
    span("jit_lower", 10.7, 0.1, fun="jit_convert_element_type"),
    span("jit_compile", 10.8, 0.1, fun="jit_convert_element_type"),
    # the step: 4 s of tracing, two inner jits and two kernel bodies inside it
    span("jit_trace", 11.0, 4.0, fun="step"),
    span("jit_trace", 11.5, 1.0, fun="_where"),
    span("jit_trace", 13.0, 0.5, fun="gmm"),
    span("kernel_trace", 12.6, 0.3, kernel="flash2_fwd"),
    span("kernel_trace", 13.1, 0.3, kernel="gmm"),
    # 3 s of lowering, a lowering rule traces inside it
    span("jit_lower", 15.0, 3.0, fun="jit_step"),
    span("jit_trace", 16.0, 0.25, fun="_take"),
    # 1.5 s of backend compile, 1.2 s of it the cache's read
    span("jit_compile", 18.0, 1.5, fun="jit_step"),
    span("cache_load", 18.2, 1.2, module="jit_step", hit=True, ladder=False),
    span("step_dispatch", 10.5, 9.1, epoch=0, step=0),
    span("numerics_fetch", 19.6, 0.4, epoch=0, step=0),
    # the feeder's thread compiles something meanwhile: not the loop's time
    span("jit_compile", 12.0, 2.0, tid=FEEDER, fun="jit_on_the_feeder"),
    span("first_step", 10.0, 10.0, epoch=0),
    span("step_relower", 20.0, 1.5, compiled=True),
    span("jit_trace", 20.1, 0.0625, fun="step"),
    # a new shape much later: the recompile signal, outside every reader
    span("jit_trace", 40.0, 4.0, fun="step"),
    span("jit_lower", 44.0, 3.0, fun="jit_step"),
    span("jit_compile", 47.0, 30.0, fun="jit_step"),
    span("first_step", 60.0, 2.0, epoch=5),
]


def make_run(events=RING):
    return types.SimpleNamespace(tracer_events=events)


@pytest.mark.parametrize("name,want", [
    ("step_trace_s", 4.1),     # the step's and the helper's; inner ones once
    ("step_lower_s", 3.1),     # the trace inside the lowering is lowering
    ("step_compile_s", 1.6),   # the feeder's compile is not the loop's
    ("step_relower_s", 1.5),
])
def test_the_readers_take_the_first_first_step(name, want):
    assert reader(name).read(make_run()) == pytest.approx(want)


def test_the_three_phases_never_sum_to_more_than_first_step():
    run = make_run()
    total = sum(reader(n).read(run) for n in NAMES[:3])
    assert total == pytest.approx(8.8) and total <= 10.0


@pytest.mark.parametrize("name", NAMES)
def test_without_a_first_step_there_is_nothing_to_read(name):
    ring = [e for e in RING if e["name"] not in ("first_step", "step_relower")]
    assert reader(name).read(make_run(ring)) is None
    assert reader(name).read(make_run([])) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_program_from_before_the_spans_gives_nothing(name):
    old = [e for e in RING if e["name"] in ("first_step", "data_wait",
                                            "step_dispatch", "state_init")]
    assert reader(name).read(make_run(old)) is None


@pytest.mark.parametrize("name,want", [("step_trace_s", 0.0), ("step_lower_s", 0.0),
                                       ("step_compile_s", 0.0)])
def test_spans_outside_first_step_are_not_its(name, want):
    # the step came out of jax's own caches: state_init's spans are all there is
    ring = [e for e in RING if e["name"] not in startup_timeline.PHASES
            or e["ts"] < 10e6]
    assert reader(name).read(make_run(ring)) == want


def test_a_span_across_first_steps_end_counts_up_to_it():
    ring = [span("first_step", 10.0, 10.0), span("jit_compile", 19.0, 5.0, fun="f"),
            span("jit_trace", 8.0, 3.0, fun="g")]
    assert reader("step_compile_s").read(make_run(ring)) == pytest.approx(1.0)
    assert reader("step_trace_s").read(make_run(ring)) == pytest.approx(1.0)


@pytest.mark.parametrize("name", NAMES)
def test_the_reader_matches_its_entry_and_names_no_cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    module, entry = reader(name), entries[name]
    assert "workloads" not in entry  # every cell reports it
    assert (module.NAME, module.UNIT, module.LAYER, module.MOVES, module.SOURCE) == (
        name, "s", "Compile / cache", "setup_s", "program_span")
    assert (entry["unit"], entry["layer"], entry["moves"], entry["source"],
            entry["better"]) == ("s", "Compile / cache", "setup_s", "program_span",
                                 "lower")


def test_the_split_of_an_exported_ring_accounts_for_first_step():
    out = startup_split.split(RING)
    first = out["first_step"]
    assert first["s"] == pytest.approx(10.0)
    assert (first["jit_trace"], first["jit_lower"], first["jit_compile"]) == (
        pytest.approx(4.1), pytest.approx(3.1), pytest.approx(1.6))
    assert first["data_wait"] == pytest.approx(0.5)
    assert first["numerics_fetch"] == pytest.approx(0.4)
    assert first["cache_load"] == pytest.approx(1.2)
    # 10 - 8.8 - 0.5 - 0.4
    assert first["unnamed"] == pytest.approx(0.3)
    assert first["kernel_trace"]["n"] == 2
    assert first["kernel_trace"]["gmm"] == {"n": 1, "s": pytest.approx(0.3)}
    assert out["step_relower"]["s"] == pytest.approx(1.5)
    assert out["step_relower"]["jit_trace"] == pytest.approx(0.0625)
    assert out["state_init"]["jit_compile"] == pytest.approx(1.0)
    assert out["jit_compile_after_first_step"] == [["jit_step", pytest.approx(30.0)]]
    assert out["cache_load"] == {"n": 1, "s": pytest.approx(1.2), "missed": []}
