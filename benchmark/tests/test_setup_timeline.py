"""``benchmark/setup_timeline.py`` and the six readers of a start's tiling
(``layer_metrics/boot_{process,import,backend}_s.py``, ``step_trace_model_s.py``,
``setup_{ring,unplaced}_s.py``) on a hand-made ring: nested spans counted once, a
hole named by its neighbours, another thread's spans ignored, containers left
out; a ring from before the spans."""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import setup_timeline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAMES = ("boot_process_s", "boot_import_s", "boot_backend_s", "step_trace_model_s",
         "setup_ring_s", "setup_unplaced_s")
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


# the process starts at 100 s; epoch 0 ends at 140 s
RING = [
    {"name": "process_name", "ph": "M", "pid": 1, "args": {"name": "proc"}},
    span("process_boot", 100.0, 4.0, modules=700, jax_loaded=True),
    span("package_import", 104.5, 0.5, package="edl_tpu.train.context", modules=40),
    # the TPU's runtime, then the CPU's client; a hole of 0.25 s before them
    span("backend_init", 105.25, 5.0, platform="tpu", devices=1),
    span("backend_init", 110.25, 0.25, platform="cpu", devices=1),
    # the loop's import holds the models' package: counted once
    span("package_import", 111.0, 3.0, package="edl_tpu.train.loop", modules=400),
    span("package_import", 112.0, 1.0, package="edl_tpu.models", modules=20),
    # 1.75 s the harness builds its job in: the hole the program has no span for
    span("trainer_init", 115.75, 0.25, ckpt=False),
    span("state_init", 116.5, 2.0, leaves=10, bytes=100),
    span("model_trace", 116.6, 0.5, part="embed"),
    span("train_setup", 116.0, 4.0),
    # the feeder's thread: not the loop's time
    span("feed_put", 119.0, 5.0, tid=FEEDER, epoch=0),
    span("data_wait", 120.0, 0.5, epoch=0, step=0),
    span("jit_trace", 120.5, 6.0, fun="step"),
    # the model's Python: two blocks, a kernel's body inside the second, and a
    # backward kernel's body outside every block
    span("model_trace", 121.0, 0.5, part="embed"),
    span("model_trace", 121.5, 1.0, part="block", layer="layer_0", mixer="attn", ffn="mlp"),
    span("kernel_trace", 122.75, 0.5, kernel="flash2_fwd"),
    span("model_trace", 122.5, 1.5, part="block", layer="layer_1", mixer="attn", ffn="moe"),
    span("kernel_trace", 125.0, 0.25, kernel="flash2_bwd"),
    span("step_dispatch", 120.5, 9.5, epoch=0, step=0),
    span("first_step", 120.0, 10.0, epoch=0),
    span("train_step", 120.0, 10.0, epoch=0, step=0),
    span("step_relower", 130.0, 0.5, compiled=True),
    span("train_step", 130.0, 2.0, epoch=0, step=1),
    span("train_step", 132.0, 2.0, epoch=0, step=2),
    # the wait that ends the epoch is no step's: a tile of its own
    span("data_wait", 134.0, 0.25, epoch=0, step=3),
    span("epoch_sync", 134.25, 5.0, epoch=0, step=2),
    # the container of all of the epoch, which is no tile
    span("train_epoch", 120.0, 19.5, epoch=0, steps=3),
    span("epoch_end_hook", 139.5, 0.5, epoch=0),
    # the next epoch: after the start
    span("model_trace", 150.0, 9.0, part="embed"),
    span("train_step", 140.0, 2.0, epoch=1, step=0),
]


def make_run(events=RING):
    return types.SimpleNamespace(tracer_events=events)


@pytest.mark.parametrize("name,want", [
    ("boot_process_s", 4.0),
    ("boot_import_s", 3.5),        # the models' package inside the loop's, once
    ("boot_backend_s", 5.25),      # both platforms
    ("step_trace_model_s", 2.5),   # 3.0 of model Python less the body inside it
    ("setup_ring_s", 40.0),
    ("setup_unplaced_s", 3.25),
])
def test_reader_on_a_hand_made_ring(name, want):
    module = reader(name)
    assert module.read(make_run()) == pytest.approx(want, abs=1e-6)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert (module.NAME, module.UNIT, module.LAYER, module.MOVES, module.SOURCE) == (
        name, entry["unit"], entry["layer"], entry["moves"], entry["source"])
    assert "workloads" not in entry and entry["better"] == "lower"


def without(*names):
    return make_run([e for e in RING if e["name"] not in names])


@pytest.mark.parametrize("name,missing", [
    ("boot_process_s", ("process_boot",)),
    ("boot_import_s", ("package_import",)),
    ("boot_backend_s", ("backend_init",)),
    ("step_trace_model_s", ("model_trace",)),
    ("setup_ring_s", ("process_boot",)),
    ("setup_unplaced_s", ("process_boot",)),
])
def test_reader_finds_nothing_on_a_ring_without_its_span(name, missing):
    assert reader(name).read(without(*missing)) is None
    # an older commit's ring: none of this PR's spans
    older = without("process_boot", "package_import", "backend_init",
                    "trainer_init", "model_trace")
    assert reader(name).read(older) is None
    assert reader(name).read(make_run([])) is None


def test_the_tiles_are_the_outermost_spans_in_order():
    run = make_run()
    whole = setup_timeline.interval(run)
    assert (whole["ts"], whole["dur"], whole["tid"]) == (100.0e6, 40.0e6, LOOP)
    seconds = setup_timeline.tile_seconds(run, whole)
    assert list(seconds) == [
        "process_boot", "package_import", "backend_init", "trainer_init",
        "train_setup", "first_step", "train_step", "data_wait", "epoch_sync",
        "epoch_end_hook",
    ]
    assert seconds == pytest.approx({
        "process_boot": 4.0, "package_import": 3.5, "backend_init": 5.25,
        "trainer_init": 0.25, "train_setup": 4.0, "first_step": 10.0,
        "train_step": 4.0, "data_wait": 0.25, "epoch_sync": 5.0,
        "epoch_end_hook": 0.5,
    })
    # every moment once: the tiles and the holes are the interval
    assert sum(seconds.values()) + setup_timeline.unplaced_s(run) == pytest.approx(40.0)


def test_a_hole_is_named_by_its_neighbours():
    run = make_run()
    _, holes = setup_timeline.tiles_and_holes(run, setup_timeline.interval(run))
    assert [(h["before"], h["after"], h["start_s"], h["seconds"]) for h in holes] == [
        ("process_boot", "package_import", 4.0, 0.5),
        ("package_import", "backend_init", 5.0, 0.25),
        ("backend_init", "package_import", 10.5, 0.5),
        ("package_import", "trainer_init", 14.0, 1.75),
        ("epoch_sync", "epoch_end_hook", 39.25, 0.25),
    ]
    assert sum(h["seconds"] for h in holes) == pytest.approx(
        setup_timeline.unplaced_s(run))


def test_another_threads_spans_and_the_containers_are_no_tiles():
    run = make_run()
    whole = setup_timeline.interval(run)
    tiles, _ = setup_timeline.tiles_and_holes(run, whole)
    assert {"feed_put", "train_epoch"}.isdisjoint(t["name"] for t in tiles)
    # a span of the feeder's over the harness's hole fills nothing
    more = make_run(RING + [span("feed_put", 114.0, 2.0, tid=FEEDER)])
    assert setup_timeline.unplaced_s(more) == pytest.approx(3.25)
    # a launcher's worker_boot over the whole boot leaves the tiles as they are
    launched = make_run(RING + [span("worker_boot", 99.0, 16.5)])
    assert setup_timeline.tile_seconds(launched, whole) == pytest.approx(
        setup_timeline.tile_seconds(run, whole))


def test_seconds_inside_counts_a_moment_under_the_outermost_name():
    run = make_run()
    first = [e for e in RING if e["name"] == "first_step"][0]
    assert setup_timeline.seconds_inside(run, first, "kernel_trace") == pytest.approx(0.75)
    assert setup_timeline.seconds_inside(
        run, first, "kernel_trace", ("model_trace", "kernel_trace")
    ) == pytest.approx(0.25)
    assert setup_timeline.seconds_inside(run, first, "model_trace") == pytest.approx(3.0)


def test_the_table_by_hand(tmp_path):
    path = tmp_path / "ring.json"
    path.write_text(json.dumps({"traceEvents": RING}))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "setup_timeline.py"), str(path)],
        capture_output=True, text=True, check=True,
    ).stdout
    assert "setup_ring_s 40.000  setup_unplaced_s 3.250" in out
    assert "1.750 s  package_import -> trainer_init" in out
    assert "edl_tpu.models (20 modules)" in out
    assert "block layer_1 attn moe" in out and "flash2_bwd" in out
    assert "train_step x2" in out
    assert "step_trace_model_s 2.500" in out
