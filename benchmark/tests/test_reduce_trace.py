"""The trace reduction on hand-made intervals and on a small recorded trace
(``data/small_trace.textproto``, whose header says what it holds)."""

import os

import pytest

from benchmark import reduce_trace as rt

TRACE = os.path.join(os.path.dirname(__file__), "data", "small_trace.textproto")


def test_union_merges_overlaps_and_touching():
    assert rt.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert rt.total(rt.union([(0, 2), (1, 3)])) == 3


def test_subtract_and_gaps():
    assert rt.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert rt.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert rt.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert rt.clip([(0, 5), (8, 12)], 2, 10) == [(2, 5), (8, 10)]


def test_collective_overlap_on_hand_made_intervals():
    collective = rt.union([(5, 7), (20, 22)])
    compute = rt.union([(0, 6), (21, 30)])
    assert rt.subtract(collective, compute) == [(6, 7), (20, 21)]


def test_label_gap_takes_the_shortest_covering_span():
    spans = [("train_step", 0, 100), ("ckpt_save", 40, 60), ("bench:x", 90, 95)]
    assert rt.label_gap((45, 55), spans) == "ckpt_save"
    assert rt.label_gap((10, 20), spans) == "train_step"
    assert rt.label_gap((200, 210), spans) == "no_host_span"


def test_recorded_trace():
    # the program's own span, on the wall clock: a save over [22, 24) ms
    start = 1_000_000_000_000  # the trace's profile_start_time, ns
    events = [{"ph": "X", "name": "ckpt_save", "ts": (start + 22e6) / 1e3, "dur": 2e3}]
    r = rt.reduce(TRACE, tracer_events=events)
    assert r["devices"] == 1
    assert r["steps"] == 3                       # the step program, not jit_small
    assert r["step_busy_ms_median"] == pytest.approx(10.0)
    assert r["step_gap_ms_median"] == pytest.approx(2.0)
    assert r["step_busy_s_total"] == pytest.approx(0.030)
    assert r["window_s"] == pytest.approx(0.034)  # first step's start -> last step's end
    assert r["busy_s"] == pytest.approx(0.030)
    assert r["collective_s"] == pytest.approx(0.006)
    assert r["collective_exposed_s"] == pytest.approx(0.003)
    assert r["op_seconds"]["fusion.1"] == pytest.approx(0.018)
    assert r["op_text"]["flash_kernel.1"].startswith("%flash_kernel.1 = ")
    assert rt.seconds_matching(r, ("%flash_kernel.", " custom-call(")) == pytest.approx(0.009)
    assert rt.seconds_matching(r, ("%flash_kernel.", " fusion(")) is None
    assert r["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(0.018)]
    gaps = dict(r["breakdown"]["idle_gaps"])
    # [10, 12) under the harness's annotation, [22, 24) under the program's span
    assert gaps["bench:on_epoch_end"] == pytest.approx(0.002)
    assert gaps["ckpt_save"] == pytest.approx(0.002)


def test_recorded_trace_window_on_the_wall_clock():
    start = 1_000_000_000_000
    r = rt.reduce(TRACE, window_ns=[start + 12_000_000, start + 24_000_000])
    assert r["window_s"] == pytest.approx(0.012)
    assert r["busy_s"] == pytest.approx(0.010)
    assert r["steps"] == 1


def test_a_program_that_stands_waiting_is_not_device_time(tmp_path):
    """As on four chips under the profiler: the second step's program event
    is 110 ms long because it waits 100 ms for its input after its first
    operation. Its device time is still the 10 ms its operations ran."""
    with open(TRACE) as f:
        text = f.read()
    ms = 1_000_000_000  # ps
    for old, new in [
        ("offset_ps: 12000000000 duration_ps: 10000000000",     # the program
         "offset_ps: 12000000000 duration_ps: %d" % (110 * ms)),
        ("offset_ps: 17000000000 duration_ps: 2000000000",      # its collective
         "offset_ps: %d duration_ps: 2000000000" % (117 * ms)),
        ("offset_ps: 19000000000 duration_ps: 3000000000",      # its kernel
         "offset_ps: %d duration_ps: 3000000000" % (119 * ms)),
    ]:
        assert text.count(old) == 1, old
        text = text.replace(old, new)
    for t in (24, 29, 31):  # the third step, program and operations, 100 ms on
        text = text.replace("offset_ps: %d " % (t * ms), "offset_ps: %d " % ((t + 100) * ms))
    path = tmp_path / "stalled.textproto"
    path.write_text(text)
    r = rt.reduce(str(path))
    assert r["steps"] == 3
    assert r["step_busy_ms_median"] == pytest.approx(10.0)
    # 10 + 11 + 10: the stalled step's collective hides behind nothing now
    assert r["step_busy_s_total"] == pytest.approx(0.031)
    assert r["step_gap_ms_median"] == pytest.approx(2.0)
    assert r["busy_s"] == pytest.approx(0.031)
    assert r["window_s"] == pytest.approx(0.134)
    assert r["collective_exposed_s"] == pytest.approx(0.004)
    # the wait is idle time, found under no host span
    assert dict(r["breakdown"]["idle_gaps"])["no_host_span"] == pytest.approx(0.101)


def test_the_window_ends_when_the_harness_stopped_the_trace():
    """With the stop on the wall clock the tail after the last step (the
    epoch's sync, the callback, a save) is inside the window."""
    start = 1_000_000_000_000
    # the harness's span over the epoch boundary, timed from outside, and the
    # program's own over the part of the save it times
    events = [
        {"ph": "X", "name": "bench:epoch_boundary", "ts": (start + 34e6) / 1e3, "dur": 6e3},
        {"ph": "X", "name": "ckpt_save", "ts": (start + 38e6) / 1e3, "dur": 2e3},
    ]
    r = rt.reduce(TRACE, window_ns=[start - 5_000_000, start + 40_000_000],
                  tracer_events=events)
    assert r["steps"] == 3
    assert r["window_s"] == pytest.approx(0.040)  # first step's start -> the stop
    assert r["busy_s"] == pytest.approx(0.030)
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps["bench:epoch_boundary"] == pytest.approx(0.006) and "ckpt_save" not in gaps


def test_the_schedule_times_the_epoch_boundaries_on_the_wall_clock():
    import time

    from benchmark import harness

    schedule = harness.Schedule({"steps_per_epoch": 30}, pool=[], seconds=1)
    now = time.monotonic()
    schedule.epochs = [
        {"t_epoch_end_return": now - 2.0, "t_next": now - 1.7},
        {"t_epoch_end_return": now - 0.5},          # the epoch fit left from
    ]
    (event,) = schedule.boundary_events()
    assert event["name"].startswith(rt.HOST_PREFIX) and event["ph"] == "X"
    assert event["dur"] == pytest.approx(0.3e6)
    assert event["ts"] / 1e6 == pytest.approx(time.time() - 2.0, abs=0.05)


def test_the_trace_readers_on_the_recorded_trace():
    """Each reader of a device-trace metric, on the reduced recorded trace."""
    import types

    from benchmark import run as bench_run

    finder = bench_run.Finder(os.path.join(bench_run.ROOT, "BENCHMARK.json"))
    family = types.SimpleNamespace(
        TRACE_KERNELS=("%flash_kernel.", " custom-call("),
        kernel_flops=lambda config, sequences: 9e9 * sequences,
    )
    run = types.SimpleNamespace(
        trace=rt.reduce(TRACE), chips=4, family=family,
        peaks={"bf16_flops_per_s": 1e13},
        config={"train": {"batch_per_chip": 2}},
    )

    def read(name):
        return finder.module("layer_metrics", name).read(run)

    assert read("step_device_ms") == pytest.approx(10.0)
    assert read("step_gap_ms") == pytest.approx(2.0)
    assert read("collective_exposed_share") == pytest.approx(10.0)   # 3 of 30 ms
    assert read("attn_kernel_share") == pytest.approx(30.0)          # 9 of 30 ms
    # 3 steps x 2 sequences x 9 GFLOP at 10 TFLOP/s is 5.4 ms; the kernels took 9
    assert read("attn_kernel_roofline") == pytest.approx(60.0)
    run.chips = 1          # one chip has no collective to expose
    assert read("collective_exposed_share") is None
    run.family = types.SimpleNamespace()   # a family that names no kernel
    assert read("attn_kernel_share") is None and read("attn_kernel_roofline") is None
    run.trace = None       # an untraced run
    assert read("step_device_ms") is None and read("step_gap_ms") is None


def test_a_trace_without_a_device_plane_is_refused(tmp_path):
    path = tmp_path / "host_only.textproto"
    path.write_text('planes { id: 1 name: "/host:CPU" }\n')
    with pytest.raises(rt.NoDevicePlane):
        rt.reduce(str(path))
