"""Distributed causal tracing: context propagation, op roots, stitching,
critical-path extraction, the repl-unacked-bytes loss-window gauge, and
the tracing-overhead bench harness.

Covers DESIGN.md "Distributed tracing": the ``tc`` wire field round-trip
(client inject -> server child span), deterministic operation trace ids,
flight-record stamping, ``obs/tracepath``'s stitch/critical-path/goodput
cross-check, the ``edl-trace`` CLI, and the ``critical_path_traced``
chaos invariant's red/green behavior on synthetic evidence.
"""

from __future__ import annotations

import json
import os
import time

import pytest

from edl_tpu.obs import events as obs_events
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import trace as obs_trace
from edl_tpu.obs import tracepath
from edl_tpu.rpc import wire


@pytest.fixture(autouse=True)
def _clean_trace_state():
    """Every test starts disarmed with no live context and ends the
    same way — tracing state is process-global by design."""
    armed = obs_trace.PROPAGATION.armed
    obs_trace.reset_context()
    yield
    obs_trace.PROPAGATION.armed = armed
    obs_trace.reset_context()
    obs_events.reset()


# -- context & wire round-trip -------------------------------------------------


class TestTraceContext:
    def test_op_ids_are_deterministic_across_processes(self):
        a = obs_trace.op_context("restage", "stage-token-1")
        b = obs_trace.op_context("restage", "stage-token-1")
        assert a == b
        assert a.trace_id != obs_trace.op_context("restage", "stage-2").trace_id
        assert a.trace_id != obs_trace.op_context("drain", "stage-token-1").trace_id
        # the root span id derives from the trace id: segments can parent
        # to a root nobody recorded yet
        assert a.span_id == obs_trace.op_root_id(a.trace_id)

    def test_wire_roundtrip(self):
        ctx = obs_trace.TraceContext("aaaa", "bbbb")
        frame = wire.pack_frame({"i": 1, "m": "put", "tc": ctx.wire()})
        (req,) = wire.FrameReader().feed(frame)
        assert obs_trace.context_from_wire(req["tc"]) == ctx

    @pytest.mark.parametrize(
        "bad", [None, [], ["only-one"], 7, "str", [1, None], ["", ""],
                ["x" * 100, "y"]],
    )
    def test_malformed_tc_degrades_to_none(self, bad):
        assert obs_trace.context_from_wire(bad) is None

    def test_inject_needs_a_live_context(self):
        assert obs_trace.inject() is None
        obs_trace.begin_process_op("restage", "s1")
        assert obs_trace.inject() == obs_trace.op_context("restage", "s1").wire()
        obs_trace.end_process_op()
        assert obs_trace.inject() is None

    def test_begin_process_op_idempotent_per_key(self):
        c1 = obs_trace.begin_process_op("restage", "s1")
        c2 = obs_trace.begin_process_op("restage", "s1")
        assert c1 is c2
        c3 = obs_trace.begin_process_op("restage", "s2")
        assert c3.trace_id != c1.trace_id

    def test_child_span_nests_and_links(self):
        obs_trace.PROPAGATION.armed = True
        obs_trace.begin_process_op("restage", "nest-stage")
        root = obs_trace.op_context("restage", "nest-stage")
        with obs_trace.child_span("outer") as outer:
            assert obs_trace.current() == outer
            with obs_trace.child_span("inner") as inner:
                assert inner.trace_id == root.trace_id
        tracer = obs_trace.get_tracer()
        spans = {
            e["name"]: e["args"]
            for e in tracer.to_events()
            if e.get("ph") == "X" and "args" in e
        }
        assert spans["inner"]["parent_id"] == outer.span_id
        assert spans["outer"]["parent_id"] == root.span_id
        assert spans["outer"]["trace_id"] == root.trace_id

    def test_record_auto_links_under_op_when_armed(self):
        obs_trace.PROPAGATION.armed = True
        ctx = obs_trace.begin_process_op("restage", "auto-stage")
        tracer = obs_trace.get_tracer()
        tracer.record("ckpt_restore", time.monotonic(), 0.01, step=3)
        ev = [
            e for e in tracer.to_events()
            if e.get("ph") == "X" and e.get("name") == "ckpt_restore"
            and (e.get("args") or {}).get("trace_id") == ctx.trace_id
        ]
        assert ev, "span under a live op must auto-link"
        assert ev[-1]["args"]["parent_id"] == ctx.span_id
        # disarmed: no linkage noise
        obs_trace.PROPAGATION.armed = False
        tracer.record("ckpt_restore", time.monotonic(), 0.01, step=4)
        last = [
            e for e in tracer.to_events()
            if e.get("ph") == "X" and e.get("name") == "ckpt_restore"
        ][-1]
        assert "trace_id" not in (last.get("args") or {})

    def test_propagation_arming_follows_env(self, monkeypatch):
        monkeypatch.delenv("EDL_TRACE_DIR", raising=False)
        monkeypatch.delenv("EDL_TRACE_PROPAGATE", raising=False)
        assert obs_trace.PROPAGATION.rearm() is False
        monkeypatch.setenv("EDL_TRACE_DIR", "/tmp/x")
        assert obs_trace.PROPAGATION.rearm() is True
        monkeypatch.setenv("EDL_TRACE_PROPAGATE", "0")
        assert obs_trace.PROPAGATION.rearm() is False
        monkeypatch.delenv("EDL_TRACE_DIR")
        monkeypatch.setenv("EDL_TRACE_PROPAGATE", "1")
        assert obs_trace.PROPAGATION.rearm() is True


class TestServerSpan:
    def test_observes_histogram_and_records_child(self):
        obs_trace.PROPAGATION.armed = True
        caller = obs_trace.op_context("restage", "srv-stage")
        before = wire.SERVER_SECONDS.count(method="unit_put", server="test")
        with wire.server_span("unit_put", caller.wire(), server="test"):
            pass
        assert (
            wire.SERVER_SECONDS.count(method="unit_put", server="test")
            == before + 1
        )
        spans = [
            e for e in obs_trace.get_tracer().to_events()
            if e.get("ph") == "X" and e.get("name") == "rpc:unit_put"
        ]
        assert spans and spans[-1]["args"]["parent_id"] == caller.span_id

    def test_malformed_tc_still_times(self):
        obs_trace.PROPAGATION.armed = True
        before = wire.SERVER_SECONDS.count(method="unit_bad", server="test")
        with wire.server_span("unit_bad", ["corrupt"], server="test"):
            pass
        assert (
            wire.SERVER_SECONDS.count(method="unit_bad", server="test")
            == before + 1
        )

    def test_disarmed_records_no_span(self):
        obs_trace.PROPAGATION.armed = False
        with wire.server_span("unit_quiet", ["t", "s"], server="test"):
            pass
        assert not [
            e for e in obs_trace.get_tracer().to_events()
            if e.get("ph") == "X" and e.get("name") == "rpc:unit_quiet"
        ]


class TestFlightStamping:
    def test_record_carries_active_trace_id(self, tmp_path, monkeypatch):
        monkeypatch.setenv("EDL_FLIGHT_DIR", str(tmp_path))
        obs_events.reset()
        obs_trace.PROPAGATION.armed = True
        obs_events.record("plain_event")
        ctx = obs_trace.begin_process_op("restage", "flight-stage")
        obs_events.record("op_event", fsync=True)
        obs_trace.end_process_op()
        obs_events.reset()  # close segments
        rows = {e["event"]: e for e in obs_events.read_segments(str(tmp_path))}
        assert "trace_id" not in rows["plain_event"]
        assert rows["op_event"]["trace_id"] == ctx.trace_id


# -- store client/server e2e ---------------------------------------------------


class TestStorePropagationE2E:
    def test_put_produces_linked_server_span_and_histogram(self):
        from edl_tpu.store.client import StoreClient
        from edl_tpu.store.server import StoreServer

        obs_trace.PROPAGATION.armed = True
        server = StoreServer(host="127.0.0.1", port=0).start()
        client = StoreClient(server.endpoint, timeout=5.0)
        try:
            ctx = obs_trace.begin_process_op("restage", "e2e-stage")
            before = wire.SERVER_SECONDS.count(method="put", server="store")
            client.put("/t/x", b"1")
            assert (
                wire.SERVER_SECONDS.count(method="put", server="store")
                == before + 1
            )
            spans = [
                e for e in obs_trace.get_tracer().to_events()
                if e.get("ph") == "X" and e.get("name") == "rpc:put"
                and (e.get("args") or {}).get("trace_id") == ctx.trace_id
            ]
            assert spans, "server span must join the caller's trace"
            assert spans[-1]["args"]["parent_id"] == ctx.span_id
        finally:
            client.close()
            server.stop()

    def test_disarmed_requests_carry_no_tc(self):
        from edl_tpu.store.client import StoreClient
        from edl_tpu.store.server import StoreServer

        obs_trace.PROPAGATION.armed = False
        obs_trace.begin_process_op("restage", "quiet-stage")
        server = StoreServer(host="127.0.0.1", port=0).start()
        client = StoreClient(server.endpoint, timeout=5.0)
        try:
            client.put("/t/y", b"1")
            spans = [
                e for e in obs_trace.get_tracer().to_events()
                if e.get("ph") == "X" and e.get("name") == "rpc:put"
                and (e.get("args") or {}).get("trace_id")
                == obs_trace.op_context("restage", "quiet-stage").trace_id
            ]
            assert not spans
        finally:
            client.close()
            server.stop()


class TestReplUnackedBytes:
    def test_stream_acks_drain_the_window(self, tmp_path):
        from edl_tpu.store.client import StoreClient
        from edl_tpu.store.server import StoreServer

        primary = StoreServer(
            host="127.0.0.1", port=0, data_dir=str(tmp_path / "p")
        ).start()
        standby = StoreServer(
            host="127.0.0.1", port=0, data_dir=str(tmp_path / "s"),
            follow=primary.endpoint, failover_grace=5.0,
        ).start()
        client = StoreClient(primary.endpoint, timeout=5.0)
        try:
            deadline = time.time() + 20
            while time.time() < deadline and not standby._has_state:
                time.sleep(0.05)
            assert standby._has_state, "standby never bootstrapped"
            for i in range(25):
                client.put("/unacked/%02d" % i, b"v" * 128)
            # acks are cumulative echoes riding the repl link: the
            # streamed-but-unacked window must drain back to zero
            deadline = time.time() + 10
            while time.time() < deadline and primary._repl_unacked_bytes() > 0:
                time.sleep(0.05)
            assert primary._repl_unacked_bytes() == 0.0
            subs = [c for c in primary._conns.values() if c.repl]
            assert subs and subs[0].repl_ack > 0
            assert subs[0].repl_tx == subs[0].repl_ack
        finally:
            client.close()
            standby.stop()
            primary.stop()


# -- tracepath: stitching + critical path -------------------------------------


def _write_trace(path, component, pid, spans):
    """A synthetic per-process export in the tracer's format: spans are
    (name, t0_s, dur_s, args)."""
    events = [
        {"name": "process_name", "ph": "M", "pid": pid,
         "args": {"name": component}}
    ]
    for name, t0, dur, args in spans:
        events.append(
            {"name": name, "ph": "X", "ts": t0 * 1e6, "dur": dur * 1e6,
             "pid": pid, "tid": 1, "args": args}
        )
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)


def _synthetic_restage(tmp_path, base=1000.0, with_worker=True,
                       orphan=False, boot_gap=0.0, boot_parts=False):
    """A launcher + worker restage trace as two export files; returns
    the op context. ``boot_gap``: ``worker_boot`` ends that much before
    ``ckpt_restore`` starts (the TPU runtime's start, untraced);
    ``boot_parts``: the worker also took ``process_boot`` under
    ``worker_boot`` and ``backend_init`` over that gap."""
    ctx = obs_trace.op_context("restage", "synt-stage")
    root = ctx.span_id

    def seg(i):
        return "s%02d" % i

    _write_trace(
        tmp_path / "launcher-100.trace.json", "launcher", 100,
        [
            ("op:restage", base, 0.0,
             {"trace_id": ctx.trace_id, "span_id": root, "root": True,
              "op": "restage", "op_key": "synt-stage", "cause": "death"}),
            ("publish", base + 0.1, 0.05,
             {"trace_id": ctx.trace_id, "span_id": seg(1),
              "parent_id": root, "op": "restage"}),
            ("spawn_workers", base + 0.2, 0.1,
             {"trace_id": ctx.trace_id, "span_id": seg(2),
              "parent_id": root, "op": "restage"}),
        ],
    )
    if with_worker:
        _write_trace(
            tmp_path / "worker-0-200.trace.json", "worker-0", 200,
            [
                ("worker_boot", base + 0.4, 1.0 - boot_gap,
                 {"trace_id": ctx.trace_id, "span_id": seg(3),
                  "parent_id": root}),
            ] + [
                ("process_boot", base + 0.5, 0.3,
                 {"trace_id": ctx.trace_id, "span_id": seg(6),
                  "parent_id": seg(3), "modules": 700, "jax_loaded": True}),
                ("backend_init", base + 1.4 - boot_gap, boot_gap,
                 {"trace_id": ctx.trace_id, "span_id": seg(7),
                  "parent_id": root, "platform": "tpu", "devices": 4}),
            ] * boot_parts + [
                ("ckpt_restore", base + 1.4, 0.4,
                 {"trace_id": ctx.trace_id, "span_id": seg(4),
                  "parent_id": root}),
                ("first_step", base + 1.8, 0.2,
                 {"trace_id": ctx.trace_id, "span_id": seg(5),
                  "parent_id": (seg(99) if orphan else root)}),
            ],
        )
    return ctx


class TestTracepath:
    def test_stitch_and_critical_path(self, tmp_path):
        ctx = _synthetic_restage(tmp_path)
        ops = tracepath.extract_ops(tracepath.load_run(str(tmp_path)))
        assert len(ops) == 1
        ot = ops[0]
        assert ot.op == "restage"
        assert ot.trace_id == ctx.trace_id
        assert ot.complete
        assert not ot.orphans
        assert ot.processes == ["launcher", "worker-0"]
        path = tracepath.critical_path(ot)
        names = [p.segment.name for p in path if p.segment is not None]
        assert names == [
            "publish", "spawn_workers", "worker_boot", "ckpt_restore",
            "first_step",
        ]
        # gaps are explicit: before publish, publish->spawn, spawn->boot
        gaps = [round(p.dur, 3) for p in path if p.segment is None]
        assert gaps == [0.1, 0.05, 0.1]
        assert tracepath.covered_seconds(path) == pytest.approx(1.75, abs=1e-6)

    def test_a_workers_boot_in_parts(self, tmp_path):
        """``process_boot`` is named inside ``worker_boot``'s interval and
        ``backend_init`` where the runtime's start was an untraced gap; the
        path covers no less for them."""
        bare, parts = tmp_path / "bare", tmp_path / "parts"
        covered = {}
        for run_dir, boot_parts in ((bare, False), (parts, True)):
            run_dir.mkdir()
            _synthetic_restage(run_dir, boot_gap=0.25, boot_parts=boot_parts)
            (ot,) = tracepath.extract_ops(tracepath.load_run(str(run_dir)))
            assert not ot.orphans
            path = tracepath.critical_path(ot)
            covered[boot_parts] = tracepath.covered_seconds(path)
            slices = [
                (p.segment.name if p.segment else None, round(p.dur, 3))
                for p in path if p.t0 >= 1000.4 - 1e-9
            ]
            if boot_parts:
                assert slices == [
                    ("worker_boot", 0.1), ("process_boot", 0.3),
                    ("worker_boot", 0.35), ("backend_init", 0.25),
                    ("ckpt_restore", 0.4), ("first_step", 0.2),
                ]
            else:
                assert slices == [
                    ("worker_boot", 0.75), (None, 0.25),
                    ("ckpt_restore", 0.4), ("first_step", 0.2),
                ]
        assert covered[True] == pytest.approx(covered[False] + 0.25, abs=1e-6)
        assert covered[False] == pytest.approx(1.5, abs=1e-6)

    def test_orphan_detection(self, tmp_path):
        _synthetic_restage(tmp_path, orphan=True)
        (ot,) = tracepath.extract_ops(tracepath.load_run(str(tmp_path)))
        assert [s.name for s in ot.orphans] == ["first_step"]

    def test_deepest_segment_wins(self, tmp_path):
        ctx = obs_trace.op_context("restage", "depth-stage")
        root = ctx.span_id
        _write_trace(
            tmp_path / "worker-0-300.trace.json", "worker-0", 300,
            [
                ("op:restage", 0.0, 0.0,
                 {"trace_id": ctx.trace_id, "span_id": root, "root": True,
                  "op": "restage", "op_key": "depth-stage"}),
                ("outer", 10.0, 4.0,
                 {"trace_id": ctx.trace_id, "span_id": "o1",
                  "parent_id": root}),
                ("inner", 11.0, 1.0,
                 {"trace_id": ctx.trace_id, "span_id": "i1",
                  "parent_id": "o1"}),
            ],
        )
        (ot,) = tracepath.extract_ops(tracepath.load_run(str(tmp_path)))
        path = tracepath.critical_path(ot)
        assert [
            (p.segment.name, round(p.dur, 3))
            for p in path if p.segment is not None
        ] == [("outer", 1.0), ("inner", 1.0), ("outer", 2.0)]

    def test_root_recovered_when_never_exported(self, tmp_path):
        # the drain-trigger process died before its export: segments
        # still stitch via the dominant unresolved parent
        _synthetic_restage(tmp_path)
        os.unlink(tmp_path / "launcher-100.trace.json")
        (ot,) = tracepath.extract_ops(tracepath.load_run(str(tmp_path)))
        assert ot.root_id == obs_trace.op_root_id(ot.trace_id)
        assert not ot.orphans
        assert ot.complete

    def test_goodput_compare_unions_matched_lanes(self, tmp_path):
        ctx = _synthetic_restage(tmp_path, base=1000.0)
        # worker-0 pid 200 goodput lane: restage 1000.4 -> 1001.8, then
        # train; an UNRELATED pid's drain lane must not count
        def tr(ts, comp, pid, state, prev, dur):
            return {
                "ts": ts, "event": "goodput", "component": comp, "pid": pid,
                "state": state, "prev": prev, "dur": dur,
            }

        flight = [
            tr(1000.4, "worker-0", 200, "restage", None, 0.0),
            tr(1001.8, "worker-0", 200, "train", "restage", 1.4),
            tr(1002.5, "worker-0", 200, None, "train", 0.7),
            # an UNRELATED incarnation (same component, other pid)
            # training through the window: if lane matching were not
            # pid-exact, its productive slices would zero the lane
            tr(1000.0, "worker-0", 999, "train", None, 0.0),
            tr(1002.0, "worker-0", 999, None, "train", 2.0),
        ]
        (ot,) = tracepath.extract_ops(tracepath.load_run(str(tmp_path)))
        cmp = tracepath.goodput_compare(ot, flight)
        assert cmp is not None
        # window ends at first_step start (1001.8); worker 200 trains
        # only FROM 1001.8, so the whole window is restage lane — and
        # pid 999's unrelated drain lane must not have shrunk it
        assert cmp["window_s"] == pytest.approx(1.8, abs=1e-6)
        assert cmp["lane_s"] == pytest.approx(1.8, abs=1e-6)
        # path covered in-window: publish .05 + spawn .1 + boot 1.0 +
        # restore .4
        assert cmp["path_s"] == pytest.approx(1.55, abs=1e-6)


class TestCriticalPathInvariant:
    def _flight(self, base):
        return [
            {"ts": base + 0.4, "event": "goodput", "component": "worker-0",
             "pid": 200, "state": "restage", "prev": None, "dur": 0.0},
            {"ts": base + 1.8, "event": "goodput", "component": "worker-0",
             "pid": 200, "state": "train", "prev": "restage", "dur": 1.4},
            {"ts": base + 2.5, "event": "goodput", "component": "worker-0",
             "pid": 200, "state": None, "prev": "train", "dur": 0.7},
        ]

    def test_green_on_stitched_restage(self, tmp_path):
        from edl_tpu.chaos import invariants as inv

        _synthetic_restage(tmp_path, base=1000.0)
        res = inv.critical_path_traced(
            tracepath.load_run(str(tmp_path)), self._flight(1000.0)
        )
        assert res.ok, res.detail

    def test_red_without_worker_segments(self, tmp_path):
        from edl_tpu.chaos import invariants as inv

        _synthetic_restage(tmp_path, with_worker=False)
        res = inv.critical_path_traced(
            tracepath.load_run(str(tmp_path)), self._flight(1000.0)
        )
        assert not res.ok
        assert "no completed restage" in res.detail

    def test_red_on_orphans(self, tmp_path):
        from edl_tpu.chaos import invariants as inv

        _synthetic_restage(tmp_path, orphan=True)
        res = inv.critical_path_traced(
            tracepath.load_run(str(tmp_path)), self._flight(1000.0)
        )
        assert not res.ok
        assert "orphan" in res.detail

    def test_red_when_path_disagrees_with_ledger(self, tmp_path):
        from edl_tpu.chaos import invariants as inv

        _synthetic_restage(tmp_path, base=1000.0)
        # the ledger says the worker trained the whole window: the
        # trace's 1.55s of claimed restage work has no lane backing it
        flight = [
            {"ts": 1000.0, "event": "goodput", "component": "worker-0",
             "pid": 200, "state": "train", "prev": None, "dur": 0.0},
            {"ts": 1002.5, "event": "goodput", "component": "worker-0",
             "pid": 200, "state": None, "prev": "train", "dur": 2.5},
        ]
        res = inv.critical_path_traced(
            tracepath.load_run(str(tmp_path)), flight
        )
        assert not res.ok
        assert "bound" in res.detail


# -- CLI + bench --------------------------------------------------------------


class TestCli:
    def test_edl_trace_human_and_json(self, tmp_path, capsys):
        from tools import edl_trace

        _synthetic_restage(tmp_path)
        assert edl_trace.main([str(tmp_path), "--op", "restage"]) == 0
        out = capsys.readouterr().out
        assert "op=restage" in out
        assert "worker_boot" in out
        assert "first_step" in out
        assert "(untraced gap)" in out
        assert edl_trace.main([str(tmp_path), "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["ops"][0]["op"] == "restage"
        assert doc["ops"][0]["complete"] is True
        assert edl_trace.main([str(tmp_path), "--list"]) == 0
        assert "complete" in capsys.readouterr().out

    def test_edl_trace_prints_a_workers_boot_in_parts(self, tmp_path, capsys):
        from tools import edl_trace

        _synthetic_restage(tmp_path, boot_gap=0.25, boot_parts=True)
        assert edl_trace.main([str(tmp_path), "--op", "restage"]) == 0
        out = capsys.readouterr().out
        names = [ln.split()[3] for ln in out.splitlines()
                 if ln.split()[2:3] == ["worker-0"]]
        assert names[:4] == [
            "worker_boot", "process_boot", "worker_boot", "backend_init"]
        assert "platform=tpu" in out and "modules=700" in out
        assert edl_trace.main([str(tmp_path), "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert {"process_boot", "backend_init"} <= {
            s["name"] for s in doc["ops"][0]["path"] if s.get("name")
        }

    def test_edl_trace_empty_dir(self, tmp_path, capsys):
        from tools import edl_trace

        assert edl_trace.main([str(tmp_path)]) == 2
        assert "no linked spans" in capsys.readouterr().err

    def test_edl_trace_module_entry(self, tmp_path):
        import subprocess
        import sys

        _synthetic_restage(tmp_path)
        proc = subprocess.run(
            [sys.executable, "-m", "tools.edl_trace", str(tmp_path),
             "--op", "restage"],
            capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert proc.returncode == 0, proc.stderr
        assert "critical path" in proc.stdout

    def test_trace_bench_shape(self):
        from tools import trace_bench

        doc = trace_bench.run(frames=400)
        assert set(doc["fps"]) == {
            "baseline", "disarmed", "armed_no_ctx", "armed_ctx",
        }
        assert all(v > 0 for v in doc["fps"].values())
        assert "propagation_toggle_pct" in doc
        # the bench must leave global tracing state as it found it
        assert obs_trace.current() is None

    def test_checked_in_bench_results(self):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        path = os.path.join(
            root, "bench_results", "trace_overhead_cpu_r10.json"
        )
        with open(path) as f:
            doc = json.load(f)
        assert doc["bench"] == "trace_overhead"
        # the contractual number: the propagation toggle is noise-level
        assert abs(doc["propagation_toggle_pct"]) < 15.0
