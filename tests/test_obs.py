"""Observability layer: registry, rendering, endpoints, spans, edl-top.

Tier-1 (no jax): the obs plane is pure control-plane code. Covers

- counter/gauge/histogram semantics + the naming convention,
- Prometheus text rendering,
- /metrics + /healthz over a real socket (including the store server's
  own mount — the acceptance path: ``curl /metrics`` must return
  ``edl_store_requests_total``),
- span export + cross-process trace merge,
- the WorkerMeter ``__init__`` regression and monotonic interval math,
- telemetry.collect() malformed-key counting,
- tools/edl_top.py --once against a live store,
- the repo-wide metric-name lint.
"""

import json
import os
import pathlib
import re
import subprocess
import sys
import time
import urllib.request

import pytest

sys.path.insert(
    0,
    os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"
    ),
)

from edl_tpu.obs import http as obs_http
from edl_tpu.obs import merge as obs_merge
from edl_tpu.obs.metrics import (
    METRIC_NAME_RE,
    MetricsRegistry,
    default_registry,
)
from edl_tpu.obs.trace import SpanTracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- registry semantics ------------------------------------------------------


class TestRegistry:
    def test_counter_inc_and_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("edl_t_requests_total", "help text")
        c.inc()
        c.inc(2)
        c.inc(5, method="put")
        assert c.value() == 3
        assert c.value(method="put") == 5
        bound = c.labels(method="put")
        bound.inc(2)
        assert c.value(method="put") == 7

    def test_counter_cannot_decrease(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("edl_t_neg_total").inc(-1)

    def test_gauge_set_inc_and_fn(self):
        reg = MetricsRegistry()
        g = reg.gauge("edl_t_queue_depth")
        g.set(4)
        g.inc()
        g.dec(2)
        assert g.value() == 3
        g2 = reg.gauge("edl_t_live_depth").set_fn(lambda: 42)
        assert g2.value() == 42

    def test_gauge_fn_failure_degrades(self):
        reg = MetricsRegistry()
        reg.gauge("edl_t_dead_depth").set_fn(lambda: 1 / 0)
        assert "edl_t_dead_depth" in reg.render()  # no raise

    def test_gauge_clear_fn_identity_guarded(self):
        reg = MetricsRegistry()
        g = reg.gauge("edl_t_owned_depth")
        old_owner = lambda: 1  # noqa: E731
        new_owner = lambda: 2  # noqa: E731
        g.set_fn(old_owner)
        g.set_fn(new_owner)  # replacement instance rebinds
        g.clear_fn(old_owner)  # stopping OLD owner must not strip NEW
        assert g.value() == 2
        g.clear_fn(new_owner)
        assert g.value() == 0

    def test_histogram_buckets_sum_count(self):
        reg = MetricsRegistry()
        h = reg.histogram("edl_t_rpc_seconds", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5, 5.0):
            h.observe(v)
        assert h.count() == 4
        assert h.sum() == pytest.approx(5.555)
        text = reg.render()
        assert 'edl_t_rpc_seconds_bucket{le="0.01"} 1' in text
        assert 'edl_t_rpc_seconds_bucket{le="0.1"} 2' in text
        assert 'edl_t_rpc_seconds_bucket{le="1"} 3' in text
        assert 'edl_t_rpc_seconds_bucket{le="+Inf"} 4' in text
        assert "edl_t_rpc_seconds_count 4" in text

    def test_histogram_timer(self):
        reg = MetricsRegistry()
        h = reg.histogram("edl_t_block_seconds")
        with h.time():
            time.sleep(0.01)
        assert h.count() == 1
        assert 0.005 < h.sum() < 5.0

    def test_get_or_create_and_type_conflict(self):
        reg = MetricsRegistry()
        a = reg.counter("edl_t_same_total")
        b = reg.counter("edl_t_same_total")
        assert a is b
        with pytest.raises(ValueError):
            reg.gauge("edl_t_same_total")

    def test_name_validation(self):
        reg = MetricsRegistry()
        for bad in ("requests_total", "edl_x", "edl_Bad_name_total", "edl__x_y"):
            with pytest.raises(ValueError):
                reg.counter(bad)
        reg.counter("edl_store_requests_total")  # the canonical good name


# -- Prometheus text rendering ----------------------------------------------


class TestRender:
    def test_help_type_and_label_escaping(self):
        reg = MetricsRegistry()
        c = reg.counter("edl_t_esc_total", "multi\nline help")
        c.inc(1, path='a"b\\c')
        text = reg.render()
        assert "# HELP edl_t_esc_total multi line help" in text
        assert "# TYPE edl_t_esc_total counter" in text
        assert 'path="a\\"b\\\\c"' in text
        assert text.endswith("\n")

    def test_non_finite_values_render_prometheus_spellings(self):
        reg = MetricsRegistry()
        g = reg.gauge("edl_t_inf_depth")
        g.set(float("inf"))
        h = reg.histogram("edl_t_inf_seconds", buckets=(1.0,))
        h.observe(float("nan"))
        text = reg.render()  # one poisoned value must not break the scrape
        assert "edl_t_inf_depth +Inf" in text
        assert "edl_t_inf_seconds_sum NaN" in text

    def test_unobserved_instruments_render_zero(self):
        reg = MetricsRegistry()
        reg.counter("edl_t_zero_total")
        reg.gauge("edl_t_zero_depth")
        text = reg.render()
        assert "edl_t_zero_total 0" in text
        assert "edl_t_zero_depth 0" in text

    def test_snapshot_scalars(self):
        reg = MetricsRegistry()
        reg.counter("edl_t_snap_total").inc(3)
        reg.histogram("edl_t_snap_seconds").observe(0.5)
        snap = reg.snapshot()
        assert snap["edl_t_snap_total"][""] == 3
        assert snap["edl_t_snap_seconds"]["count"] == 1


# -- HTTP endpoints over a real socket --------------------------------------


def _get(url, timeout=5.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.headers.get("Content-Type", ""), resp.read()


class TestHttp:
    def test_metrics_and_healthz(self):
        reg = MetricsRegistry()
        reg.counter("edl_t_http_total").inc(7)
        server = obs_http.ObsServer(
            "unittest", host="127.0.0.1", port=0, registry=reg,
            health_fn=lambda: {"stage": "abc"},
        ).start()
        try:
            status, ctype, body = _get(
                "http://127.0.0.1:%d/metrics" % server.port
            )
            assert status == 200
            assert ctype.startswith("text/plain")
            assert b"edl_t_http_total 7" in body

            status, ctype, body = _get(
                "http://127.0.0.1:%d/healthz" % server.port
            )
            assert status == 200
            doc = json.loads(body)
            assert doc["status"] == "ok"
            assert doc["component"] == "unittest"
            assert doc["stage"] == "abc"
            assert doc["pid"] == os.getpid()
            assert doc["uptime_s"] >= 0

            with pytest.raises(urllib.error.HTTPError):
                _get("http://127.0.0.1:%d/nope" % server.port)
        finally:
            server.stop()

    def test_health_fn_failure_degrades_not_500(self):
        server = obs_http.ObsServer(
            "sick", host="127.0.0.1", port=0, registry=MetricsRegistry(),
            health_fn=lambda: 1 / 0,
        ).start()
        try:
            status, _, body = _get("http://127.0.0.1:%d/healthz" % server.port)
            assert status == 200
            assert json.loads(body)["status"] == "degraded"
        finally:
            server.stop()

    def test_start_from_env_gating(self, monkeypatch):
        monkeypatch.delenv("EDL_OBS_PORT", raising=False)
        assert obs_http.start_from_env("gated") is None
        monkeypatch.setenv("EDL_OBS_PORT", "off")
        assert obs_http.start_from_env("gated") is None
        monkeypatch.setenv("EDL_OBS_PORT", "0")
        try:
            a = obs_http.start_from_env("gated", health_fn=lambda: {"gen": 1})
            b = obs_http.start_from_env("gated", health_fn=lambda: {"gen": 2})
            assert a is not None and a is b  # idempotent per component
            # an in-process replacement rebinds health (no frozen /healthz)
            assert a.health()["gen"] == 2
        finally:
            obs_http.stop_all()

    def test_start_from_env_port_overflow_degrades(self, monkeypatch):
        """A port scan reaching past 65535 (OverflowError, not OSError)
        must fall back to an ephemeral port, never crash the workload."""
        monkeypatch.setenv("EDL_OBS_PORT", "65535")
        try:
            server = obs_http.start_from_env("overflow")
            assert server is not None
            assert 0 < server.port <= 65535
        finally:
            obs_http.stop_all()

    def test_release_health_marks_stale(self, monkeypatch):
        monkeypatch.setenv("EDL_OBS_PORT", "0")
        try:
            owner_fn = lambda: {"gen": 1}  # noqa: E731
            server = obs_http.start_from_env("stale", health_fn=owner_fn)
            assert server.health()["status"] == "ok"
            obs_http.release_health("stale", lambda: {})  # wrong owner: no-op
            assert server.health()["status"] == "ok"
            obs_http.release_health("stale", owner_fn)
            doc = server.health()
            assert doc["status"] == "stale"  # monitors see the stop
        finally:
            obs_http.stop_all()

    def test_store_server_mounts_metrics(self, monkeypatch):
        """Acceptance path: curl /metrics on the store server returns
        Prometheus text including edl_store_requests_total."""
        from edl_tpu.store.client import StoreClient
        from edl_tpu.store.server import StoreServer

        monkeypatch.setenv("EDL_OBS_PORT", "0")
        srv = StoreServer(host="127.0.0.1", port=0).start()
        client = None
        try:
            obs = obs_http.start_from_env("store")
            assert obs is not None
            client = StoreClient(srv.endpoint, timeout=5.0)
            client.put("/t/k", b"v")
            assert client.get("/t/k") == b"v"
            # client-controlled method strings must not mint new series
            # (the registry is the process's: count from where an earlier
            # test of this worker left the series)
            def unknown(text):
                found = re.search(
                    r'edl_store_requests_total\{method="<unknown>"\} (\d+)', text
                )
                return int(found.group(1)) if found else 0

            _, _, body = _get("http://127.0.0.1:%d/metrics" % obs.port)
            before = unknown(body.decode())
            for bogus in ("evil1", "evil2"):
                with pytest.raises(Exception):
                    client.request(bogus)
            _, _, body = _get("http://127.0.0.1:%d/metrics" % obs.port)
            text = body.decode()
            assert "edl_store_requests_total" in text
            assert 'method="put"' in text
            # the SERVER counter must not mint a series per bogus method
            # (the client-side roundtrip histogram may: its method labels
            # come from local code, not from the network)
            assert 'edl_store_requests_total{method="evil1"}' not in text
            assert unknown(text) == before + 2
            assert "edl_store_connections_open" in text
            _, _, hbody = _get("http://127.0.0.1:%d/healthz" % obs.port)
            health = json.loads(hbody)
            assert health["component"] == "store"
            assert health["revision"] >= 1
        finally:
            if client is not None:
                client.close()
            srv.stop()
            obs_http.stop_all()


# -- spans + cross-process merge --------------------------------------------


_CHILD_SCRIPT = """
import sys, time
sys.path.insert(0, %(repo)r)
from edl_tpu.obs.trace import SpanTracer
t = SpanTracer(component="child-proc")
with t.span("child_work", k=1):
    time.sleep(0.01)
t.instant("child_marker")
print(t.export(%(path)r))
"""


class TestTrace:
    def test_span_records_bounded(self):
        t = SpanTracer(component="x", maxlen=4)
        for i in range(10):
            with t.span("op", i=i):
                pass
        assert len(t) == 4  # ring buffer bound

    def test_span_error_annotated(self):
        t = SpanTracer(component="x")
        with pytest.raises(RuntimeError):
            with t.span("boom"):
                raise RuntimeError("no")
        events = t.to_events()
        spans = [e for e in events if e.get("ph") == "X"]
        assert spans[0]["args"]["error"] == "RuntimeError"

    def test_export_and_epoch_alignment(self, tmp_path):
        t = SpanTracer(component="exp")
        with t.span("a"):
            time.sleep(0.002)
        path = t.export(str(tmp_path / "exp.trace.json"))
        doc = json.loads(pathlib.Path(path).read_text())
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert spans and spans[0]["dur"] >= 2000  # us
        # epoch anchoring: ts is unix-epoch microseconds, now-ish
        assert abs(spans[0]["ts"] / 1e6 - time.time()) < 60

    def test_export_without_dir_is_noop(self, monkeypatch):
        monkeypatch.delenv("EDL_TRACE_DIR", raising=False)
        assert SpanTracer(component="noop").export() is None

    def test_cross_process_merge(self, tmp_path):
        # parent process trace
        parent = SpanTracer(component="parent-proc")
        with parent.span("parent_work"):
            time.sleep(0.002)
        p1 = parent.export(str(tmp_path / "parent.trace.json"))
        # child process trace (REAL second process)
        p2 = str(tmp_path / "child.trace.json")
        script = _CHILD_SCRIPT % {"repo": REPO, "path": p2}
        out = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 0, out.stderr
        assert os.path.exists(p2)

        merged_path = str(tmp_path / "merged.trace.json")
        rc = obs_merge.main([p1, p2, "-o", merged_path])
        assert rc == 0
        doc = json.loads(pathlib.Path(merged_path).read_text())
        events = doc["traceEvents"]
        span_pids = {e["pid"] for e in events if e.get("ph") == "X"}
        assert len(span_pids) >= 2  # spans from >= 2 processes
        names = {e["name"] for e in events}
        assert {"parent_work", "child_work", "child_marker"} <= names
        # process labels survive the pid remap
        labels = [
            e["args"]["name"] for e in events
            if e.get("ph") == "M" and e.get("name") == "process_name"
        ]
        assert any("parent-proc" in l for l in labels)
        assert any("child-proc" in l for l in labels)
        # rebase: earliest non-meta ts is 0
        tss = [e["ts"] for e in events if e.get("ph") != "M"]
        assert min(tss) == 0

    def test_merge_skips_torn_file(self, tmp_path):
        good = SpanTracer(component="g")
        with good.span("ok"):
            pass
        p1 = good.export(str(tmp_path / "g.trace.json"))
        p2 = tmp_path / "torn.trace.json"
        p2.write_text('{"traceEvents": [tr')  # torn export
        doc = obs_merge.merge_traces([p1, str(p2)])
        assert any(e["name"] == "ok" for e in doc["traceEvents"])

    def test_merge_includes_drained_process_with_closed_spans(self, tmp_path):
        """A worker that exits DRAINED_EXIT=76 mid-trace (the NORMAL end
        of a preemption-noticed stage — atexit may or may not run) still
        yields a merged Chrome trace containing its spans, all closed."""
        from edl_tpu.cluster.contract import DRAINED_EXIT

        script = """
import os, sys, time
sys.path.insert(0, %(repo)r)
from edl_tpu.obs.trace import get_tracer
t = get_tracer("drained-worker")
with t.span("step", i=0):
    time.sleep(0.005)
with t.span("emergency_ckpt"):
    time.sleep(0.005)
t.export()
os._exit(%(exit)d)   # DRAINED_EXIT: no atexit, mid-session
""" % {"repo": REPO, "exit": DRAINED_EXIT}
        env = dict(os.environ, EDL_TRACE_DIR=str(tmp_path))
        out = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True, text=True, timeout=60, env=env,
        )
        assert out.returncode == DRAINED_EXIT, out.stderr
        exported = list(tmp_path.glob("drained-worker-*.trace.json"))
        assert exported, "drained worker left no trace export behind"

        survivor = SpanTracer(component="survivor")
        with survivor.span("keeps_running"):
            time.sleep(0.002)
        p_live = survivor.export(str(tmp_path / "survivor.trace.json"))
        merged = str(tmp_path / "merged.trace.json")
        assert obs_merge.main([p_live, str(exported[0]), "-o", merged]) == 0
        doc = json.loads(pathlib.Path(merged).read_text())
        drained_spans = [
            e for e in doc["traceEvents"]
            if e.get("ph") == "X" and e["name"] in ("step", "emergency_ckpt")
        ]
        assert {e["name"] for e in drained_spans} == {"step", "emergency_ckpt"}
        # "closed": every span is a complete X event with a duration —
        # nothing half-open leaked from the drained process
        assert all(e.get("dur", 0) > 0 for e in drained_spans)
        labels = [
            e["args"]["name"] for e in doc["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"
        ]
        assert any("drained-worker" in l for l in labels)


# -- WorkerMeter regression + collect() drop counting ------------------------


class _Env:
    def __init__(self, endpoint="", job_id="obsjob", stage="stagemeter"):
        self.job_id = job_id
        self.stage = stage
        self.global_rank = 0
        self.world_size = 2
        self.store_endpoint = endpoint


class TestWorkerMeter:
    def test_fields_initialized_in_init(self):
        """Regression: _first_ts/_first_recorded used to be created only
        inside step(), so close()/samples_per_s() on a stepless meter
        relied on getattr defensiveness."""
        from edl_tpu.utils.telemetry import WorkerMeter

        meter = WorkerMeter(_Env(), batch_per_step=8)
        assert meter._first_ts is None
        assert meter._first_recorded is False
        assert meter.samples_per_s() is None
        meter.close()  # no steps, no store: must not raise

    def test_first_step_event_and_meter_roundtrip(self, store):
        from edl_tpu.store.client import StoreClient
        from edl_tpu.utils import telemetry

        client = StoreClient(store.endpoint, timeout=5.0)
        try:
            env = _Env(store.endpoint)
            meter = telemetry.WorkerMeter(
                env, batch_per_step=8, warmup=1, report_every=1, client=client
            )
            meter.step()
            time.sleep(0.02)
            meter.step()
            meter.close()
            data = telemetry.collect(client, env.job_id)
            assert data["dropped"] == 0
            assert "w0" in data["events"][env.stage]["first_step"]
            m = data["metrics"][env.stage]["w0"]
            assert m["sps"] > 0
            assert m["steps"] == 2
            assert m["t1"] >= m["t0"]  # wall timestamps still published
        finally:
            client.close()

    def test_wall_clock_jump_cannot_corrupt_sps(self, store, monkeypatch):
        """An NTP step backwards between steps must not break samples/s
        (interval math is monotonic now)."""
        from edl_tpu.store.client import StoreClient
        from edl_tpu.utils import telemetry

        class _FakeTime:
            def __init__(self):
                self._mono = 1000.0
                self._wall = 5000.0

            def monotonic(self):
                self._mono += 0.05
                return self._mono

            def time(self):
                self._wall -= 3600.0  # violent backwards NTP step
                return self._wall

        monkeypatch.setattr(telemetry, "time", _FakeTime())
        client = StoreClient(store.endpoint, timeout=5.0)
        try:
            env = _Env(store.endpoint, job_id="ntpjob", stage="ntpstage")
            meter = telemetry.WorkerMeter(
                env, batch_per_step=4, warmup=1, report_every=1, client=client
            )
            for _ in range(4):
                meter.step()
            sps = meter.samples_per_s()
            assert sps is not None and sps > 0
        finally:
            client.close()

    def test_collect_counts_malformed_keys(self, store):
        from edl_tpu.store.client import StoreClient
        from edl_tpu.utils import telemetry

        client = StoreClient(store.endpoint, timeout=5.0)
        try:
            job = "corruptjob"
            client.put("/%s/events/stg/first_step.w0" % job, b"12.5")
            client.put("/%s/events/stg/first_step.w1" % job, b"not-a-float")
            client.put("/%s/metrics/stg/w0" % job, b'{"sps": 3}')
            client.put("/%s/metrics/stg/w1" % job, b"{broken json")
            client.put("/%s/stages/stg" % job, b"also broken")
            data = telemetry.collect(client, job)
            assert data["dropped"] == 3
            assert data["events"]["stg"]["first_step"] == {"w0": 12.5}
            assert data["metrics"]["stg"] == {"w0": {"sps": 3}}
        finally:
            client.close()


# -- edl-top -----------------------------------------------------------------


class TestEdlTop:
    def _seed_job(self, client, job):
        from edl_tpu.utils import telemetry

        t = time.time()
        telemetry.record_event(client, job, "stageaaa", "drain", "p1", ts=t - 30)
        telemetry.record_event(client, job, "stageaaa", "published", "p1", ts=t - 29)
        telemetry.record_stage(client, job, "stageaaa", {"world": 2, "ts": t - 29})
        telemetry.record_event(client, job, "stagebbb", "drain", "p1", ts=t - 20)
        telemetry.record_event(client, job, "stagebbb", "published", "p1", ts=t - 19)
        telemetry.record_event(
            client, job, "stagebbb", "first_step", "w0", ts=t - 18
        )
        telemetry.record_stage(client, job, "stagebbb", {"world": 2, "ts": t - 19})
        for rank, sps in ((0, 12.5), (1, 11.75)):
            client.put(
                "/%s/metrics/stagebbb/w%d" % (job, rank),
                json.dumps(
                    {"sps": sps, "steps": 40, "batch": 8,
                     "t0": t - 18, "t1": t - 1, "world": 2}
                ).encode(),
            )

    def test_once_renders_workers_stage_and_endpoints(self, store, capsys):
        from edl_tpu.store.client import StoreClient

        import edl_top

        default_registry().counter(
            "edl_store_requests_total", "store RPCs dispatched, by method"
        ).inc(5, method="put")
        obs = obs_http.ObsServer(
            "store", host="127.0.0.1", port=0,
            health_fn=lambda: {"revision": 1},
        ).start()
        client = StoreClient(store.endpoint, timeout=5.0)
        job = "topjob"
        try:
            self._seed_job(client, job)
            obs_http.register_endpoint(
                client, job, "store", "s0", "127.0.0.1:%d" % obs.port
            )
            rc = edl_top.main(
                ["--store", store.endpoint, "--job", job, "--once"]
            )
            assert rc == 0
            out = capsys.readouterr().out
            assert "stage=stagebbb"[:14] in out
            assert "w0" in out and "12.5" in out
            assert "w1" in out and ("11.8" in out or "11.75" in out)
            assert "store.s0" in out and "ok" in out
            assert "stageaaa"[:8] in out  # transition line
            assert "downtime" in out
        finally:
            client.close()
            obs.stop()

    def test_gather_flags_dropped_telemetry(self, store):
        from edl_tpu.store.client import StoreClient

        import edl_top

        client = StoreClient(store.endpoint, timeout=5.0)
        try:
            client.put("/dropjob/events/s/first_step.w0", b"garbage")
            snap = edl_top.gather(client, "dropjob")
            assert snap["dropped"] == 1
            assert "malformed" in edl_top.render(snap)
        finally:
            client.close()


# -- naming-convention lint ---------------------------------------------------
# Since the edl-lint PR these are thin wrappers over the analyzer passes
# in edl_tpu/analysis/catalogue.py — one AST-based implementation, same
# test names stay green (and the same checks also run via
# `python -m tools.edl_lint` against the committed baseline).


def test_every_registered_metric_name_matches_convention():
    """Every metric registered anywhere in edl_tpu/ follows
    edl_<component>_<name>_<unit> (METRIC_NAME_RE) — enforced by the
    `metric-naming` analyzer pass."""
    from edl_tpu.analysis import (
        collect_metric_registrations, repo_context, run_analysis,
    )

    ctx = repo_context()
    declared = collect_metric_registrations(ctx)
    assert declared, "expected metric registrations under edl_tpu/"
    assert "edl_store_requests_total" in declared
    findings, _ = run_analysis(ctx, only=["metric-naming"])
    assert not findings, "non-conforming metric names:\n" + "\n".join(
        str(f) for f in findings
    )


def test_every_registered_metric_has_a_catalogue_row():
    """Mirror of the fault-point catalogue lint: every metric registered
    at import time anywhere under edl_tpu/ must have a row in DESIGN.md's
    metric catalogue — a metric without documented semantics is a
    dashboard mystery waiting to happen. Enforced by the
    `metric-catalogue` analyzer pass (direct registrations plus
    bind_gauges spec tuples)."""
    from edl_tpu.analysis import (
        collect_metric_registrations, repo_context, run_analysis,
    )

    ctx = repo_context()
    declared = collect_metric_registrations(ctx)
    assert declared, "expected metric registrations under edl_tpu/"
    assert "edl_goodput_seconds_total" in declared  # the goodput plane
    findings, _ = run_analysis(ctx, only=["metric-catalogue"])
    assert not findings, (
        "metrics missing from the DESIGN.md catalogue:\n"
        + "\n".join(str(f) for f in findings)
    )
