"""Profiling plane: cost-model/roofline math, windowed-MFU telemetry,
the memory_stats guard, store-driven capture windows, alert-triggered
auto-capture bounds, the mfu-degraded rule drill, and the CLI.

Tier-1. The capstone is the live 2-pod CPU drill: a real launcher job
running the chaos trainee answers an ``edl-profile --request`` with one
``jax.profiler`` trace artifact and a published ``profile/result/{pod}``
record per pod, within the acceptance bound.
"""

import json
import os
import pathlib
import subprocess
import sys
import time
import types

import jax
import jax.numpy as jnp
import pytest

from edl_tpu.chaos import plane as chaos
from edl_tpu.chaos.scenario import TRAINEE
from edl_tpu.harness.resize import ResizeHarness
from edl_tpu.obs import events as obs_events
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import profile as obs_profile
from edl_tpu.obs.metrics import MetricsRegistry
from edl_tpu.obs.monitor import Monitor, Rule, builtin_rules
from edl_tpu.obs.profile import (
    AutoCapture,
    CaptureController,
    StepTelemetry,
    device_memory_stats,
    hbm_bandwidth,
    peak_flops,
    read_results,
    request_capture,
    roofline,
    step_cost,
)

REPO = pathlib.Path(__file__).resolve().parent.parent

T0 = 1_000_000.0


class FakeDevice:
    """A device stub: ``device_kind`` + a pluggable ``memory_stats``."""

    def __init__(self, kind="cpu", stats="absent"):
        self.device_kind = kind
        self._stats = stats

    def memory_stats(self):
        if self._stats == "absent":
            raise AttributeError("memory_stats")  # older runtimes raise
        return self._stats


# -- the cost model -----------------------------------------------------------


class TestCostModel:
    def test_peak_table_is_ordered_most_specific_first(self):
        # "v5" must not shadow "v5p": the lookup is first-substring-wins
        assert peak_flops("TPU v5p") == 459e12
        assert peak_flops("TPU v5 lite") == 197e12
        assert peak_flops("TPU v4") == 275e12

    def test_unknown_kind_is_none_and_env_overrides(self, monkeypatch):
        assert peak_flops("quantum9000") is None
        assert hbm_bandwidth("quantum9000") is None
        monkeypatch.setenv("EDL_PEAK_FLOPS", "123e12")
        monkeypatch.setenv("EDL_HBM_BW", "456e9")
        assert peak_flops("quantum9000") == 123e12
        assert hbm_bandwidth("quantum9000") == 456e9

    def test_garbage_override_is_ignored(self, monkeypatch):
        monkeypatch.setenv("EDL_PEAK_FLOPS", "not-a-number")
        assert peak_flops("TPU v4") == 275e12

    def test_cpu_nominal_fallback(self):
        # CPU rigs must be able to drive the plumbing: nominal, nonzero
        assert peak_flops("cpu") == obs_profile.CPU_NOMINAL_PEAK_FLOPS
        assert hbm_bandwidth("cpu") == obs_profile.CPU_NOMINAL_HBM_BW

    def test_roofline_compute_vs_memory_bound(self, monkeypatch):
        monkeypatch.setenv("EDL_HBM_BW", "10.0")  # ridge = peak/bw = 10
        compute = roofline({"flops": 100.0, "bytes accessed": 5.0},
                           "chipzilla", peak=100.0)
        assert compute["bound"] == "compute"
        assert compute["arithmetic_intensity"] == 20.0
        assert compute["roofline_mfu_ceiling"] == 1.0
        memory = roofline({"flops": 100.0, "bytes accessed": 20.0},
                          "chipzilla", peak=100.0, mfu=0.25)
        assert memory["bound"] == "memory"
        assert memory["arithmetic_intensity"] == 5.0
        assert memory["roofline_mfu_ceiling"] == 0.5  # ai/ridge = 5/10
        assert memory["mfu_of_ceiling"] == 0.5        # 0.25 of a 0.5 ceiling

    @pytest.mark.parametrize("flops,bound,ceiling", [
        (1e12, "memory", 0.416),  # 100 FLOPs/byte under the ridge below
        (1e13, "compute", 1.0),
    ])
    def test_roofline_on_the_v5e_rows(self, flops, bound, ceiling):
        # the tables' own v5e ridge: 197e12 / 819e9 = 240.5 FLOPs/byte
        got = roofline(
            {"flops": flops, "bytes accessed": 1e10}, "TPU v5e", 197e12
        )
        assert got["bound"] == bound
        assert got["roofline_mfu_ceiling"] == ceiling

    def test_roofline_empty_on_missing_inputs(self):
        assert roofline({}, "TPU v4", peak=275e12) == {}
        assert roofline({"flops": 1.0}, "TPU v4", peak=275e12) == {}
        assert roofline({"flops": 1.0, "bytes accessed": 1.0},
                        "quantum9000", peak=1.0) == {}

    def test_normalize_cost_accepts_list_shape(self):
        # some backends return cost_analysis() as a one-element list
        assert obs_profile.normalize_cost([{"flops": 2.0}]) == {"flops": 2.0}
        assert obs_profile.normalize_cost(None) == {}
        assert obs_profile.normalize_cost([]) == {}

    def test_step_cost_extracts_real_flops(self):
        @jax.jit
        def step(w, x):
            return w @ x

        n = 16
        cost = step_cost(step, jnp.ones((n, n)), jnp.ones((n, n)))
        flops = obs_profile.cost_flops(cost)
        # a matmul's cost must be within 2x of the textbook 2*n^3
        assert flops and 0.5 * 2 * n ** 3 <= flops <= 2 * 2 * n ** 3

    def test_step_cost_failure_degrades_to_empty(self):
        assert step_cost(lambda: None) == {}  # not jitted: no .lower


# -- memory_stats guard -------------------------------------------------------


class TestDeviceMemoryStats:
    def test_absent_method_is_none(self):
        assert device_memory_stats(FakeDevice(stats="absent")) is None

    def test_none_and_non_dict_results_are_none(self):
        assert device_memory_stats(FakeDevice(stats=None)) is None
        assert device_memory_stats(FakeDevice(stats="bogus-string")) is None

    def test_dict_without_either_key_is_none(self):
        assert device_memory_stats(FakeDevice(stats={"num_allocs": 3})) is None

    def test_real_stats_extracted(self):
        dev = FakeDevice(stats={"bytes_in_use": 7, "bytes_limit": 100})
        assert device_memory_stats(dev) == (7.0, 100.0)
        # bytes_reservable_limit is the older spelling of the limit
        dev = FakeDevice(stats={"bytes_in_use": 7, "bytes_reservable_limit": 50})
        assert device_memory_stats(dev) == (7.0, 50.0)

    def test_cpu_backend_device_does_not_crash(self):
        # the real guard: whatever the CPU backend returns, no exception
        device_memory_stats(jax.devices()[0])


# -- live telemetry -----------------------------------------------------------


class TestStepTelemetry:
    def _armed(self, monkeypatch, flops=20.0, stats="absent"):
        monkeypatch.setenv("EDL_PEAK_FLOPS", "100.0")
        monkeypatch.setenv("EDL_HBM_BW", "10.0")
        reg = MetricsRegistry()
        tele = StepTelemetry(registry=reg, window_s=60.0)
        dev = FakeDevice(kind="chipzilla", stats=stats)
        roof = tele.set_cost({"flops": flops, "bytes accessed": 5.0}, device=dev)
        # injected timestamps anchored to real monotonic time: the bound
        # gauge's scrape-time staleness check uses time.monotonic()
        return reg, tele, roof, time.monotonic()

    def test_window_mfu_uses_median_step_time(self, monkeypatch):
        reg, tele, _, t0 = self._armed(monkeypatch)
        assert tele.window_mfu() == 0.0  # no steps yet
        tele.observe_step(dt=0.25, ts=t0)
        assert tele.window_mfu() == 0.0  # one step proves nothing
        for i in range(1, 5):
            tele.observe_step(dt=0.25, ts=t0 + 0.25 * i)
        assert tele.window_mfu() == pytest.approx(20.0 / 0.25 / 100.0)  # 0.8
        # one checkpoint pause must not crater the ratio: median, not span
        tele.observe_step(dt=5.0, ts=t0 + 7.0)
        assert tele.window_mfu() == pytest.approx(0.8)
        tele.close()

    def test_old_steps_age_out_of_the_window(self, monkeypatch):
        _reg, tele, _, t0 = self._armed(monkeypatch)
        for i in range(4):
            tele.observe_step(dt=0.25, ts=t0 + 0.25 * i)
        # 100s later only the new (slower) regime is in the 60s window
        for i in range(4):
            tele.observe_step(dt=1.0, ts=t0 + 100.0 + i)
        assert tele.window_mfu(now=t0 + 103.0) == pytest.approx(20.0 / 1.0 / 100.0)
        tele.close()

    def test_wedged_worker_reads_zero_not_last_healthy_ratio(self, monkeypatch):
        _reg, tele, _, t0 = self._armed(monkeypatch)
        for i in range(4):
            tele.observe_step(dt=0.25, ts=t0 + 0.25 * i)
        assert tele.window_mfu(now=t0 + 1.0) == pytest.approx(0.8)
        # the worker wedges: a scrape past the window must read degraded,
        # not keep exporting the final healthy window forever
        assert tele.window_mfu(now=t0 + 120.0) == 0.0
        tele.close()

    def test_gauges_exported_and_counter_advances(self, monkeypatch):
        reg, tele, roof, t0 = self._armed(monkeypatch)
        assert roof["roofline_mfu_ceiling"] == 0.4  # ai=4, ridge=10
        for i in range(3):
            tele.observe_step(dt=0.25, ts=t0 + 0.25 * i)
        assert reg.get("edl_train_step_flops").value() == 20.0
        assert reg.get("edl_train_mfu_ratio").value() == pytest.approx(0.8)
        assert reg.get("edl_train_roofline_mfu_ceiling").value() == 0.4
        assert reg.get("edl_train_arithmetic_intensity").value() == 4.0
        assert reg.get("edl_train_flops_total").value() == 60.0
        tele.close()

    def test_hbm_gauges_absent_without_memory_stats(self, monkeypatch):
        reg, tele, _, _t0 = self._armed(monkeypatch, stats="absent")
        # the guard satellite: no memory_stats -> the gauges don't exist
        assert reg.get("edl_device_hbm_bytes_in_use") is None
        assert reg.get("edl_device_hbm_bytes_limit") is None
        assert tele.hbm_in_use() is None
        assert "hbm_bytes_in_use" not in tele.snapshot()
        tele.close()

    def test_hbm_gauges_exported_with_memory_stats(self, monkeypatch):
        reg, tele, _, _t0 = self._armed(
            monkeypatch, stats={"bytes_in_use": 9e9, "bytes_limit": 16e9}
        )
        assert reg.get("edl_device_hbm_bytes_in_use").value() == 9e9
        assert reg.get("edl_device_hbm_bytes_limit").value() == 16e9
        assert tele.snapshot()["hbm_bytes_in_use"] == 9e9
        tele.close()

    def test_empty_cost_exports_nothing_but_does_not_crash(self):
        reg = MetricsRegistry()
        tele = StepTelemetry(registry=reg)
        tele.set_cost({}, device=FakeDevice())
        tele.observe_step(dt=0.1, ts=T0)
        assert tele.window_mfu() == 0.0
        assert reg.get("edl_train_mfu_ratio") is None
        assert reg.get("edl_train_flops_total").value() == 0.0
        tele.close()

    def test_close_releases_gauge_closures(self, monkeypatch):
        reg, tele, _, _t0 = self._armed(monkeypatch)
        gauge = reg.get("edl_train_mfu_ratio")
        assert gauge._fn is not None
        tele.close()
        assert gauge._fn is None  # a restaged stage must not leak closures

    def test_rearming_replaces_the_binding(self, monkeypatch):
        reg, tele, _, _t0 = self._armed(monkeypatch)
        tele.set_cost({"flops": 40.0, "bytes accessed": 5.0},
                      device=FakeDevice(kind="chipzilla"))
        assert reg.get("edl_train_step_flops").value() == 40.0
        tele.close()
        assert reg.get("edl_train_step_flops")._fn is None


# -- on-demand capture --------------------------------------------------------


def _toy():
    step = jax.jit(lambda w: w + 1.0)
    return step, jnp.zeros(8, jnp.float32)


class _CtlEnv:
    def __init__(self, store_endpoint="", job_id="", pod_id="podA"):
        self.job_id = job_id
        self.store_endpoint = store_endpoint
        self.pod_id = pod_id
        self.rank_in_pod = 0
        self.global_rank = 0


class TestCaptureController:
    def test_local_window_produces_trace_artifact(self, tmp_path):
        step, w = _toy()
        reg = MetricsRegistry()
        ctl = CaptureController(_CtlEnv(), registry=reg)
        ctl.arm_local(str(tmp_path), start_after=2, steps=2)
        try:
            for _ in range(6):
                w = step(w)
                ctl.on_step(sync=lambda w=w: jax.block_until_ready(w))
        finally:
            ctl.close()
        files = [os.path.join(d, f) for d, _s, fs in os.walk(tmp_path) for f in fs]
        assert files, "no trace artifact written"
        assert reg.get("edl_profile_captures_total").value(trigger="env") == 1
        assert not ctl.tracing

    def test_store_request_honored_once_and_result_published(
        self, store, tmp_path
    ):
        from edl_tpu.store.client import StoreClient

        step, w = _toy()
        tele = StepTelemetry(registry=MetricsRegistry())
        tele.set_cost(step_cost(step, w))
        reg = MetricsRegistry()
        env = _CtlEnv(store.endpoint, "ctljob")
        client = StoreClient(store.endpoint, timeout=5.0)
        ctl = CaptureController(env, telemetry=tele, registry=reg)
        try:
            rid = request_capture(client, "ctljob", steps=2,
                                  out_dir=str(tmp_path))
            deadline = time.time() + 20
            results = {}
            while time.time() < deadline and not results:
                w = step(w)
                tele.observe_step()
                ctl.on_step(sync=lambda w=w: jax.block_until_ready(w))
                results = read_results(client, "ctljob", rid)
                time.sleep(0.02)
            assert set(results) == {"podA"}
            doc = results["podA"]
            assert doc["id"] == rid and doc["steps"] == 2
            assert doc["step_ms"] > 0 and "mfu" in doc
            assert os.path.isdir(doc["dir"]) and os.listdir(doc["dir"])
            captures = reg.get("edl_profile_captures_total")
            assert captures.value(trigger="manual") == 1
            # the same request id again: answered already, never re-run
            request_capture(client, "ctljob", steps=2, request_id=rid,
                            out_dir=str(tmp_path))
            for _ in range(8):
                w = step(w)
                ctl.on_step()
                time.sleep(0.02)
            assert captures.value(trigger="manual") == 1
            assert not ctl.tracing
        finally:
            ctl.close()
            tele.close()
            client.close()

    def test_restaged_worker_seeds_done_ids_from_published_result(
        self, store, tmp_path
    ):
        from edl_tpu.store.client import StoreClient

        client = StoreClient(store.endpoint, timeout=5.0)
        try:
            client.put(
                "/oldjob/profile/result/podA",
                json.dumps({"id": "r1", "steps": 2}).encode(),
            )
            env = _CtlEnv(store.endpoint, "oldjob")
            reg = MetricsRegistry()
            ctl = CaptureController(env, registry=reg)
            try:
                # the standing request this incarnation's predecessor
                # already answered must not re-trigger
                request_capture(client, "oldjob", steps=2, request_id="r1",
                                out_dir=str(tmp_path))
                step, w = _toy()
                for _ in range(10):
                    w = step(w)
                    ctl.on_step()
                    time.sleep(0.02)
                assert not ctl.tracing
                assert reg.get("edl_profile_captures_total").value() == 0
            finally:
                ctl.close()
        finally:
            client.close()

    def test_redelivered_done_request_not_consumed(self):
        # the service watch refires on ANY profile/ key change (e.g. a
        # peer's result publication) and may re-arm a request this
        # worker was still tracing when the event arrived; once the id
        # is in the done-set the stale pending entry must be dropped at
        # consumption time, not traced a second time
        ctl = CaptureController(_CtlEnv())
        ctl._done_ids.add("rX")
        ctl._pending = {"id": "rX", "steps": 1}
        ctl.on_step()
        assert not ctl.tracing
        assert ctl._pending is None  # consumed and discarded, not re-run
        ctl.close()

    def test_exception_in_step_hook_is_contained(self, tmp_path):
        blocker = tmp_path / "not-a-dir"
        blocker.write_text("file where the trace root should go")
        ctl = CaptureController(_CtlEnv())
        # the artifact root is unusable: makedirs fails before start_trace
        ctl.arm_local(str(blocker / "sub"), start_after=0, steps=1)
        ctl.on_step()  # must not raise out of the step loop
        assert not ctl.tracing
        ctl.close()


# -- alert-triggered snapshots ------------------------------------------------


class _PutRecorder:
    def __init__(self, fail=False):
        self.puts = []
        self.fail = fail

    def put(self, key, value):
        if self.fail:
            raise RuntimeError("store down")
        self.puts.append((key, value))


class TestAutoCapture:
    def _rule(self, name="mfu-degraded"):
        return types.SimpleNamespace(name=name)

    def test_cooldown_and_cap(self):
        client = _PutRecorder()
        auto = AutoCapture(client, "j", cooldown_s=10.0, max_captures=2,
                           registry=MetricsRegistry())
        auto(self._rule(), {"ts": T0})
        assert len(client.puts) == 1
        auto(self._rule(), {"ts": T0 + 5})      # inside cooldown: dropped
        assert len(client.puts) == 1
        auto(self._rule(), {"ts": T0 + 15})     # past cooldown: second
        assert len(client.puts) == 2
        auto(self._rule(), {"ts": T0 + 60})     # cap reached: dropped
        assert len(client.puts) == 2
        assert all(k == "/j/profile/request" for k, _v in client.puts)

    def test_request_carries_the_firing_rule_as_reason(self):
        client = _PutRecorder()
        reg = MetricsRegistry()
        auto = AutoCapture(client, "j", cooldown_s=0.0, registry=reg)
        auto(self._rule("goodput-degraded"), {"ts": T0})
        doc = json.loads(client.puts[0][1])
        assert doc["reason"] == "goodput-degraded"
        assert reg.get("edl_monitor_capture_requests_total").value(
            rule="goodput-degraded"
        ) == 1

    def test_unlisted_rule_is_ignored(self):
        client = _PutRecorder()
        auto = AutoCapture(client, "j", registry=MetricsRegistry())
        auto(self._rule("dead-endpoint"), {"ts": T0})
        assert client.puts == []

    def test_store_failure_is_contained_and_spends_no_slot(self):
        client = _PutRecorder(fail=True)
        auto = AutoCapture(client, "j", cooldown_s=10.0, max_captures=1,
                           registry=MetricsRegistry())
        for i in range(3):  # alerts fire exactly when the store is sick:
            auto(self._rule(), {"ts": T0 + i})  # contained, no slot spent
        client.fail = False  # store recovers: the cap is still intact
        auto(self._rule(), {"ts": T0 + 60})
        assert len(client.puts) == 1

    def test_monitor_on_fire_publishes_request(self, store):
        from edl_tpu.store.client import StoreClient

        client = StoreClient(store.endpoint, timeout=5.0)
        mon = Monitor(
            store.endpoint, "firejob", registry=MetricsRegistry(),
            rules=[Rule("gp", metric="edl_goodput_ratio", op="<", value=0.7)],
            on_fire=AutoCapture(client, "firejob", rules=("gp",),
                                cooldown_s=0.0, registry=MetricsRegistry()),
        )
        try:
            mon.ingest("w0", {"edl_goodput_ratio": {"": 0.1}}, ts=time.time())
            out = mon.evaluate()
            assert [t["state"] for t in out] == ["firing"]
            raw = client.get("/firejob/profile/request")
            assert raw and json.loads(raw)["reason"] == "gp"
        finally:
            mon.stop()
            client.close()

    def test_on_fire_exception_does_not_stop_the_sensor(self):
        def bomb(_rule, _doc):
            raise RuntimeError("action exploded")

        mon = Monitor(
            None, "bombjob", registry=MetricsRegistry(),
            rules=[Rule("gp", metric="edl_goodput_ratio", op="<", value=0.7)],
            on_fire=bomb,
        )
        mon.ingest("w0", {"edl_goodput_ratio": {"": 0.1}}, ts=T0)
        out = mon.evaluate(now=T0)
        assert [t["state"] for t in out] == ["firing"]
        mon.stop()


# -- the mfu-degraded rule drill ---------------------------------------------


class TestMfuDegradedRule:
    def _engine(self):
        rule = next(r for r in builtin_rules() if r.name == "mfu-degraded")
        return Monitor(None, "mfujob", rules=[rule],
                       registry=MetricsRegistry(), interval=0.25)

    def _feed(self, mon, value, ts):
        mon.ingest("w0", {"edl_train_flops_total": {"": value}}, ts=ts)
        return mon.evaluate(now=ts)

    def test_red_drill_fires_after_dispatch_collapses(self):
        mon = self._engine()
        ts, v = T0, 0.0
        for _ in range(20):           # healthy: 1e9 FLOPs every 5s
            v += 1e9
            assert self._feed(mon, v, ts) == []
            ts += 5.0
        fired = []
        for _ in range(20):           # the dispatch rate collapses to zero
            fired.extend(self._feed(mon, v, ts))
            ts += 5.0
        assert [t["state"] for t in fired] == ["firing"]
        assert fired[0]["rule"] == "mfu-degraded"
        mon.stop()

    def test_never_dispatched_job_stays_quiet(self):
        # the monitor-clean analog: a job that NEVER dispatched (cost
        # model unavailable, counter flat zero) must not page
        mon = self._engine()
        ts = T0
        for _ in range(40):
            assert self._feed(mon, 0.0, ts) == []
            ts += 5.0
        assert mon.firing() == []
        mon.stop()


# -- live 2-pod e2e drill -----------------------------------------------------


class TestTwoPodCaptureDrill:
    def test_edl_profile_request_on_live_job(self, store, tmp_path):
        """The acceptance drill: a real 2-pod CPU launcher job running
        the chaos trainee answers ``edl-profile --request`` with a trace
        artifact + a ``profile/result/{pod}`` record per pod within 30s,
        and the capture windows are flight-recorded."""
        from edl_tpu.store.client import StoreClient

        flight_dir = tmp_path / "flight"
        out_dir = tmp_path / "prof"
        harness = ResizeHarness(
            store.endpoint, "profjob", TRAINEE,
            nodes_range="2:2", ttl=5.0,
            log_dir=str(tmp_path / "logs"),
            extra_env={
                "EDL_CKPT_PATH": str(tmp_path / "ckpt"),
                "EDL_FLIGHT_DIR": str(flight_dir),
                "JAX_PLATFORMS": "cpu",
                "EDL_DEVICES_PER_PROC": "1",
                "EDL_CHAOS_TOTAL_STEPS": "600",
                "EDL_CHAOS_CKPT_EVERY": "200",
                "EDL_CHAOS_STEP_TIME": "0.05",
            },
        )
        client = StoreClient(store.endpoint, timeout=5.0)
        progress = chaos.chaos_prefix("profjob") + "progress/step.w%d"
        try:
            harness.resize_to(2)
            deadline = time.time() + 90
            stepping = False
            while time.time() < deadline and not stepping:
                cursors = [client.get(progress % r) for r in (0, 1)]
                stepping = all(c and int(c) >= 1 for c in cursors)
                time.sleep(0.2)
            assert stepping, "2-pod job never started stepping"
            t_req = time.time()
            out = subprocess.run(
                [sys.executable, "-m", "tools.edl_profile",
                 "--store", store.endpoint, "--job", "profjob",
                 "--request", "--steps", "3", "--timeout", "30",
                 "--out", str(out_dir), "--json"],
                capture_output=True, text=True, timeout=120, cwd=str(REPO),
            )
            elapsed = time.time() - t_req
            assert out.returncode == 0, out.stderr
            results = json.loads(out.stdout)
            assert len(results) == 2, (results, out.stderr)
            assert elapsed < 30.0, "capture took %.1fs" % elapsed
            for _name, doc in results.items():
                assert doc["steps"] == 3
                assert doc["step_ms"] > 0
                assert "mfu" in doc  # CPU nominal peak: plumbing signal
                assert os.path.isdir(doc["dir"]) and os.listdir(doc["dir"]), (
                    "no trace artifact under %s" % doc["dir"]
                )
        finally:
            harness.shutdown()
            client.close()
        profile_events = [
            e for e in obs_events.read_segments(str(flight_dir))
            if e.get("event") == "profile"
        ]
        phases = sorted(e["phase"] for e in profile_events)
        # at least the two published captures (a lease blip under suite
        # load can restage mid-drill; the fresh incarnation legitimately
        # re-answers a request whose result it never saw published)
        assert phases.count("start") >= 2 and phases.count("done") >= 2, (
            "capture windows not flight-recorded: %r" % phases
        )


# -- CLI ----------------------------------------------------------------------


class TestEdlProfileCli:
    def test_once_json_reads_published_results(self, store):
        from edl_tpu.store.client import StoreClient

        client = StoreClient(store.endpoint, timeout=5.0)
        try:
            client.put(
                "/clijob/profile/result/podX",
                json.dumps({"id": "r9", "steps": 5, "step_ms": 12.3,
                            "mfu": 0.41, "dir": "/tmp/x"}).encode(),
            )
        finally:
            client.close()
        out = subprocess.run(
            [sys.executable, "-m", "tools.edl_profile",
             "--store", store.endpoint, "--job", "clijob", "--once", "--json"],
            capture_output=True, text=True, timeout=60, cwd=str(REPO),
        )
        assert out.returncode == 0, out.stderr
        results = json.loads(out.stdout)
        assert results["podX"]["steps"] == 5

    def test_once_renders_human_table(self, store):
        from edl_tpu.store.client import StoreClient

        client = StoreClient(store.endpoint, timeout=5.0)
        try:
            client.put(
                "/tabjob/profile/result/podY",
                json.dumps({"id": "r1", "steps": 2, "step_ms": 8.0,
                            "mfu": 0.5, "hbm_bytes_in_use": 2e9,
                            "dir": "/tmp/y"}).encode(),
            )
        finally:
            client.close()
        out = subprocess.run(
            [sys.executable, "-m", "tools.edl_profile",
             "--store", store.endpoint, "--job", "tabjob", "--once"],
            capture_output=True, text=True, timeout=60, cwd=str(REPO),
        )
        assert out.returncode == 0, out.stderr
        assert "podY" in out.stdout and "0.5000" in out.stdout

    def test_missing_args_rejected(self):
        out = subprocess.run(
            [sys.executable, "-m", "tools.edl_profile", "--request"],
            capture_output=True, text=True, timeout=60, cwd=str(REPO),
        )
        assert out.returncode == 2
        assert "--store" in out.stderr

    def test_local_drill_is_the_tpu_suite_payload(self, tmp_path):
        """``edl-profile --local``: the storeless round-6 payload — cost
        extraction, telemetry gauges, one capture window, one JSON line."""
        out = subprocess.run(
            [sys.executable, "-m", "tools.edl_profile",
             "--local", "--steps", "2", "--out", str(tmp_path)],
            capture_output=True, text=True, timeout=300, cwd=str(REPO),
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert out.returncode == 0, out.stderr
        doc = json.loads(out.stdout)
        assert doc["metric"] == "profile_plane_selftest"
        assert doc["platform"] == "cpu"
        assert doc["step_flops"] and doc["flops_total"] > 0
        assert doc["trace_files"] > 0
        assert doc["value"] > 0  # windowed MFU moved (nominal CPU peak)
        assert doc["roofline_mfu_ceiling"] > 0
