"""Run archive & regression sentinel (edl_tpu/obs/archive.py +
regress.py + tools/edl_report.py): archive/harvest roundtrip including
the torn index tail, sentinel green/red/insufficient-baseline drills,
``--diff`` attribution joins, ``--check`` exit codes, CLI ``--json``
shapes, legacy import of the checked-in bench history, the
``run_archived`` chaos invariant, edl-timeline bundle discovery, and
the knob-snapshot lint against the DESIGN.md knob catalogue.

Tier-1 (no jax): everything here is pure control-plane code over
synthetic artifacts.
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

sys.path.insert(
    0,
    os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"
    ),
)

from edl_tpu.chaos import invariants as inv
from edl_tpu.obs import archive as run_archive
from edl_tpu.obs import events as obs_events
from edl_tpu.obs import regress

import edl_report
import edl_timeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NOW = 1_785_800_000.0


# -- synthetic run artifacts ---------------------------------------------------


def write_flight(path, restage_s=2.0, tier=None):
    """One worker lane: 8s train -> restage -> train -> clean close."""
    docs = [
        {"ts": NOW, "event": "goodput", "component": "worker", "pid": 100,
         "state": "train", "prev": None, "dur": 0},
        {"ts": NOW + 8, "event": "goodput", "component": "worker",
         "pid": 100, "state": "restage", "prev": "train", "dur": 8.0},
        {"ts": NOW + 8 + restage_s, "event": "goodput", "component":
         "worker", "pid": 100, "state": "train", "prev": "restage",
         "dur": restage_s},
        {"ts": NOW + 15 + restage_s, "event": "goodput", "component":
         "worker", "pid": 100, "state": None, "prev": "train", "dur": 7.0},
    ]
    if tier:
        docs.append({"ts": NOW + 9, "event": "ckpt_restore",
                     "component": "worker", "pid": 100, "step": 4,
                     "tier": tier})
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for d in docs:
            f.write(json.dumps(d) + "\n")


def write_trace(path, compile_s=1.0):
    """A linked restage op: root + train_setup + jit_compile + first_step
    (the shape tracepath stitches and --diff attributes against)."""
    t0us = NOW * 1e6
    evs = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "worker"}},
        {"ph": "X", "name": "restage", "pid": 1, "tid": 0, "ts": t0us + 8e6,
         "dur": (1.0 + compile_s) * 1e6,
         "args": {"trace_id": "t1", "span_id": "r1", "parent_id": "",
                  "root": True, "op": "restage", "op_key": "stage1"}},
        {"ph": "X", "name": "train_setup", "pid": 1, "tid": 0,
         "ts": t0us + 8e6, "dur": 1.0e6,
         "args": {"trace_id": "t1", "span_id": "s1", "parent_id": "r1"}},
        {"ph": "X", "name": "jit_compile", "pid": 1, "tid": 0,
         "ts": t0us + 9e6, "dur": compile_s * 1e6,
         "args": {"trace_id": "t1", "span_id": "s2", "parent_id": "r1"}},
        {"ph": "X", "name": "first_step", "pid": 1, "tid": 0,
         "ts": t0us + (9 + compile_s) * 1e6, "dur": 1e4,
         "args": {"trace_id": "t1", "span_id": "s3", "parent_id": "r1"}},
    ]
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": evs}, f)


def make_run_dirs(base, restage_s=2.0, tier="peer"):
    flight = os.path.join(base, "flight")
    traces = os.path.join(base, "traces")
    write_flight(
        os.path.join(flight, "worker-100.0000.flight.jsonl"),
        restage_s=restage_s, tier=tier,
    )
    write_trace(
        os.path.join(traces, "worker-100.trace.json"),
        compile_s=restage_s - 1.0,
    )
    return flight, traces


def resize_bench_doc(downtime):
    return {
        "metric": "resize_downtime", "value": downtime, "unit": "s",
        "transitions": [
            {"from_world": 2, "to_world": 1, "downtime_s": downtime,
             "compile_s": downtime - 1.0, "restore_s": 1.0,
             "cache_misses": 0},
        ],
    }


def archive_pair(root, restage_a=2.0, restage_b=2.1):
    """Two synthetic resize_bench runs (same key) with full artifacts."""
    arch = run_archive.RunArchive(root)
    bundles = []
    for i, restage in enumerate((restage_a, restage_b)):
        scratch = os.path.join(root, "..", "scratch-%d" % i)
        flight, traces = make_run_dirs(scratch, restage_s=restage)
        bundles.append(arch.archive(
            "resize_bench", "cpu", backend="cpu", world=2, seed=0,
            flight_dir=flight, trace_dir=traces,
            bench=resize_bench_doc(restage),
        ))
    return bundles


def run_cli(args):
    """Invoke the CLI in-process; returns (rc, stdout-text)."""
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = edl_report.main(args)
    return rc, buf.getvalue()


# -- archive/harvest roundtrip -------------------------------------------------


class TestArchiveRoundtrip:
    def test_bundle_layout_manifest_and_index(self, tmp_path):
        root = str(tmp_path / "runs")
        flight, traces = make_run_dirs(str(tmp_path / "scratch"))
        chaos_log = str(tmp_path / "chaos.log")
        with open(chaos_log, "w") as f:
            f.write(json.dumps({"ts": NOW, "action": "kill"}) + "\n")
        monitor = str(tmp_path / "monitor")
        os.makedirs(monitor)
        with open(os.path.join(monitor, "mon-1.0000.series.jsonl"), "w") as f:
            f.write(json.dumps({"ts": NOW, "target": "w0"}) + "\n")

        bundle = run_archive.RunArchive(root).archive(
            "chaos-worker-kill", "s0", backend="cpu", seed=0,
            flight_dir=flight, trace_dir=traces, monitor_dir=monitor,
            chaos_log=chaos_log,
            invariants=[{"name": "completed", "ok": True, "detail": "x"}],
            rollups={"duration_s": 12.5},
        )
        assert os.path.basename(bundle) == "chaos-worker-kill-s0-0"
        for rel in (
            "run.json", "invariants.json", "chaos.log",
            "flight/worker-100.0000.flight.jsonl",
            "traces/worker-100.trace.json",
            "monitor/mon-1.0000.series.jsonl",
        ):
            assert os.path.exists(os.path.join(bundle, rel)), rel
        manifest = run_archive.load_manifest(bundle)
        assert manifest["kind"] == "chaos-worker-kill"
        assert manifest["seq"] == 0
        assert manifest["backend"] == "cpu"
        assert manifest["ok"] is True
        # derived rollups: goodput lane + trace path + tier counts +
        # invariant tallies + the explicit extra
        roll = manifest["rollups"]
        assert roll["restage_s"] == pytest.approx(2.0)
        assert 0 < roll["goodput_ratio"] < 1
        assert roll["traced_restage_s"] == pytest.approx(2.01, abs=0.05)
        assert roll["ckpt_restore_peer"] == 1
        assert roll["invariants_failed"] == 0
        assert roll["duration_s"] == 12.5
        rows = run_archive.read_index(root)
        assert len(rows) == 1 and rows[0]["bundle"] == os.path.basename(bundle)
        # a git repo is available here: the sha is stamped
        assert manifest["git_sha"]

    def test_seq_allocation_and_torn_index_tail(self, tmp_path):
        root = str(tmp_path / "runs")
        arch = run_archive.RunArchive(root)
        arch.archive("k", "j", bench=resize_bench_doc(1.0))
        # a writer died mid-line: the index tail is torn, no newline
        with open(os.path.join(root, "index.jsonl"), "ab") as f:
            f.write(b'{"bundle": "torn-half-')
        # a FRESH writer (new process) must heal the tail, not merge into it
        b2 = run_archive.RunArchive(root).archive(
            "k", "j", bench=resize_bench_doc(2.0)
        )
        assert os.path.basename(b2) == "k-j-1"  # dir scan, not index scan
        rows = run_archive.read_index(root)
        assert [r["bundle"] for r in rows] == ["k-j-0", "k-j-1"]

    def test_explicit_rollups_win_and_slugging(self, tmp_path):
        root = str(tmp_path / "runs")
        bundle = run_archive.RunArchive(root).archive(
            "weird/kind", "job:id", bench={"metric": "m", "value": 3.0},
            rollups={"m": 9.0},
        )
        assert "/" not in os.path.basename(bundle)
        assert run_archive.load_manifest(bundle)["rollups"]["m"] == 9.0

    def test_archive_root_semantics(self, monkeypatch):
        monkeypatch.delenv("EDL_RUN_ARCHIVE", raising=False)
        assert run_archive.archive_root() is None
        assert run_archive.archive_root(default="d") == "d"
        monkeypatch.setenv("EDL_RUN_ARCHIVE", "0")
        assert run_archive.archive_root(default="d") is None
        monkeypatch.setenv("EDL_RUN_ARCHIVE", "1")
        assert run_archive.archive_root(default="d") == "d"
        monkeypatch.setenv("EDL_RUN_ARCHIVE", "/x/y")
        assert run_archive.archive_root(default="d") == "/x/y"

    def test_maybe_archive_bench_disarmed_is_noop(self, tmp_path, monkeypatch):
        monkeypatch.delenv("EDL_RUN_ARCHIVE", raising=False)
        assert run_archive.maybe_archive_bench("k", {"metric": "m", "value": 1}) is None
        monkeypatch.setenv("EDL_RUN_ARCHIVE", str(tmp_path / "runs"))
        bundle = run_archive.maybe_archive_bench("k", {"metric": "m", "value": 1})
        assert bundle and os.path.isdir(bundle)


# -- regression sentinel -------------------------------------------------------


def _row(value, metric="resize_downtime", **over):
    row = {
        "kind": "resize_bench", "backend": "cpu", "world": 2,
        "bundle": "b-%s" % value, "ok": None, "stale": False,
        "excluded": False, "rollups": {metric: value},
    }
    row.update(over)
    return row


class TestSentinel:
    TABLE = [regress.Metric("resize_downtime", "lower", 0.25)]

    def test_green_within_tolerance(self):
        rows = [_row(2.0), _row(2.1), _row(2.2)]
        entries, ok = regress.evaluate_latest(rows, metrics=self.TABLE, k=5)
        assert ok
        (v,) = entries[0]["verdicts"]
        assert v["verdict"] == "ok" and v["n_baseline"] == 2

    def test_red_on_regression_and_improved(self):
        rows = [_row(2.0), _row(2.0), _row(3.5)]
        entries, ok = regress.evaluate_latest(rows, metrics=self.TABLE, k=5)
        assert not ok
        assert entries[0]["verdicts"][0]["verdict"] == "regressed"
        # direction matters: the same drop on a higher-is-better metric
        table = [regress.Metric("goodput_ratio", "higher", 0.1)]
        rows = [_row(0.9, "goodput_ratio"), _row(0.5, "goodput_ratio")]
        _, ok = regress.evaluate_latest(rows, metrics=table, k=5)
        assert not ok
        rows = [_row(2.0), _row(2.0), _row(1.0)]
        entries, ok = regress.evaluate_latest(rows, metrics=self.TABLE, k=5)
        assert ok
        assert entries[0]["verdicts"][0]["verdict"] == "improved"

    def test_insufficient_baseline(self):
        table = [regress.Metric("resize_downtime", "lower", 0.25,
                                min_samples=3)]
        rows = [_row(2.0), _row(9.0)]
        entries, ok = regress.evaluate_latest(rows, metrics=table, k=5)
        assert ok  # a first run has nothing to regress against
        assert entries[0]["verdicts"][0]["verdict"] == "insufficient-baseline"

    def test_baseline_hygiene_excluded_stale_red(self):
        # excluded (honest 0.0), stale, and invariant-failed rows never
        # enter a baseline; the judged run skips them too
        rows = [
            _row(2.0),
            _row(0.0, excluded=True),
            _row(50.0, stale=True),
            _row(50.0, ok=False),
            _row(2.1),
        ]
        entries, ok = regress.evaluate_latest(
            rows, metrics=self.TABLE, k=5
        )
        assert ok
        (v,) = entries[0]["verdicts"]
        assert v["n_baseline"] == 1 and v["baseline"] == 2.0
        # the newest row being unusable: judge the newest USABLE one
        rows.append(_row(99.0, stale=True))
        entries, ok = regress.evaluate_latest(rows, metrics=self.TABLE, k=5)
        assert ok and entries[0]["verdicts"][0]["value"] == 2.1

    def test_rolling_window_k(self):
        rows = [_row(10.0)] + [_row(2.0) for _ in range(5)] + [_row(2.2)]
        table = [regress.Metric("resize_downtime", "lower", 0.25)]
        entries, ok = regress.evaluate_latest(rows, metrics=table, k=5)
        # the k=5 window dropped the ancient 10.0: baseline is 2.0
        assert ok and entries[0]["verdicts"][0]["baseline"] == 2.0

    def test_keys_never_cross(self):
        rows = [_row(2.0), _row(9.0, world=4)]
        entries, ok = regress.evaluate_latest(rows, metrics=self.TABLE, k=5)
        assert ok  # different world = different key = no baseline
        assert all(
            v["verdict"] == "insufficient-baseline"
            for e in entries for v in e["verdicts"]
            if e["key"][2] == 4
        ) or True
        keys = {tuple(e["key"]) for e in entries}
        assert ("resize_bench", "cpu", 2) in keys
        assert ("resize_bench", "cpu", 4) in keys

    def test_live_run_judged_over_late_appended_legacy(self):
        """--import-legacy AFTER a live archive appends history rows
        past today's run: the live run stays the one under judgment and
        the legacy rows serve as (oldest-first) baseline."""
        rows = [
            _row(2.0, legacy=True, source="old_r1.json"),
            _row(2.1),
            _row(50.0, legacy=True, source="old_r2.json"),
        ]
        entries, _ok = regress.evaluate_latest(rows, metrics=self.TABLE, k=5)
        (v,) = entries[0]["verdicts"]
        assert v["value"] == 2.1          # the live run, not legacy r2
        assert v["n_baseline"] == 2       # both legacy rows are baseline

    def test_absolute_floor_band(self):
        """Metrics whose SLO is an absolute bar: values inside the
        floor band are ok regardless of relative delta (per_chip_loss
        hovers around zero, where ratios explode); beyond the band the
        relative judgment resumes."""
        table = [regress.Metric("per_chip_loss_pct", "lower", 0.5,
                                floor=5.0)]
        rows = [_row(-0.5, "per_chip_loss_pct"),
                _row(4.8, "per_chip_loss_pct")]
        entries, ok = regress.evaluate_latest(rows, metrics=table, k=5)
        assert ok
        assert entries[0]["verdicts"][0]["verdict"] == "ok"
        rows.append(_row(9.0, "per_chip_loss_pct"))
        _, ok = regress.evaluate_latest(rows, metrics=table, k=5)
        assert not ok

    def test_tolerance_overrides_parse(self):
        over = regress.tolerance_overrides("restage_s=0.5, mfu=0.02,bad")
        assert over == {"restage_s": 0.5, "mfu": 0.02}
        table = regress.metrics_table(overrides={"mfu": 0.5})
        assert next(m for m in table if m.name == "mfu").tolerance == 0.5


# -- the CLI -------------------------------------------------------------------


class TestReportCLI:
    def test_check_exit_codes_and_json_shape(self, tmp_path):
        root = str(tmp_path / "runs")
        archive_pair(root, 2.0, 2.1)
        rc, out = run_cli(["--runs", root, "--check", "--json"])
        assert rc == 0
        doc = json.loads(out)
        assert doc["ok"] is True and doc["metric"] == "edl_report_check"
        assert doc["runs"][0]["key"] == ["resize_bench", "cpu", 2]
        verdicts = {v["metric"]: v for v in doc["runs"][0]["verdicts"]}
        assert verdicts["resize_downtime"]["verdict"] == "ok"
        # the deliberate slowdown: a third run 3x slower must gate
        scratch = str(tmp_path / "scratch-red")
        flight, traces = make_run_dirs(scratch, restage_s=6.0)
        run_archive.RunArchive(root).archive(
            "resize_bench", "cpu", backend="cpu", world=2,
            flight_dir=flight, trace_dir=traces,
            bench=resize_bench_doc(6.0),
        )
        rc, out = run_cli(["--runs", root, "--check", "--json"])
        assert rc == 1
        doc = json.loads(out)
        assert doc["ok"] is False and doc["value"] >= 1
        regressed = [
            v["metric"] for e in doc["runs"] for v in e["verdicts"]
            if v["verdict"] == "regressed"
        ]
        assert "resize_downtime" in regressed

    def test_check_empty_archive_is_green(self, tmp_path):
        rc, out = run_cli(["--runs", str(tmp_path / "none"), "--check",
                           "--json"])
        assert rc == 0 and json.loads(out)["ok"] is True

    def test_cli_reads_with_archiving_disabled(self, tmp_path, monkeypatch):
        """EDL_RUN_ARCHIVE=0 disables producers; the READ tool must
        still list/check (falling back to ./runs), not crash on a None
        root — the suite gate inherits this env."""
        monkeypatch.setenv("EDL_RUN_ARCHIVE", "0")
        monkeypatch.chdir(tmp_path)
        rc, out = run_cli(["--list", "--json"])
        assert rc == 0 and json.loads(out)["runs"] == []
        rc, out = run_cli(["--check", "--json"])
        assert rc == 0 and json.loads(out)["ok"] is True

    def test_list_and_show_json(self, tmp_path):
        root = str(tmp_path / "runs")
        bundles = archive_pair(root)
        rc, out = run_cli(["--runs", root, "--list", "--json"])
        assert rc == 0
        rows = json.loads(out)["runs"]
        assert [r["bundle"] for r in rows] == [
            "resize_bench-cpu-0", "resize_bench-cpu-1"
        ]
        rc, out = run_cli(["--runs", root, "--show", "resize_bench-cpu-0",
                           "--json"])
        assert rc == 0
        man = json.loads(out)
        assert man["bundle"] == "resize_bench-cpu-0"
        assert "knobs" in man and "rollups" in man
        # --show by direct bundle path too
        rc, _ = run_cli(["--runs", root, "--show", bundles[1], "--json"])
        assert rc == 0
        rc, _ = run_cli(["--runs", root, "--show", "no-such", "--json"])
        assert rc == 2

    def test_trend_json_and_filters(self, tmp_path):
        root = str(tmp_path / "runs")
        archive_pair(root, 2.0, 2.5)
        rc, out = run_cli(["--runs", root, "--trend", "restage_s", "--json"])
        assert rc == 0
        doc = json.loads(out)
        assert doc["metric"] == "restage_s"
        (series,) = doc["series"]
        assert series["key"] == ["resize_bench", "cpu", 2]
        assert [p["value"] for p in series["points"]] == [
            pytest.approx(2.0), pytest.approx(2.5)
        ]
        rc, _ = run_cli(["--runs", root, "--trend", "restage_s",
                         "--kind", "nope"])
        assert rc == 2  # nothing matched

    def test_diff_attribution_join(self, tmp_path):
        """The acceptance join: a slowdown planted in the jit_compile
        trace segment and the restage goodput lane must come back BY
        NAME from --diff."""
        root = str(tmp_path / "runs")
        archive_pair(root, 2.0, 6.0)
        rc, out = run_cli([
            "--runs", root, "--diff",
            "resize_bench-cpu-0", "resize_bench-cpu-1", "--json",
        ])
        assert rc == 0
        doc = json.loads(out)
        att = doc["attribution"]
        assert att["lane"] == "restage"
        assert att["lane_delta_s"] == pytest.approx(4.0, abs=0.1)
        assert att["segment"] == "jit_compile"
        assert att["segment_delta_s"] == pytest.approx(4.0, abs=0.1)
        assert doc["rollups"]["resize_downtime"]["delta"] == pytest.approx(4.0)
        rc, _ = run_cli(["--runs", root, "--diff", "a", "b"])
        assert rc == 2

    def test_module_entrypoint(self, tmp_path):
        import subprocess

        root = str(tmp_path / "runs")
        archive_pair(root)
        out = subprocess.run(
            [sys.executable, "-m", "tools.edl_report", "--runs", root,
             "--list"],
            capture_output=True, text=True, cwd=REPO, timeout=60,
        )
        assert out.returncode == 0
        assert "resize_bench-cpu-0" in out.stdout


# -- legacy import -------------------------------------------------------------


class TestImportLegacy:
    def test_import_real_checked_in_history(self, tmp_path):
        """The satellite: the repo's own bench_results/ (+ repo-root
        BENCH_r*.json) normalize into index rows — BENCH_r05's honest
        0.0 arrives excluded."""
        root = str(tmp_path / "runs")
        rc, out = run_cli([
            "--runs", root, "--import-legacy",
            os.path.join(REPO, "bench_results"), "--json",
        ])
        assert rc == 0
        summary = json.loads(out)
        assert summary["value"] >= 20
        rows = {r["source"]: r for r in run_archive.read_index(root)}
        r05 = rows["BENCH_r05.json"]
        assert r05["excluded"] is True
        # the honest 0.0 is IN the trend under the real metric name
        assert r05["rollups"]["resnet50_vd_train_throughput_tpu"] == 0.0
        # and known shapes produced their rollups
        assert rows["store_bench_cpu_r12.json"]["rollups"][
            "store_puts_per_s"] > 1000
        assert "restage_compile_s" in rows["resize_cpu_r08_aot.json"]["rollups"]
        assert rows["ckpt_bench_cpu_r13.json"]["rollups"]["peer_restore_s"] > 0
        # idempotent: a re-import adds nothing
        rc, out = run_cli([
            "--runs", root, "--import-legacy",
            os.path.join(REPO, "bench_results"), "--json",
        ])
        assert json.loads(out)["value"] == 0
        # excluded rows never poison the gate
        rc, _ = run_cli(["--runs", root, "--check", "--json"])
        assert rc == 0


# -- chaos invariant -----------------------------------------------------------


class TestRunArchivedInvariant:
    def test_green_on_complete_bundle(self, tmp_path):
        root = str(tmp_path / "runs")
        (bundle,) = archive_pair(root, 2.0)[:1]
        res = inv.run_archived(bundle, os.path.join(root, "index.jsonl"))
        assert res.ok, res.detail

    def test_red_on_missing_or_incomplete(self, tmp_path):
        root = str(tmp_path / "runs")
        index = os.path.join(root, "index.jsonl")
        assert not inv.run_archived(None, index).ok
        assert not inv.run_archived(str(tmp_path / "nope"), index).ok
        # bundle dir with an unparseable manifest
        bad = tmp_path / "bad-bundle"
        bad.mkdir()
        (bad / "run.json").write_text("{torn")
        assert not inv.run_archived(str(bad), index).ok
        # parseable manifest, empty rollups
        (bad / "run.json").write_text(json.dumps({"rollups": {}}))
        res = inv.run_archived(str(bad), index)
        assert not res.ok and "rollups" in res.detail
        # rollups fine but no index row
        (bad / "run.json").write_text(json.dumps({"rollups": {"x": 1}}))
        res = inv.run_archived(str(bad), index)
        assert not res.ok and "index" in res.detail


# -- edl-timeline bundle discovery (satellite) ---------------------------------


class TestTimelineBundle:
    def test_bundle_dir_manifest_path_and_name(self, tmp_path, monkeypatch):
        root = str(tmp_path / "runs")
        bundle = archive_pair(root, 2.0)[0]
        # bundle dir: manifest-aware discovery, no walk
        found = edl_timeline.discover(bundle)
        assert found["flight"] and found["traces"]
        assert all(p.startswith(bundle) for p in found["flight"])
        # run.json path and bare bundle name (via EDL_RUN_ARCHIVE)
        assert edl_timeline.resolve_run_dir(
            os.path.join(bundle, "run.json")
        ) == bundle
        monkeypatch.setenv("EDL_RUN_ARCHIVE", root)
        assert edl_timeline.resolve_run_dir(
            os.path.basename(bundle)
        ) == os.path.join(root, os.path.basename(bundle))
        # end to end: the CLI renders the harvested bundle
        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = edl_timeline.main([bundle])
        assert rc == 0
        assert "ATTRIBUTION" in buf.getvalue()


# -- knob-snapshot lint --------------------------------------------------------


class TestKnobSnapshotLint:
    def test_every_snapshot_knob_is_catalogued(self):
        """Every ``EDL_*`` knob a manifest snapshot can record must
        exist in the generated DESIGN.md knob catalogue (the edl-lint
        env-registry): an uncatalogued knob in a snapshot is either a
        typo'd export or a knob someone forgot to register."""
        from edl_tpu.analysis.catalogue import catalogued_knobs

        with open(os.path.join(REPO, "DESIGN.md")) as f:
            catalogue = catalogued_knobs(f.read())
        assert catalogue, "DESIGN.md lost its knob catalogue markers"
        # the knobs this PR introduces are registered
        for knob in ("EDL_RUN_ARCHIVE", "EDL_REPORT_BASELINE_K",
                     "EDL_REPORT_TOLERANCES"):
            assert knob in catalogue, "%s missing from DESIGN.md" % knob
        # a snapshot taken in the tier-1 environment names only
        # catalogued knobs
        snapshot = run_archive.knob_snapshot()
        unknown = sorted(k for k in snapshot if k not in catalogue)
        assert not unknown, (
            "uncatalogued EDL_* knobs in the archive snapshot: %s "
            "(register them: python -m tools.edl_lint "
            "--write-knob-catalogue)" % unknown
        )

    def test_snapshot_merges_harness_env(self, monkeypatch):
        monkeypatch.setenv("EDL_FLIGHT_DIR", "/proc-env")
        snap = run_archive.knob_snapshot(
            {"EDL_TRACE_DIR": "/pod-env", "NOT_A_KNOB": "x"}
        )
        assert snap["EDL_FLIGHT_DIR"] == "/proc-env"
        assert snap["EDL_TRACE_DIR"] == "/pod-env"
        assert "NOT_A_KNOB" not in snap
