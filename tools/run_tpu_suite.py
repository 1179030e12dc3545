"""One-shot TPU measurement suite: run every queued on-chip benchmark in
priority order, committing nothing — artifacts land in ``bench_results/``
for review.

One process per chip: this parent never imports jax. It asks a throwaway
child what devices there are (no TPU = exit 1, nothing measured), then
runs the steps one after another, each in a process of its own with a
timeout, writing ``bench_results/<name>_tpu_r{round}.json`` after each
step so a late failure still keeps everything measured so far.

Usage::

    python tools/run_tpu_suite.py --round 4 [--skip attention_bench ...]

Steps (priority order — the BASELINE bars first):

0. edl_profile --local      round-6 payload: profiling-plane sanity on the
                            real chip — cost-model gauges (MFU/roofline/
                            HBM from device.memory_stats) + one on-demand
                            jax.profiler capture window through the real
                            CaptureController
1. bench.py                 fresh headline (sweep + remat A/B + 3 trials)
2. lm_bench                 TransformerLM tokens/s + MFU (bf16 kernels,
                            save_flash remat, fp32-accum head)
3. lm_profile               per-op attribution of the LM step
4. attention_bench --calibrate   kernel-vs-XLA + dispatch-table regen
5. attention_block_sweep    re-sweep block table (bf16 operands moved it)
6. distill_retention        service distill vs pure train, jitted teachers
7. resize_bench --platform tpu   1,r,r restart drill (standby shells on)
7b. resize_bench_aot[_control]   round-7 payload: AOT resize ladder +
                            portable cache keys on-chip (EDL_CACHE_
                            PORTABLE_KEYS=all) vs the --no-aot control —
                            the restage lane's compile_s should collapse
                            to a cache load
7c. hbm_oom_drill           round-8 payload: the memory plane's red drill
                            — injected RESOURCE_EXHAUSTED must produce an
                            fsynced forensics bundle + oom-detected alert
                            + restage-to-completion; the archived rollups
                            (hbm_peak_gb, hbm_plan_accuracy_pct — the
                            compile-time plan judged against the runtime
                            census high-water mark, with a per-step
                            mem_census trail in the flight records) feed
                            the regression sentinel's memory rows
8. lm_long_sweep            8k/16k/32k curve with MFU/roofline
9. colocated_distill        fused same-chip KD step (bf16 teacher)
10. edl_report --check      closing gate: every step above was indexed
                            into the run archive (``runs/`` or
                            ``EDL_RUN_ARCHIVE``); the regression
                            sentinel judges the round against the
                            rolling baseline and its verdict is
                            archived as bench_results/edl_report_r{N}.json
                            — a regressed metric turns the suite red
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS = os.path.join(REPO, "bench_results")

sys.path.insert(0, REPO)


def run_step(name, cmd, out_path, timeout, extra_env=None):
    env = dict(os.environ)
    env.update(extra_env or {})
    t0 = time.time()
    print("== %s: %s" % (name, " ".join(cmd)), file=sys.stderr)
    try:
        out = subprocess.run(
            cmd, timeout=timeout, capture_output=True, text=True,
            env=env, cwd=REPO,
        )
    except subprocess.TimeoutExpired:
        print("== %s TIMED OUT after %ds" % (name, timeout), file=sys.stderr)
        return False
    lines = [l for l in out.stdout.splitlines() if l.strip().startswith("{")]
    if out.returncode != 0 or not lines:
        print(
            "== %s FAILED rc=%d: %s"
            % (name, out.returncode, (out.stderr or "")[-500:]),
            file=sys.stderr,
        )
        return False
    payload = lines if len(lines) > 1 else lines[-1:]
    with open(out_path, "w") as f:
        f.write("\n".join(payload) + "\n")
    archive_step(name, out_path)
    print(
        "== %s ok in %.0fs -> %s" % (name, time.time() - t0, out_path),
        file=sys.stderr,
    )
    return True


def suite_archive_root():
    from edl_tpu.obs import archive as run_archive

    return run_archive.archive_root(default=os.path.join(REPO, "runs"))


def archive_step(name, out_path):
    """Every suite step's result JSON becomes an indexed run-archive
    bundle (kind = step name, backend = tpu), so round-over-round
    on-chip numbers trend and gate via edl_report — best-effort: a
    broken archive never fails the measurement."""
    try:
        from edl_tpu.obs import archive as run_archive

        root = suite_archive_root()
        if not root:
            return
        docs = []
        with open(out_path) as f:
            for line in f:
                try:
                    doc = json.loads(line)
                except ValueError:
                    continue
                if isinstance(doc, dict):
                    docs.append(doc)
        if not docs:
            return
        doc = docs[-1]  # jsonl sweeps: the last row carries the summary
        if doc.get("bundle"):
            return  # the tool self-archived (EDL_RUN_ARCHIVE reached the
            # child): a second bundle of the same run would enter its
            # own baseline and dilute the very regressions the gate hunts
        if not run_archive.rollups_from_bench(doc):
            return  # no comparable scalar (lint verdicts, dispatch
            # tables): nothing a baseline could gate on
        run_archive.maybe_archive_bench(
            name, doc, job_id="tpu", backend="tpu", root=root,
        )
    except Exception as exc:  # noqa: BLE001
        print("== archive of %s failed: %s" % (name, exc), file=sys.stderr)


def run_report_gate(py, round_no):
    """The suite's closing step, first-class like the edl_lint opener:
    `edl_report --check --json` over the round's archived runs, verdict
    archived as bench_results/edl_report_r{round}.json. Returns True
    when no table metric regressed."""
    root = suite_archive_root()
    if not root:
        # EDL_RUN_ARCHIVE=0: nothing was archived this round, and gating
        # on a leftover ./runs from an older experiment would red a
        # round that measured nothing regressed
        print("== edl_report skipped: archiving disabled", file=sys.stderr)
        return True
    out_path = os.path.join(RESULTS, "edl_report_r%d.json" % round_no)
    cmd = [py, "-m", "tools.edl_report", "--check", "--json",
           "--runs", root]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    print("== edl_report: %s" % " ".join(cmd), file=sys.stderr)
    try:
        out = subprocess.run(
            cmd, timeout=300, capture_output=True, text=True,
            env=env, cwd=REPO,
        )
    except subprocess.TimeoutExpired:
        print("== edl_report TIMED OUT", file=sys.stderr)
        return False
    lines = [l for l in out.stdout.splitlines() if l.strip().startswith("{")]
    if lines:
        with open(out_path, "w") as f:
            f.write(lines[-1] + "\n")
    if out.returncode != 0:
        print(
            "== edl_report GATE RED rc=%d: %s"
            % (out.returncode, (lines[-1:] or [out.stderr[-500:]])[0]),
            file=sys.stderr,
        )
        return False
    print("== edl_report gate OK -> %s" % out_path, file=sys.stderr)
    return True


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--round", type=int, default=8)
    p.add_argument("--skip", nargs="*", default=[])
    args = p.parse_args()

    from edl_tpu.cluster.job_env import probe_devices

    found = probe_devices()
    if found.platform != "tpu":
        print(
            "== no TPU (jax found platform %r); nothing measured"
            % found.platform,
            file=sys.stderr,
        )
        return 1
    print("== TPU up: %d x %s" % (found.count, found.kind), file=sys.stderr)
    os.makedirs(RESULTS, exist_ok=True)
    r = args.round
    py = sys.executable

    steps = [
        # static-analysis conformance first: cheap, and the per-pass
        # one-line pass/fail summary (--compact) is archived with the
        # round's payloads so a red lint is visible in bench_results
        ("edl_lint",
         [py, "-m", "tools.edl_lint", "--json", "--compact",
          "--baseline", ".edl_lint_baseline.json"],
         "edl_lint_r%d.json" % r, 300, {"JAX_PLATFORMS": "cpu"}),
        # profiling-plane payload (round 6): telemetry-gauge sanity + one
        # on-demand capture on the real chip. First in line — it is cheap
        # (~20 toy steps + a bounded trace window) and proves the live
        # MFU/HBM plane works where it matters before the long bars run.
        ("profile_plane", [py, "tools/edl_profile.py", "--local"],
         "profile_plane_tpu_r%d.json" % r, 1200, None),
        # outer timeout sized for bench.py's worst case: up to 9 child
        # runs (baseline, 2 batches, LHS, remat, LHS+remat, 2 extra
        # trials) x EDL_BENCH_RUN_TIMEOUT each
        ("bench", [py, "bench.py"],
         "bench_tpu_r%d.json" % r, 10800,
         {"EDL_BENCH_RUN_TIMEOUT": "1000"}),
        # numerics-plane cost claim, measured where it matters: the A/B
        # lane (probe fused vs not, interleaved trials) archives one
        # numerics_probe_overhead_pct record the report gate holds under
        # the 2% bar (obs/regress.py floor)
        ("numerics_overhead", [py, "bench.py", "--numerics-overhead"],
         "numerics_overhead_tpu_r%d.json" % r, 7200,
         {"EDL_BENCH_RUN_TIMEOUT": "1000"}),
        ("lm_bench", [py, "tools/lm_bench.py", "--batch", "16"],
         "lm_tpu_r%d.json" % r, 2400, None),
        # activation-strategy A/B at the flagship shape: 'none' skips ALL
        # recompute (fastest iff activations fit the 16 GiB HBM)
        ("lm_bench_noremat",
         [py, "tools/lm_bench.py", "--batch", "16", "--remat", "none"],
         "lm_noremat_tpu_r%d.json" % r, 2400, None),
        # GQA training variant: grouped kernels, kv projections /4
        ("lm_bench_gqa",
         [py, "tools/lm_bench.py", "--batch", "16", "--kv_heads", "4"],
         "lm_gqa_tpu_r%d.json" % r, 2400, None),
        ("lm_profile", [py, "tools/lm_profile.py"],
         "lm_profile_tpu_r%d.json" % r, 3000, None),
        ("attention_bench",
         [py, "tools/attention_bench.py", "--calibrate",
          os.path.join(RESULTS, "attention_dispatch_r%d.json" % r)],
         "attention_tpu_r%d.jsonl" % r, 3000, None),
        # the bf16-operand kernel rewrite moves the block optima; the r4
        # table was swept with fp32 operands
        ("attention_block_sweep",
         [py, "tools/attention_block_sweep.py"],
         "attention_blocks_r%d.jsonl" % r, 3600, None),
        ("attention_block_sweep_flash2",
         [py, "tools/attention_block_sweep.py", "--impl", "flash2",
          "--seqs", "8192"],
         "attention_blocks_flash2_r%d.jsonl" % r, 3600, None),
        # does the whole-KV kernel compile at 8192 now that bf16 halved
        # its VMEM refs? error rows are the answer either way (the r4
        # wall was a compile crash at any block config past 4096)
        ("attention_flash_8k_probe",
         [py, "tools/attention_block_sweep.py", "--impl", "flash",
          "--seqs", "8192", "--blocks_q", "128", "256",
          "--blocks_k", "512"],
         "attention_flash8k_r%d.jsonl" % r, 1800,
         {"EDL_FLASH_MAX_SEQ": "16384"}),
        # jax backend derives the fully-serialized co-location floor
        # (teacher-only sps) so the ratio is self-interpreting. Teachers
        # are threads of the student's process: a teacher PROCESS beside a
        # training process cannot exist on one chip (one owner per chip).
        ("distill_retention",
         [py, "tools/distill_retention.py", "--backend", "jax",
          "--batch", "64", "--units", "20", "--epochs", "2"],
         "distill_retention_tpu_r%d.json" % r, 2400, None),
        # echo isolates the pipeline machinery on-chip; 3 trials +
        # spread: a single short run sits within noise of the bar
        ("distill_retention_echo",
         [py, "tools/distill_retention.py", "--backend", "echo",
          "--trials", "3", "--batch", "64", "--units", "20",
          "--epochs", "2"],
         "distill_retention_echo_tpu_r%d.json" % r, 3600, None),
        # single-chip restart drill (multi-worker worlds can't share the
        # one chip).
        # Standby shells are on by default — the measured lever for the
        # <=10s downtime bar; the control is --no-standby.
        ("resize_bench",
         [py, "tools/resize_bench.py", "--platform", "tpu",
          "--schedule", "1,r,r", "--interval", "300"],
         "resize_tpu_r%d.json" % r, 2400, None),
        # round-7 payload: AOT resize ladder + portable cache keys ON
        # REAL TPU. The 1,r,r restart drill with topology-independent
        # keys answers "does a relaunched incarnation's restage lane
        # drop to a cache load on-chip" (compile_s vs restore_s split +
        # per-stage cache hit/miss ledger are in the report now); the
        # --no-aot control is the same schedule paying the recompile.
        # EDL_CACHE_PORTABLE_KEYS=all is the TPU opt-in being confirmed.
        ("resize_bench_aot",
         [py, "tools/resize_bench.py", "--platform", "tpu",
          "--schedule", "1,r,r", "--interval", "300"],
         "resize_aot_tpu_r%d.json" % r, 2400,
         {"EDL_CACHE_PORTABLE_KEYS": "all"}),
        ("resize_bench_aot_control",
         [py, "tools/resize_bench.py", "--platform", "tpu",
          "--schedule", "1,r,r", "--interval", "300", "--no-aot"],
         "resize_aot_control_tpu_r%d.json" % r, 2400,
         {"EDL_CACHE_PORTABLE_KEYS": "0"}),
        ("lm_long_sweep", [py, "tools/lm_long_sweep.py"],
         "lm_long_tpu_r%d.jsonl" % r, 5400, None),
        ("colocated_distill", [py, "tools/colocated_distill.py"],
         "colocated_tpu_r%d.json" % r, 2400, None),
        # KV-cache decode: the GQA/MQA bandwidth story in tokens/s
        ("decode_bench", [py, "tools/decode_bench.py"],
         "decode_tpu_r%d.jsonl" % r, 2400, None),
        # the numerics plane's red drill rides every round: seeded
        # gradient corruption must produce a nan-detected/loss-spike
        # alert + nonfinite flight record end-to-end (CPU rig — the
        # plane under test is detection, not the chip). chaos_run exits
        # nonzero on any red invariant, failing the step; the archived
        # bundle carries the verdicts into the round's index
        ("grad_corrupt_drill",
         [py, "tools/chaos_run.py", "--scenario", "grad-corrupt",
          "--seed", "0"],
         "grad_corrupt_r%d.json" % r, 900,
         {"EDL_RUN_ARCHIVE": suite_archive_root() or "0"}),
        # the scale plane's drill rides every round too: a live Scaler
        # steering real grow/shrink through drain/restage, gated on
        # goodput loss vs the offline oracle + decision->restage
        # latency; the archived rollups feed the regression sentinel's
        # autoscale_goodput_loss_pct / decision_to_restage_s rows
        ("autoscale_churn_drill",
         [py, "tools/chaos_run.py", "--scenario", "autoscale-churn",
          "--seed", "0"],
         "autoscale_churn_r%d.json" % r, 900,
         {"EDL_RUN_ARCHIVE": suite_archive_root() or "0"}),
        # round-8 payload: the memory plane's red drill. An injected
        # RESOURCE_EXHAUSTED at step dispatch must leave a parseable
        # fsynced forensics bundle, fire oom-detected within budget, and
        # still complete the job after restage; the tight census cadence
        # (EVERY=4) archives the mem_census trail and the plan-vs-actual
        # rollups (hbm_peak_gb / hbm_plan_accuracy_pct) the regression
        # sentinel's memory rows judge (CPU rig — the plane under test
        # is forensics + fit-gating, not the chip)
        ("hbm_oom_drill",
         [py, "tools/chaos_run.py", "--scenario", "hbm-oom",
          "--seed", "0"],
         "hbm_oom_r%d.json" % r, 900,
         {"EDL_RUN_ARCHIVE": suite_archive_root() or "0"}),
        # the serving resilience plane rides every round: the SLO bench
        # (nominal + overload lanes — serve_qps/serve_p99_ms/
        # serve_shed_pct rollups feed the regression sentinel) and the
        # teacher-churn drill (dead teacher -> breaker ejection, graceful
        # drain, sub-SLO latency tail -> hedges) on the CPU rig — the
        # plane under test is the client/admission machinery, not the
        # chip
        ("serve_slo_bench",
         [py, "tools/serve_slo.py", "--qps", "60", "--duration", "8",
          "--teachers", "2", "--overload"],
         "serve_slo_r%d.json" % r, 900, None),
        ("serve_slo_churn_drill",
         [py, "tools/chaos_run.py", "--scenario", "serve-slo-churn",
          "--seed", "0"],
         "serve_slo_churn_r%d.json" % r, 900,
         {"EDL_RUN_ARCHIVE": suite_archive_root() or "0"}),
        # the consistency plane's soak: seeded failover + shard-failover
        # drills whose taped op histories replay through the
        # no-stale-reads / monotonic-session / watch-gap-free checker
        # (CPU rig — the plane under test is the store, not the chip);
        # each run's consistency verdicts ride its archived bundle
        ("store_consistency_soak",
         [py, "tools/chaos_run.py", "--scenario",
          "store-failover,store-shard-failover", "--repeat", "5",
          "--seed", "0"],
         "store_consistency_r%d.json" % r, 1800,
         {"EDL_RUN_ARCHIVE": suite_archive_root() or "0"}),
    ]
    done = 0
    for name, cmd, out_name, timeout, extra in steps:
        if name in args.skip:
            continue
        if run_step(name, cmd, os.path.join(RESULTS, out_name), timeout, extra):
            done += 1
    # the regression sentinel closes the round: every step above indexed
    # its result in the run archive; a regressed table metric turns the
    # whole suite red (the verdict itself is archived for the round)
    gate_ok = True
    if "edl_report" not in args.skip:
        gate_ok = run_report_gate(py, r)
    print(json.dumps({
        "metric": "tpu_suite", "value": done, "unit": "steps",
        "device": found.kind, "of": len(steps) - len(args.skip),
        "report_gate_ok": gate_ok,
    }))
    return 0 if done and gate_ok else 1


if __name__ == "__main__":
    sys.exit(main())
