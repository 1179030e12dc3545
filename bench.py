"""Headline benchmark: ResNet50_vd ImageNet-shape training throughput on TPU.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "img/s", "vs_baseline": N, ...}

Baseline: the reference's pure-train row — 1828 img/s on 8x V100
(reference README.md:70), i.e. 228.5 img/s per accelerator. ``vs_baseline``
is per-chip throughput here divided by per-GPU throughput there, so >1.0
means one TPU chip beats one V100 on the same workload. ``mfu`` is model
FLOPs utilization: XLA's cost-analysis FLOPs for the jitted train step
divided by wall time and the chip's peak bf16 FLOP/s.

One process per chip: this parent never imports jax, and every
measurement runs in a child of its own, one after another. The child finds
the device itself; with no TPU it fails, the parent exits non-zero and no
number is printed (a CPU debug run is available via EDL_BENCH_FORCE_CPU=1,
clearly labelled ``..._cpu_debug``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# the cost model — peak-FLOPs / HBM-bandwidth tables and the roofline
# estimator — lives in the live profiling plane now (it exports the same
# numbers as scrape-time gauges); the bench imports it back so offline
# and live can never disagree about what a chip can do
from edl_tpu.obs.profile import (  # noqa: F401 — re-exported for tools
    HBM_BW,
    PEAK_BF16_FLOPS,
    hbm_bandwidth as _hbm_bw,
    peak_flops as _peak_flops,
    roofline,
)

BASELINE_IMG_PER_S_PER_GPU = 1828.0 / 8.0  # reference README.md:70


def measure() -> dict:
    """The actual benchmark; runs inside the measurement subprocess.

    Config via env (the sweep driver sets these per subprocess):
      EDL_BENCH_BATCH  per-chip batch size      (default 256 on TPU)
      EDL_BENCH_INPUT  "pipeline" | "resident"  (default pipeline on TPU)

    ``pipeline`` feeds the step from a REAL host input pipeline — distinct
    numpy batches pushed through ``prefetch_to_device`` double-buffering,
    so host→device transfer overlaps compute the way training does
    (round-2 weak spot: the bench fed one resident tensor every step,
    measuring a regime no training job runs in). ``resident`` keeps the
    old behavior for A/B-ing the transfer cost itself.
    """
    import sys as _sys

    _sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax

    import jax.numpy as jnp
    import numpy as np
    import optax

    from edl_tpu.data import prefetch_to_device
    from edl_tpu.models import ResNet50_vd
    from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and os.environ.get("EDL_BENCH_FORCE_CPU") != "1":
        raise SystemExit(
            "bench: no TPU (jax found platform %r); refusing to measure"
            % dev.platform
        )
    if on_tpu:
        # every sweep child shares one persistent compilation cache
        # (JAX_COMPILATION_CACHE_DIR when set, else the in-checkout
        # default): each (model, batch, flags) program compiles once
        from edl_tpu.cluster.job_env import default_compile_cache_dir
        from edl_tpu.train import enable_compilation_cache

        enable_compilation_cache(default_compile_cache_dir())
    batch = int(os.environ.get("EDL_BENCH_BATCH", "256" if on_tpu else "8"))
    input_mode = os.environ.get(
        "EDL_BENCH_INPUT", "pipeline" if on_tpu else "resident"
    )
    size = 224 if on_tpu else 24
    # overridable so the numerics A/B lane can use a real measurement
    # window on cpu_debug (2 steps is pure noise for a <=2% comparison)
    steps = int(os.environ.get("EDL_BENCH_STEPS", "30" if on_tpu else "2"))
    warmup = int(os.environ.get("EDL_BENCH_WARMUP", "8" if on_tpu else "1"))

    # EDL_BENCH_REMAT=1: recompute block activations in the backward —
    # the workload is HBM-bound (roofline ceiling 0.331 at AI ~80), so
    # cutting activation traffic can raise the ceiling itself
    remat = os.environ.get("EDL_BENCH_REMAT", "0") == "1"
    if on_tpu:
        model = ResNet50_vd(num_classes=1000, remat=remat)
    else:
        # cpu_debug exists to validate plumbing; a full ResNet50 takes
        # many minutes to compile on one CPU core
        from edl_tpu.models import ResNet

        model = ResNet(stage_sizes=(1, 1), num_classes=1000, width=8)
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (batch, size, size, 3), jnp.float32)
    y = jax.random.randint(rng, (batch,), 0, 1000)

    state = create_state(model, rng, x, optax.sgd(0.1, momentum=0.9))
    # EDL_NUMERICS=1 fuses the numerics probe's scalar bundle into the
    # step — the --numerics-overhead lane A/Bs exactly this against the
    # plain step. Opt-IN here (unlike training, where the plane defaults
    # on): the headline must stay comparable across history.
    numerics = os.environ.get("EDL_NUMERICS", "") == "1"
    probe = None
    if numerics:
        from edl_tpu.obs import numerics as obs_numerics

        probe = obs_numerics.NumericsProbe()
    step = make_train_step(
        cross_entropy_loss, {"train": True}, numerics=numerics
    )

    # AOT-compile ONCE; the compiled object gives both the timed step and
    # XLA's own FLOP count for one step (fwd+bwd+update), for MFU
    compiled = step.lower(state, (x, y)).compile()
    flops_per_step = None
    cost = {}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops_per_step = float(cost.get("flops", 0.0)) or None
    except Exception:
        pass

    if input_mode == "pipeline":
        # 4 distinct host batches cycled through the double-buffered
        # prefetch: generation stays out of the loop, the transfers don't
        host = [
            (
                # float32 straight from the generator: a float64 randn
                # intermediate at batch 1024 is an extra 1.2 GB host peak
                np.random.default_rng(i).standard_normal(
                    (batch, size, size, 3), dtype=np.float32
                ),
                np.random.default_rng(100 + i)
                .integers(0, 1000, (batch,)).astype(np.int32),
            )
            for i in range(4)
        ]

        def feed(n):
            return prefetch_to_device(
                (host[i % len(host)] for i in range(n)), depth=2
            )

    else:

        def feed(n):
            return ((x, y) for _ in range(n))

    # each window closes by fetching the final loss: it depends on every
    # prior step through the state chain, so one fetch waits for them all
    for i, placed in enumerate(feed(warmup)):
        state, metrics = compiled(state, placed)
        bundle = metrics.pop("_numerics", None)
        if probe is not None:
            # the probe's one SYNC publish (gauge arming) lands here, in
            # warmup — the timed loop below sees only the throttled path
            probe.on_step(i, bundle)
    warm_loss = float(jax.device_get(metrics["loss"]))

    t0 = time.perf_counter()
    for i, placed in enumerate(feed(steps)):
        state, metrics = compiled(state, placed)
        bundle = metrics.pop("_numerics", None)
        if probe is not None:
            probe.on_step(warmup + i, bundle)
    final_loss = float(jax.device_get(metrics["loss"]))
    dt = time.perf_counter() - t0
    if probe is not None:
        probe.close()  # final flush OUTSIDE the timed window
    assert final_loss == final_loss and warm_loss == warm_loss, "loss is NaN"

    img_per_s = batch * steps / dt
    # a plain jit with no mesh runs on device 0 only: this measurement IS
    # per-chip by construction, however many chips are visible
    n_chips = 1
    per_chip = img_per_s / n_chips
    out = {
        "metric": "resnet50_vd_train_throughput_%s"
        % ("tpu" if on_tpu else "cpu_debug"),
        "value": round(img_per_s, 1),
        "unit": "img/s",
        # a cpu_debug run uses a toy model; only a TPU run is comparable
        "vs_baseline": round(per_chip / BASELINE_IMG_PER_S_PER_GPU, 3)
        if on_tpu else 0.0,
        "device": dev.device_kind,
        "n_chips": n_chips,
        "n_devices_visible": len(jax.devices()),
        "per_chip": round(per_chip, 1),
        "batch": batch,
        "steps": steps,
        "input": input_mode,
        "remat": remat,
        "numerics": numerics,
    }
    peak = _peak_flops(dev.device_kind)
    if flops_per_step and peak and on_tpu:
        out["mfu"] = round(flops_per_step * (steps / dt) / (peak * n_chips), 4)
        out["step_tflops"] = round(flops_per_step / 1e12, 2)
        out.update(roofline(cost, dev.device_kind, peak, mfu=out["mfu"]))
    return out


def _emit(result):
    """The ONE exit for the headline JSON line: print it and, with
    ``EDL_RUN_ARCHIVE`` armed, index it in the run archive. The bundle
    name is stamped into the printed line so downstream archivers
    (run_tpu_suite's archive_step) know the run is already indexed."""
    from edl_tpu.obs import archive as run_archive

    bundle = run_archive.maybe_archive_bench(
        "bench", result,
        backend="cpu" if result["metric"].endswith("_cpu_debug") else "tpu",
    )
    if bundle:
        result["bundle"] = os.path.basename(bundle)
    print(json.dumps(result))


def numerics_overhead():
    """The A/B lane behind the numerics plane's cost claim: the SAME
    bench measured with the probe bundle fused into the step
    (``EDL_NUMERICS=1``) and without, interleaved trials, best-of-N per
    arm. Emits one archived ``numerics_probe_overhead_pct`` record — the
    regression table (obs/regress.py) holds it under the paper's 2%
    bar. Runs on whatever platform the normal bench would use; a
    cpu_debug run widens the step count so the window is measurable."""
    on_tpu = os.environ.get("EDL_BENCH_FORCE_CPU") != "1"
    env = dict(os.environ)
    if not on_tpu:
        env["JAX_PLATFORMS"] = "cpu"
    budget = float(os.environ.get("EDL_BENCH_RUN_TIMEOUT", "1500"))
    common = {
        "EDL_BENCH_SWEEP": "0",
        "EDL_BENCH_STEPS": os.environ.get(
            "EDL_BENCH_STEPS", "30" if on_tpu else "40"
        ),
        "EDL_BENCH_WARMUP": os.environ.get(
            "EDL_BENCH_WARMUP", "8" if on_tpu else "5"
        ),
    }

    def run_one(extra_env):
        child = dict(env)
        child.update(common)
        child.update(extra_env)
        try:
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--_measure"],
                timeout=budget, capture_output=True, text=True, env=child,
            )
        except subprocess.TimeoutExpired:
            return None
        for line in out.stdout.splitlines():
            if line.startswith("RESULT="):
                return json.loads(line[len("RESULT="):])
        return None

    # interleaved A/B so host-load drift hits both arms equally
    n_trials = int(os.environ.get("EDL_BENCH_TRIALS", "3"))
    off_vals, on_vals = [], []
    for _ in range(max(1, n_trials)):
        r = run_one({"EDL_NUMERICS": "0"})
        if r is not None:
            off_vals.append(float(r["value"]))
        r = run_one({"EDL_NUMERICS": "1"})
        if r is not None:
            on_vals.append(float(r["value"]))
    if not off_vals or not on_vals:
        raise SystemExit(
            "bench: one or both A/B arms produced no measurement"
        )
    # best-of-N per arm: the max of each arm is the least-perturbed
    # observation of that configuration — the honest overhead estimate
    # on a shared host (means fold scheduler hiccups into the delta)
    off_best, on_best = max(off_vals), max(on_vals)
    pct = (off_best - on_best) / off_best * 100.0
    doc = {
        "metric": "numerics_probe_overhead_pct",
        "value": round(pct, 2),
        "unit": "%",
        "vs_baseline": round(2.0 / max(pct, 1e-9), 3),  # >=1.0 = within bar
        "target_pct": 2.0,
        "baseline_img_per_s": round(off_best, 1),
        "probe_img_per_s": round(on_best, 1),
        "trials_off": [round(v, 1) for v in off_vals],
        "trials_on": [round(v, 1) for v in on_vals],
        "steps": int(common["EDL_BENCH_STEPS"]),
        "platform": "tpu" if on_tpu else "cpu_debug",
    }
    from edl_tpu.obs import archive as run_archive

    bundle = run_archive.maybe_archive_bench(
        "numerics_overhead", doc, backend="tpu" if on_tpu else "cpu"
    )
    if bundle:
        doc["bundle"] = os.path.basename(bundle)
    print(json.dumps(doc))


def main():
    if "--_measure" in sys.argv:
        # child mode: full JSON on the last stdout line
        print("RESULT=" + json.dumps(measure()))
        return
    if "--numerics-overhead" in sys.argv:
        numerics_overhead()
        return

    force_cpu = os.environ.get("EDL_BENCH_FORCE_CPU") == "1"
    env = dict(os.environ)
    if force_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    # compile can take minutes on first run; the timeout only guards hangs
    budget = float(os.environ.get("EDL_BENCH_RUN_TIMEOUT", "1500"))

    def run_one(extra_env):
        child = dict(env)
        child.update(extra_env)
        try:
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--_measure"],
                timeout=budget, capture_output=True, text=True, env=child,
            )
        except subprocess.TimeoutExpired:
            return None, "measurement subprocess hung"
        for line in out.stdout.splitlines():
            if line.startswith("RESULT="):
                return json.loads(line[len("RESULT="):]), None
        return None, "measurement failed: " + (out.stderr or "")[-400:]

    result, detail = run_one({})
    sweep = []
    if (
        result is not None
        and not force_cpu
        and os.environ.get("EDL_BENCH_SWEEP", "1") != "0"
    ):
        # batch sweep, then latency-hiding-scheduler and remat variants at
        # the winner; failed configs (e.g. an OOM batch) are skipped,
        # never fatal. Each candidate remembers the env that produced it
        # so the winner can be re-run for trials.
        candidates = [({}, result)]
        for b in (128, 512, 1024):
            e = {"EDL_BENCH_BATCH": str(b)}
            r, _ = run_one(e)
            if r is not None:
                candidates.append((e, r))
        best = max(candidates, key=lambda c: c[1]["value"])[1]
        lhs_flags = (
            env.get("XLA_FLAGS", "")
            + " --xla_tpu_enable_latency_hiding_scheduler=true"
        ).strip()
        e = {"EDL_BENCH_BATCH": str(best["batch"]), "XLA_FLAGS": lhs_flags}
        r, _ = run_one(e)
        if r is not None:
            r["xla_flags"] = "latency_hiding_scheduler"
            candidates.append((e, r))
        # remat trades recompute FLOPs for activation HBM traffic — on a
        # memory-bound roofline it can raise the ceiling (VERDICT r4 #5);
        # measured alone AND combined with LHS, so the sweep can find a
        # joint winner instead of evaluating each against a mixed baseline
        e = {"EDL_BENCH_BATCH": str(best["batch"]), "EDL_BENCH_REMAT": "1"}
        r, _ = run_one(e)
        if r is not None:
            candidates.append((e, r))
        e = {
            "EDL_BENCH_BATCH": str(best["batch"]),
            "EDL_BENCH_REMAT": "1",
            "XLA_FLAGS": lhs_flags,
        }
        r, _ = run_one(e)
        if r is not None:
            r["xla_flags"] = "latency_hiding_scheduler"
            candidates.append((e, r))
        sweep = [r for _, r in candidates]
        best_env, best = max(candidates, key=lambda c: c[1]["value"])
        # >=3 trials of the winning config (VERDICT r4 #2: a headline
        # with no variance is one scheduler hiccup from fiction); the
        # reported record is the MEDIAN trial, with the spread attached
        n_trials = int(os.environ.get("EDL_BENCH_TRIALS", "3"))
        trials = [best]
        for _ in range(max(0, n_trials - 1)):
            r, _ = run_one(best_env)
            if r is not None:
                trials.append(r)
        trials.sort(key=lambda r: r["value"])
        # LOWER median on an even count (a failed re-run must not leave
        # the max masquerading as the median)
        result = dict(trials[(len(trials) - 1) // 2])
        if "xla_flags" in best:
            result["xla_flags"] = best["xla_flags"]
        result["trials"] = [r["value"] for r in trials]
        if len(trials) > 1:
            result["trials_spread_pct"] = round(
                (trials[-1]["value"] - trials[0]["value"])
                / trials[-1]["value"] * 100, 2,
            )
        # roofline columns ride along so a throughput anomaly (r4's
        # unexplained b512 cliff) arrives with its own diagnosis: a real
        # ceiling shift shows in step_hbm_gb/bound, a corrupted
        # measurement doesn't
        result["sweep"] = [
            {k: r.get(k)
             for k in ("batch", "value", "mfu", "input", "xla_flags",
                       "remat", "step_hbm_gb", "roofline_mfu_ceiling",
                       "bound")
             if k in r}
            for r in sweep
        ]
    if result is None:
        raise SystemExit("bench: " + detail)
    _emit(result)


if __name__ == "__main__":
    main()
