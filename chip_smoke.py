"""chip_smoke.py — does the system still start on the chip?

Drives the main path once through the entry points a user types, at the
full width of the models on record, with random weights from ``--seed``:

- ``train``: ``python -m edl_tpu.launch --embed_store ...`` spawning one
  worker that runs ``ElasticTrainer.fit`` on ResNet50_vd (224x224, batch
  256), then a second launch under a new job id that must resume from the
  first's checkpoint with its step program a compile-cache HIT.
- ``ladder``: a third launch with ``--nodes_range 1:2``, to see what the
  AOT resize ladder does with a world-2 rung on a host it cannot grow on.
- ``lm``: ``ElasticTrainer.fit`` on the 12-layer d_model-1024
  TransformerLM at batch 16 x seq 2048 — the Pallas flash kernels inside a
  real step — then kernel-vs-float32-reference errors on the chip.
- ``teacher``: a ``PredictServer`` over a jitted ResNet50_vd in its own
  process; this parent sends predicts through ``PredictClient``.

``--chips 4`` runs instead, and only, the mesh phase: one worker owning
four chips under ``dp=2 x fsdp=2`` against the same seed and batch on one
device.

One process per chip: this parent is stdlib + numpy and never imports jax;
every phase is a child process, one after another, each with a timeout.
With no TPU the script exits non-zero. Each phase prints JSON lines; the
LAST line of stdout is ``{"ok": ..., "device": {...}}``. The compile cache
is wherever ``JAX_COMPILATION_CACHE_DIR`` says, else the checkout's fixed
default — this script sets no directory.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
import traceback

import numpy as np

from edl_tpu.cluster.job_env import probe_devices
from edl_tpu.utils.net import find_free_ports

REPO = os.path.dirname(os.path.abspath(__file__))
SELF = os.path.abspath(__file__)
LOGS = os.path.join(REPO, "chiprun_out", "chip_smoke")   # small: comes back
WORK = os.path.join(REPO, ".scratch", "chip_smoke")      # checkpoints: stays

BUDGET_S = 1150.0  # the whole run, compilation included, against 1200

# kernel-vs-reference tolerance: max |kernel - ref| over max |ref|, with
# the reference in float32 at full matmul precision on the same bf16
# inputs. bf16 keeps 8 mantissa bits (one rounding ~ 2**-9 = 0.002
# relative); the kernels round q.k products' probabilities and dS to bf16
# once each before an fp32-accumulated matmul, so a few roundings compound.
KERNEL_REL_TOL = 2e-2

# one-device vs dp2 x fsdp2 losses: same arithmetic, different reduction
# order in bf16 convolutions and batch-norm statistics
MESH_LOSS_RTOL = 2e-2

FULL = {
    "platform": "tpu",
    "train": {"model": "resnet50_vd", "batch": 256, "image": 224,
              "classes": 1000, "steps": 6, "epochs": 2},
    "lm": {"vocab": 32000, "d_model": 1024, "layers": 12, "heads": 16,
           "d_ff": 2688, "batch": 16, "seq": 2048, "steps": 3,
           # (b, h, h_kv, t, d): flash at the lm shape's width, flash2
           # past the whole-KV limit, one GQA case
           "kernel_cases": [[2, 16, 16, 2048, 64], [1, 16, 16, 8192, 64],
                            [2, 16, 4, 2048, 64]]},
    "teacher": {"model": "resnet50_vd", "batch": 32, "image": 224,
                "classes": 1000, "calls": 3},
    "mesh": {"model": "resnet50_vd", "batch": 256, "image": 224,
             "classes": 1000, "steps": 3, "axes": {"dp": 2, "fsdp": 2}},
}

# the trainer's own "carrying on without it" lines (train/loop.py,
# train/aot.py): on the smoke path each one is a failure
_DEGRADED = re.compile(
    r"elastic-trainer: .*unavailable|continuing without|continuing uncached"
)


class PhaseFailed(Exception):
    pass


def emit(**doc):
    print(json.dumps(doc), flush=True)


# -- parent-side plumbing -----------------------------------------------------


def _child_env():
    """The caller's environment, minus the test rigs' device-count pin: the
    smoke takes the defaults a user on a TPU host gets."""
    env = dict(os.environ)
    env.pop("EDL_DEVICES_PER_PROC", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _kill_group(proc):
    """Stop ``proc`` and everything it started (it leads its own session)."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def _run(cmd, log_path, timeout, env):
    """Run one child to its end, output to ``log_path``; returns
    ``(exit code, seconds)``; a timeout kills its whole process group."""
    os.makedirs(os.path.dirname(log_path), exist_ok=True)
    t0 = time.monotonic()
    with open(log_path, "ab") as log:
        proc = subprocess.Popen(
            cmd, cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        try:
            code = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            code = None
        finally:
            _kill_group(proc)
    if code is None:
        raise PhaseFailed(
            "timed out after %.0fs (log: %s)" % (timeout, log_path)
        )
    return code, time.monotonic() - t0


def _read(path):
    try:
        with open(path, errors="replace") as f:
            return f.read()
    except OSError:
        return ""


def _tail(path, n=1500):
    return _read(path)[-n:]


def _load_report(path, code, *log_paths):
    """The child's report, or PhaseFailed with the tails of its logs."""
    if code != 0 or not os.path.exists(path):
        raise PhaseFailed("exit code %s; %s" % (
            code, "; ".join("%s: %s" % (p, _tail(p)) for p in log_paths)
        ))
    with open(path) as f:
        return json.load(f)


def _ckpt_dir():
    return os.path.join(WORK, "ckpt")


def _check_worker_log(text):
    bad = _DEGRADED.findall(text)
    if bad:
        raise PhaseFailed("trainer degraded: %r" % bad[:3])


def _role_cmd(role, cfg, report, *extra):
    return [SELF, "--role", role, "--cfg", json.dumps(cfg),
            "--report", report, *extra]


def _launch(cfg, tag, role, nodes_range, timeout, *role_args):
    """One ``python -m edl_tpu.launch`` run of this script's ``role`` —
    the README quick start with an embedded store. Returns the worker's
    report, its log text and the launcher's wall seconds."""
    log_dir = os.path.join(LOGS, tag)
    report = os.path.join(WORK, "%s.json" % tag)
    cmd = [
        sys.executable, "-m", "edl_tpu.launch",
        "--job_id", "smoke-%s-%d" % (tag, os.getpid()),
        "--store", "127.0.0.1:%d" % find_free_ports(1)[0], "--embed_store",
        "--nodes_range", nodes_range, "--nproc_per_node", "1",
        "--log_dir", log_dir,
        *_role_cmd(role, cfg, report, *role_args),
    ]
    launcher_log = os.path.join(log_dir, "launcher.log")
    code, seconds = _run(cmd, launcher_log, timeout, _child_env())
    worker_log = os.path.join(log_dir, "workerlog.0")
    rep = _load_report(report, code, launcher_log, worker_log)
    spawns = _read(launcher_log).count("spawned worker")
    if spawns != 1:
        # e.g. the launcher lost its own leases and SIGKILLed a healthy
        # worker (first chip run: every TPU runtime start froze the VM)
        raise PhaseFailed(
            "the launcher spawned its worker %d times on a quiet host "
            "(log: %s)" % (spawns, launcher_log)
        )
    return rep, _read(worker_log), seconds


# -- phases (parent side) -----------------------------------------------------


def _count(names):
    return {name: names.count(name) for name in sorted(set(names))}


def phase_train(cfg, left):
    """A cold launch, then a second job resuming from its checkpoint: the
    step program must come out of the compile cache."""
    c = cfg["train"]
    ckpt = _ckpt_dir()

    rep, log, secs = _launch(
        cfg, "train-cold", "train", "1:1", min(480, left()),
        "--epochs", str(c["epochs"]), "--ckpt", ckpt,
    )
    _check_worker_log(log)
    misses = rep.pop("missed_modules")
    if rep["cache"]["hit"] + rep["cache"]["miss"] == 0:
        raise PhaseFailed("cache counters are all zero: not instrumented")
    if rep["step"] != c["epochs"] * c["steps"]:
        raise PhaseFailed("state.step %d after the cold launch" % rep["step"])
    emit(phase="train", launch="cold", ok=True, seconds=round(secs, 1),
         missed_modules=_count(misses), **rep)
    device = rep["device"]

    rep, log, secs = _launch(
        cfg, "train-resumed", "train", "1:1", min(300, left()),
        "--epochs", str(c["epochs"] + 1), "--ckpt", ckpt,
    )
    _check_worker_log(log)
    misses = rep.pop("missed_modules")
    if "resumed at epoch %d" % c["epochs"] not in log:
        raise PhaseFailed("the second launch did not resume at epoch %d"
                          % c["epochs"])
    if rep["step"] != (c["epochs"] + 1) * c["steps"]:
        raise PhaseFailed("state.step %d after the resumed launch" % rep["step"])
    if rep["cache"]["hit"] <= 0:
        raise PhaseFailed("no compile-cache hit on the resumed launch: %r"
                          % rep["cache"])
    if "jit_step" in misses:
        raise PhaseFailed("the train-step program MISSED the compile cache")
    emit(phase="train", launch="resumed", ok=True, seconds=round(secs, 1),
         missed_modules=_count(misses), **rep)
    return device


def phase_ladder(cfg, left):
    """A third job, now with an elastic window (``--nodes_range 1:2``): what
    does the AOT resize ladder do with a world-2 rung on a host it cannot
    grow on? A counted skip is fine; a crash or a stalled step is not.
    Resumes from ``phase_train``'s checkpoint."""
    c = cfg["train"]
    rep, log, secs = _launch(
        cfg, "train-ladder", "train", "1:2", min(300, left()),
        "--epochs", str(c["epochs"] + 2), "--ckpt", _ckpt_dir(), "--ladder",
    )
    _check_worker_log(log)
    misses = rep.pop("missed_modules")
    if rep["step"] != (c["epochs"] + 2) * c["steps"]:
        raise PhaseFailed("state.step %d after the ladder launch" % rep["step"])
    if not any(rep["ladder"].values()):
        raise PhaseFailed("the ladder recorded no outcome for its rung")
    if rep["ladder"]["failed"]:
        raise PhaseFailed("a ladder rung failed: %r" % rep["ladder"])
    emit(phase="ladder", ok=True, seconds=round(secs, 1),
         missed_modules=_count(misses), **rep)
    return rep["device"]


def phase_lm(cfg, left):
    report = os.path.join(WORK, "lm.json")
    log_path = os.path.join(LOGS, "lm", "lm.log")
    code, secs = _run(
        [sys.executable, *_role_cmd("lm", cfg, report)],
        log_path, min(600, left()), _child_env(),
    )
    rep = _load_report(report, code, log_path)
    _check_worker_log(_read(log_path))
    emit(phase="lm", ok=True, seconds=round(secs, 1), **rep)
    return rep["device"]


def phase_teacher(cfg, left):
    """The serving pillar: a teacher process alone on the chip, predicts
    sent from here through the real client."""
    from edl_tpu.distill import PredictClient

    c = cfg["teacher"]
    log_path = os.path.join(LOGS, "teacher", "teacher.log")
    os.makedirs(os.path.dirname(log_path), exist_ok=True)
    t0 = time.monotonic()
    with open(log_path, "ab") as log:
        proc = subprocess.Popen(
            [sys.executable, *_role_cmd("teacher", cfg, "-")],
            cwd=REPO, env=_child_env(), stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=log, start_new_session=True,
        )
        try:
            hello = _first_line(proc, min(300, left()))
            if hello is None:
                raise PhaseFailed(
                    "teacher never came up; log tail: %s" % _tail(log_path)
                )
            hello = json.loads(hello)
            up_s = time.monotonic() - t0
            client = PredictClient(hello["endpoint"], timeout=min(300, left()))
            try:
                rs = np.random.default_rng(cfg["seed"])
                images = rs.standard_normal(
                    (c["batch"], c["image"], c["image"], 3), dtype=np.float32
                )
                outs, call_s = [], []
                for i in range(c["calls"]):
                    # first and last call send the SAME images: the answers
                    # must agree exactly (one program, one set of weights)
                    feed = images if i in (0, c["calls"] - 1) else images[::-1]
                    t1 = time.monotonic()
                    out = client.predict({"image": np.ascontiguousarray(feed)})
                    call_s.append(round(time.monotonic() - t1, 3))
                    outs.append(out["soft_label"])
            finally:
                client.close()
        finally:
            _kill_group(proc)
    for out in outs:
        if out.shape != (c["batch"], c["classes"]):
            raise PhaseFailed("predict shape %r" % (out.shape,))
        if not np.isfinite(out).all():
            raise PhaseFailed("predict returned non-finite values")
        if not np.allclose(out.sum(axis=-1), 1.0, atol=1e-3):
            raise PhaseFailed("soft labels do not sum to 1")
    if not np.array_equal(outs[0], outs[-1]):
        raise PhaseFailed("same images, different answers")
    emit(phase="teacher", ok=True, seconds=round(time.monotonic() - t0, 1),
         up_seconds=round(up_s, 1), predict_seconds=call_s,
         shape=list(outs[0].shape), device=hello["device"])
    return hello["device"]


def _first_line(proc, timeout):
    """First stdout line of ``proc`` or None after ``timeout`` / on exit."""
    got = []
    reader = threading.Thread(
        target=lambda: got.append(proc.stdout.readline()), daemon=True
    )
    reader.start()
    reader.join(timeout)
    return got[0].decode() if got and got[0].strip() else None


def phase_mesh(cfg, left):
    """Four chips, one worker under the launcher: dp x fsdp against one
    device of the same host."""
    rep, log, secs = _launch(cfg, "mesh", "mesh", "1:1", min(900, left()))
    _check_worker_log(log)
    emit(phase="mesh", ok=True, seconds=round(secs, 1), **rep)
    return rep["device"]


# -- worker roles (children: these import jax) --------------------------------


def _device_doc():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _require_platform(cfg):
    """Fail before any work when jax did not find the platform the smoke is
    for: a phase must never quietly run somewhere else."""
    import jax

    if jax.default_backend() != cfg["platform"]:
        raise SystemExit(
            "chip_smoke: jax.default_backend() is %r, not %r"
            % (jax.default_backend(), cfg["platform"])
        )


def _vision_model(c):
    from edl_tpu.models import ResNet, ResNet50_vd

    if c["model"] == "resnet50_vd":
        return ResNet50_vd(num_classes=c["classes"])
    # the rehearsal size (tests/test_chip_smoke.py): same code, toy widths
    return ResNet(stage_sizes=(1, 1), num_classes=c["classes"], width=8)


def _image_batches(c, seed, n):
    rs = np.random.default_rng(seed)
    for _ in range(n):
        yield (
            rs.standard_normal(
                (c["batch"], c["image"], c["image"], 3), dtype=np.float32
            ),
            rs.integers(0, c["classes"], (c["batch"],)).astype(np.int32),
        )


def role_train(cfg, args):
    import jax
    import optax

    _require_platform(cfg)
    from edl_tpu.obs import memory as obs_memory
    from edl_tpu.obs import metrics as obs_metrics
    from edl_tpu.obs.profile import peak_flops
    from edl_tpu.store.client import connect_store
    from edl_tpu.train import ElasticTrainer, aot, current_env, make_cross_entropy_loss

    c = cfg["train"]
    on_chip = cfg["platform"] == "tpu"
    losses, seen = [], {}

    def on_epoch_end(epoch, metrics):
        losses.append(float(metrics["loss"]))
        if epoch != args.epochs - 1:
            return
        # the planes are alive only inside fit(): read them here
        seen["hbm_peak_bytes"] = obs_metrics.gauge(
            "edl_device_hbm_peak_bytes").value()
        seen["plan_total_bytes"] = obs_metrics.gauge(
            "edl_train_hbm_plan_bytes").value(kind="total")
        env = current_env()
        client = connect_store(env.store_endpoint, timeout=5.0)
        try:
            plan = obs_memory.read_plans(client, env.job_id).get(env.world_size)
        finally:
            client.close()
        seen["plan_limit_bytes"] = plan.limit if plan is not None else None
        if args.ladder:
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline and not any(_ladder().values()):
                time.sleep(0.2)

    def _ladder():
        counter = obs_metrics.counter("edl_train_aot_compiles_total")
        return {
            outcome: int(counter.value(outcome=outcome))
            for outcome in ("ok", "failed", "skipped_grow", "skipped_nonlocal",
                            "skipped_claimed", "skipped_indivisible")
        }

    trainer = ElasticTrainer(
        _vision_model(c),
        optax.sgd(0.05, momentum=0.9, nesterov=True),
        make_cross_entropy_loss(5),
        sample_input=np.zeros(
            (c["batch"], c["image"], c["image"], 3), np.float32
        ),
        apply_kwargs={"train": True},
        ckpt_dir=args.ckpt,
        seed=cfg["seed"],
    )
    t0 = time.monotonic()
    state = trainer.fit(
        lambda epoch: _image_batches(c, cfg["seed"] * 1000 + epoch, c["steps"]),
        epochs=args.epochs,
        on_epoch_end=on_epoch_end,
    )
    jax.block_until_ready(state)
    fit_s = time.monotonic() - t0

    if not losses or not all(np.isfinite(losses)):
        raise SystemExit("chip_smoke: non-finite loss %r" % losses)
    kind = jax.devices()[0].device_kind
    peak = peak_flops(kind)
    if peak is None:
        raise SystemExit("chip_smoke: no peak FLOP/s for device_kind %r" % kind)
    if not seen.get("plan_total_bytes"):
        raise SystemExit("chip_smoke: no MemoryPlan was harvested")
    if on_chip and not (seen["plan_limit_bytes"] or 0) > 0:
        raise SystemExit("chip_smoke: MemoryPlan.limit is %r on the chip"
                         % seen["plan_limit_bytes"])
    if on_chip and not seen["hbm_peak_bytes"] > 0:
        raise SystemExit("chip_smoke: edl_device_hbm_peak_bytes is 0")
    return {
        "device": _device_doc(),
        "first_step_seconds": round(obs_metrics.gauge(
            "edl_train_first_step_seconds").value(), 2),
        "fit_seconds": round(fit_s, 2),
        "epochs_run": len(losses),
        "losses": [round(v, 4) for v in losses],
        "step": int(state.step),
        "cache": aot.cache_event_counts(),
        "missed_modules": aot.missed_modules(),
        "cache_dir": jax.config.jax_compilation_cache_dir,
        "ladder": _ladder(),
        "peak_flops": {"device_kind": kind, "flops": peak},
        **seen,
    }


def _lm_loss(logits, targets):
    from edl_tpu.train import cross_entropy_loss

    return cross_entropy_loss(
        logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
    )


def role_lm(cfg, args):
    import jax
    import jax.numpy as jnp
    import optax

    _require_platform(cfg)
    from edl_tpu.models import TransformerLM
    from edl_tpu.obs import metrics as obs_metrics
    from edl_tpu.obs import numerics as obs_numerics
    from edl_tpu.train import ElasticTrainer, make_train_step

    c = cfg["lm"]
    model = TransformerLM(
        vocab_size=c["vocab"], d_model=c["d_model"], num_heads=c["heads"],
        num_layers=c["layers"], d_ff=c["d_ff"], remat=True,
    )
    losses = []

    def tokens(epoch):
        rs = np.random.default_rng(cfg["seed"] * 1000 + epoch)
        t = rs.integers(0, c["vocab"], (c["batch"], c["seq"] + 1)).astype(np.int32)
        yield t[:, :-1], t[:, 1:]

    trainer = ElasticTrainer(
        model, optax.adamw(1e-3), _lm_loss,
        sample_input=np.zeros((c["batch"], c["seq"]), np.int32),
        seed=cfg["seed"],
    )
    t0 = time.monotonic()
    # one step an epoch: on_epoch_end then sees every step's loss
    state = trainer.fit(
        tokens, epochs=c["steps"],
        on_epoch_end=lambda epoch, m: losses.append(float(m["loss"])),
    )
    jax.block_until_ready(state)
    fit_s = time.monotonic() - t0
    if len(losses) != c["steps"] or not all(np.isfinite(losses)):
        raise SystemExit("chip_smoke: lm losses %r" % losses)
    if int(state.step) != c["steps"]:
        raise SystemExit("chip_smoke: lm state.step %d" % int(state.step))

    # the step fit() ran, built the way _fit_stage builds it: which Pallas
    # kernels does it hold? (a jax trace; nothing compiles)
    step = make_train_step(_lm_loss, None, numerics=obs_numerics.enabled())
    text = step.lower(state, next(tokens(0))).as_text()
    names = re.findall(r'kernel_name = "(\w+)"', text)
    calls = {
        k: names.count(k)
        for k in ("_flash2_kernel", "_flash2_bwd_kernel")
    }
    if cfg["platform"] == "tpu":
        layers = c["layers"]
        # every layer's attention went through the kernel in both
        # directions: a ragged-shape fallback to the dense reference
        # would leave a layer with no custom call
        if min(calls.values()) < layers or any(
            n % layers for n in calls.values()
        ) or len(set(calls.values())) != 1:
            raise SystemExit("chip_smoke: lm step's Pallas calls %r" % calls)

    state = None  # free the model before the dense references
    kernels = [
        _kernel_vs_reference(cfg["seed"], *case) for case in c["kernel_cases"]
    ]
    for k in kernels:
        if k["max_rel_err"] > KERNEL_REL_TOL:
            raise SystemExit(
                "chip_smoke: kernel off its reference past %g: %r"
                % (KERNEL_REL_TOL, k)
            )
    return {
        "device": _device_doc(),
        "first_step_seconds": round(obs_metrics.gauge(
            "edl_train_first_step_seconds").value(), 2),
        "fit_seconds": round(fit_s, 2),
        "losses": [round(v, 4) for v in losses],
        "pallas_calls": calls,
        "kernel_rel_tol": KERNEL_REL_TOL,
        "kernels": kernels,
        "hbm_peak_bytes": (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use"),
    }


def _kernel_vs_reference(seed, b, h, h_kv, t, d):
    """``flash_attention`` (value and q/k/v gradients, causal, bf16) against
    ``attention_reference`` in float32 on the same inputs. The reference
    runs a few kv heads at a time: its [t, t] scores are dense."""
    import jax
    import jax.numpy as jnp

    from edl_tpu.ops import attention_reference, flash_attention

    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (b, h, t, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, h_kv, t, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, h_kv, t, d), jnp.bfloat16)
    w = jax.random.normal(keys[3], (b, h, t, d), jnp.bfloat16)  # cotangent

    def value_and_grads(fn):
        def run(q, k, v, w):
            out, vjp = jax.vjp(lambda q, k, v: fn(q, k, v, causal=True), q, k, v)
            return (out, *vjp(w.astype(out.dtype)))
        return jax.jit(run)

    got = value_and_grads(flash_attention)(q, k, v, w)

    ref_fn = value_and_grads(attention_reference)
    group = h // h_kv
    kv_chunk = max(1, 4 // group)
    parts = []
    with jax.default_matmul_precision("float32"):
        for i in range(0, h_kv, kv_chunk):
            qs = slice(i * group, (i + kv_chunk) * group)
            ks = slice(i, i + kv_chunk)
            parts.append(ref_fn(
                q[:, qs].astype(jnp.float32), k[:, ks].astype(jnp.float32),
                v[:, ks].astype(jnp.float32), w[:, qs].astype(jnp.float32),
            ))
    want = [jnp.concatenate(p, axis=1) for p in zip(*parts)]

    errs = {}
    for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        if not np.isfinite(a).all():
            raise SystemExit("chip_smoke: kernel %s is not finite" % name)
        abs_err = float(np.max(np.abs(a - r)))
        errs[name] = {"max_abs_err": round(abs_err, 5),
                      "max_rel_err": round(abs_err / float(np.max(np.abs(r))), 5)}
    return {
        "shape": [b, h, h_kv, t, d],
        "max_rel_err": max(e["max_rel_err"] for e in errs.values()),
        **errs,
    }


def role_teacher(cfg, args):
    """Serve until stdin closes. The one stdout line is the hello."""
    import jax

    _require_platform(cfg)
    from edl_tpu.distill import JaxPredictBackend, PredictServer

    c = cfg["teacher"]
    model = _vision_model(c)
    variables = jax.jit(lambda: model.init(
        jax.random.PRNGKey(cfg["seed"]),
        np.zeros((1, c["image"], c["image"], 3), np.float32), train=False,
    ))()

    def apply(feeds):
        logits = model.apply(variables, feeds["image"], train=False)
        return {"soft_label": jax.nn.softmax(logits, axis=-1)}

    server = PredictServer(
        JaxPredictBackend(apply), host="127.0.0.1", port=0
    ).start()
    emit(endpoint=server.endpoint, device=_device_doc())
    try:
        sys.stdin.read()
    finally:
        server.stop()
    return None


def role_mesh(cfg, args):
    import jax
    import optax

    _require_platform(cfg)
    from edl_tpu.obs import numerics as obs_numerics
    from edl_tpu.parallel import batch_sharding, make_mesh
    from edl_tpu.train import (
        ElasticTrainer, create_state, make_cross_entropy_loss, make_train_step,
    )

    c = cfg["mesh"]
    n_dev = int(np.prod(list(c["axes"].values())))
    if len(jax.devices()) != n_dev:
        raise SystemExit(
            "chip_smoke: mesh %r needs %d devices, jax has %d"
            % (c["axes"], n_dev, len(jax.devices()))
        )
    model = _vision_model(c)
    tx = optax.sgd(0.05, momentum=0.9, nesterov=True)
    loss_head = make_cross_entropy_loss(5)
    sample = np.zeros((c["batch"], c["image"], c["image"], 3), np.float32)
    # one step an epoch, so on_epoch_end sees every step's loss
    batches = [
        next(_image_batches(c, cfg["seed"] * 1000 + i, 1))
        for i in range(c["steps"])
    ]

    mesh_losses = []
    trainer = ElasticTrainer(
        model, tx, loss_head, sample_input=sample,
        apply_kwargs={"train": True}, fsdp=True, mesh_axes=c["axes"],
        seed=cfg["seed"],
    )
    t0 = time.monotonic()
    state = trainer.fit(
        lambda epoch: [batches[epoch]], epochs=c["steps"],
        on_epoch_end=lambda epoch, m: mesh_losses.append(float(m["loss"])),
    )
    jax.block_until_ready(state)
    fit_s = time.monotonic() - t0

    leaves = jax.tree.leaves((state.params, state.opt_state))
    spans = sorted({len(leaf.sharding.device_set) for leaf in leaves})
    sharded = sum(
        1 for leaf in leaves if not leaf.sharding.is_fully_replicated
    )
    in_use = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()
    ]
    mesh = make_mesh(c["axes"])
    step = make_train_step(
        loss_head, {"train": True}, numerics=obs_numerics.enabled()
    )
    with mesh:
        placed = jax.device_put(batches[0], batch_sharding(mesh, "dp"))
        hlo = step.lower(state, placed).compile().as_text()
    collectives = {
        op: len(re.findall(r"\b%s(?:-start)?\(" % op, hlo))
        for op in ("all-reduce", "all-gather", "reduce-scatter",
                   "collective-permute", "all-to-all")
    }
    state = None

    # the comparison: same seed, same global batches, one device
    one = jax.devices()[0]
    ref_state = jax.device_put(
        create_state(model, jax.random.PRNGKey(cfg["seed"]), sample, tx), one
    )
    ref_step = make_train_step(
        loss_head, {"train": True}, numerics=obs_numerics.enabled()
    )
    ref_losses = []
    for batch in batches:
        ref_state, metrics = ref_step(ref_state, jax.device_put(batch, one))
        ref_losses.append(float(metrics["loss"]))

    if spans != [n_dev]:
        raise SystemExit("chip_smoke: leaf shardings span %r devices" % spans)
    if not sharded:
        raise SystemExit("chip_smoke: fsdp sharded no leaf")
    if not any(collectives.values()):
        raise SystemExit("chip_smoke: no collective in the compiled step")
    if cfg["platform"] == "tpu":
        if not all(in_use) or max(in_use) > 10 * min(in_use):
            raise SystemExit("chip_smoke: bytes_in_use per device %r" % in_use)
    for got, want in zip(mesh_losses, ref_losses):
        if not np.isfinite(got) or abs(got - want) > MESH_LOSS_RTOL * abs(want):
            raise SystemExit(
                "chip_smoke: mesh losses %r vs one device %r"
                % (mesh_losses, ref_losses)
            )
    return {
        "device": _device_doc(),
        "fit_seconds": round(fit_s, 2),
        "mesh_axes": c["axes"],
        "mesh_losses": [round(v, 4) for v in mesh_losses],
        "one_device_losses": [round(v, 4) for v in ref_losses],
        "loss_rtol": MESH_LOSS_RTOL,
        "leaves": len(leaves),
        "leaves_sharded": sharded,
        "leaf_device_span": spans,
        "bytes_in_use": in_use,
        "collectives": collectives,
    }


ROLES = {"train": role_train, "lm": role_lm, "teacher": role_teacher,
         "mesh": role_mesh}


# -- entry --------------------------------------------------------------------


def run_phases(cfg, phases):
    """Run ``phases`` in order; returns ``(ok, device)``. A failed phase is
    reported and fails the run; later phases still run (each is its own
    process, and what they say narrows the fault)."""
    shutil.rmtree(WORK, ignore_errors=True)
    shutil.rmtree(LOGS, ignore_errors=True)
    os.makedirs(WORK)
    deadline = time.monotonic() + BUDGET_S
    left = lambda: max(1.0, deadline - time.monotonic())  # noqa: E731
    ok, device = True, None
    for phase in phases:
        try:
            device = phase(cfg, left) or device
        except Exception as exc:  # noqa: BLE001 — the last line must still print
            ok = False
            emit(phase=phase.__name__[len("phase_"):], ok=False,
                 error=str(exc) if isinstance(exc, PhaseFailed)
                 else traceback.format_exc())
    return ok, device


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 = only the dp x fsdp mesh phase")
    parser.add_argument("--seed", type=int, default=0)
    # the parent -> child seam (not for users)
    parser.add_argument("--role", choices=sorted(ROLES), help=argparse.SUPPRESS)
    parser.add_argument("--cfg", help=argparse.SUPPRESS)
    parser.add_argument("--report", help=argparse.SUPPRESS)
    parser.add_argument("--epochs", type=int, help=argparse.SUPPRESS)
    parser.add_argument("--ckpt", help=argparse.SUPPRESS)
    parser.add_argument("--ladder", action="store_true", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)

    if args.role:
        report = ROLES[args.role](json.loads(args.cfg), args)
        if report is not None:
            with open(args.report, "w") as f:
                json.dump(report, f)
        return 0

    cfg = dict(FULL, seed=args.seed)
    found = probe_devices(_child_env())
    device = {"platform": found.platform, "kind": found.kind,
              "count": found.count}
    emit(phase="preflight", device=device)
    ok = found.platform == cfg["platform"] and found.count == args.chips
    if not ok:
        emit(phase="preflight", ok=False,
             error="need %d %s device(s)" % (args.chips, cfg["platform"]))
    else:
        phases = (
            (phase_mesh,) if args.chips == 4
            else (phase_train, phase_ladder, phase_lm, phase_teacher)
        )
        ok, seen = run_phases(cfg, phases)
        device = seen or device
    assert "jax" not in sys.modules, "the smoke's parent imported jax"
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
