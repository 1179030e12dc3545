"""Distill retention benchmark: service-distill vs pure-train throughput.

The reference's headline claim is service distillation at 0.83x of
pure-train throughput with better accuracy (1514 vs 1828 img/s, reference
README.md:68-72). This measures the same ratio end-to-end on THIS stack:

1. **pure**: a jitted student train loop over a synthetic epoch.
2. **distill**: the SAME student step plus a soft-label KL term, fed by a
   :class:`DistillReader` under the full discovery/balance stack — store,
   DiscoveryService, ≥2 registered ``PredictServer`` teachers running a
   real jitted teacher model (JaxPredictBackend) — with one teacher
   stopped mid-run, connections reset (the connection-failure failover
   path stays on the hot path; for a hung-peer/RPC-timeout drill, kill a
   remote teacher process instead).

Prints ONE JSON line::

    {"metric": "distill_retention", "value": <distill/pure ratio>,
     "unit": "x", "vs_baseline": <ratio / 0.828>, ...}

Model sizes scale with the platform (tiny MLPs on CPU, ResNet50-class on
TPU), so CPU runs exercise the machinery while TPU runs defend the bar.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REFERENCE_RATIO = 1514.0 / 1828.0  # reference README.md:70-72


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--units", type=int, default=40, help="batches/epoch")
    parser.add_argument("--batch", type=int, default=None)
    parser.add_argument("--epochs", type=int, default=3)
    parser.add_argument("--teachers", type=int, default=2)
    parser.add_argument(
        "--kill_teacher", action=argparse.BooleanOptionalAction, default=True,
        help="stop one teacher mid-run (--no-kill_teacher for the "
        "no-failover baseline)",
    )
    parser.add_argument(
        "--backend", choices=("jax", "echo"), default="jax",
        help="jax = real jitted teacher model (shares this host's compute "
        "unless teachers run elsewhere); echo = near-free teacher, "
        "isolating the reader/discovery pipeline overhead",
    )
    parser.add_argument(
        "--trials", type=int, default=1,
        help="repeat the pure/distill measurement N times and report the "
        "mean ratio plus spread — a single 3-epoch run on a busy host "
        "is within noise of the bar",
    )
    parser.add_argument(
        "--student_hidden", type=int, default=128,
        help="CPU student MLP width: raises step compute intensity toward "
        "the regime the 0.83 bar was defined for (ResNet50 steps are "
        "tens of ms; a toy step makes fixed per-byte pipeline cost loom "
        "artificially large, especially on a single-core host where "
        "student and pipeline cannot overlap at all)",
    )
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from edl_tpu.distill import DistillReader, EchoPredictBackend, PredictServer
    from edl_tpu.distill.discovery import DiscoveryService, TeacherRegister
    from edl_tpu.distill.serving import JaxPredictBackend
    from edl_tpu.models import MLP, ResNet50_vd
    from edl_tpu.store.server import StoreServer
    from edl_tpu.train import create_state, make_train_step

    on_tpu = jax.devices()[0].platform != "cpu"
    batch = args.batch or (128 if on_tpu else 32)
    num_classes = 1000 if on_tpu else 100

    if on_tpu:
        student = ResNet50_vd(num_classes=num_classes)
        teacher = ResNet50_vd(num_classes=num_classes)
        shape = (224, 224, 3)
        apply_kwargs = {"train": True}
        # teacher is inference-only: BatchNorm must read running stats,
        # not try to update the (immutable outside a train step)
        # batch_stats collection
        teacher_kwargs = {"train": False}
    else:
        h = args.student_hidden
        student = MLP(hidden=(h, h), features=num_classes)
        teacher = MLP(hidden=(4 * h, 4 * h), features=num_classes)
        shape = (256,)
        apply_kwargs = None
        teacher_kwargs = {}

    rng = jax.random.PRNGKey(0)
    data = np.random.RandomState(0).randn(args.units, batch, *shape).astype(np.float32)
    labels = np.random.RandomState(1).randint(
        0, num_classes, (args.units, batch)
    ).astype(np.int64)

    def gen():
        for i in range(args.units):
            yield (data[i], labels[i])

    sample_x = jnp.asarray(data[0])

    # -- pure train --------------------------------------------------------
    def pure_loss(logits, y):
        one_hot = jax.nn.one_hot(y, num_classes)
        return optax.softmax_cross_entropy(logits, one_hot).mean(), {}

    state = create_state(student, rng, sample_x, optax.sgd(0.1, momentum=0.9))
    step = make_train_step(pure_loss, apply_kwargs, donate=False)

    from edl_tpu.data import prefetch_to_device

    def overlapped(src):
        """Host->device uploads overlapping compute — a win only where a
        real transfer exists. On CPU host == device: the extra feeder
        thread + copies just burn the shared core (measured: echo ratio
        0.72 vs 0.795 at the r4 config), so both loops stay plain there
        and the ratio remains comparable across rounds."""
        return prefetch_to_device(src, depth=2) if on_tpu else src

    def run_pure():
        s = state
        # warmup epoch (compile), then timed epochs
        for _ in range(2):
            s, m = step(s, (jnp.asarray(data[0]), jnp.asarray(labels[0])))
        # the state chain makes one scalar fetch wait for every prior step
        float(jax.device_get(m["loss"]))
        t0 = time.perf_counter()
        n = 0
        for _ in range(args.epochs):
            # same upload treatment as the distill loop — the RATIO must
            # compare pipelines, not transfer disciplines
            for x, y in overlapped(gen()):
                s, m = step(s, (jnp.asarray(x), jnp.asarray(y)))
                n += x.shape[0]
        float(jax.device_get(m["loss"]))
        return n / (time.perf_counter() - t0)

    # -- distill stack -----------------------------------------------------
    # distill step: hard CE + soft CE against teacher logits
    def distill_loss(logits, y_and_soft):
        y, t_logits = y_and_soft
        one_hot = jax.nn.one_hot(y, num_classes)
        hard = optax.softmax_cross_entropy(logits, one_hot).mean()
        soft = optax.softmax_cross_entropy(
            logits, jax.nn.softmax(t_logits)
        ).mean()
        return 0.5 * hard + 0.5 * soft, {}

    dstep_raw = make_train_step(distill_loss, apply_kwargs, donate=False)

    def make_backend():
        if args.backend == "echo":
            return EchoPredictBackend()
        t_params = teacher.init(jax.random.PRNGKey(7), sample_x)

        def t_apply(feeds):
            return {"logits": teacher.apply(t_params, feeds["img"], **teacher_kwargs)}

        return JaxPredictBackend(t_apply)

    import contextlib

    @contextlib.contextmanager
    def pipeline_stack(job):
        """The full serving stack, started: store + discovery + teachers
        + a configured DistillReader. One definition so the floor
        measurement streams exactly the pipeline being floored."""
        store = StoreServer(port=0).start()
        servers, regs = [], []
        svc = reader = None
        try:
            for _ in range(args.teachers):
                srv = PredictServer(make_backend()).start()
                servers.append(srv)
                regs.append(
                    TeacherRegister(store.endpoint, job, "teacher", srv.endpoint)
                )
            svc = DiscoveryService(store.endpoint, job, ["teacher"])
            fetchs = ("logits",) if args.backend == "jax" else ("echo_img",)
            reader = DistillReader(
                feeds=("img",), fetchs=fetchs,
                teacher_batch_size=batch, require_num=3,
                # gen() yields slices of a persistent array — no buffer
                # reuse, so the pipeline may own the rows without a
                # defensive memcpy
                copy_batches=False,
            )
            reader.set_dynamic_teacher(store.endpoint, job, "teacher")
            reader.set_batch_generator(gen)
            yield reader, servers, regs
        finally:
            if reader is not None:
                reader.stop()
            for r in regs:
                r.stop()
            if svc is not None:
                svc.stop()
            for srv in servers:
                srv.stop()
            store.stop()

    def run_distill():
        with pipeline_stack("retention") as (reader, servers, regs):
            killer = None
            if args.kill_teacher and len(servers) > 1:
                def chaos():
                    time.sleep(0.3)
                    regs[-1].stop()
                    servers[-1].stop()  # mid-run teacher death
                killer = threading.Thread(target=chaos, daemon=True)

            def consume(s, placed):
                # echo mode: teacher output is row sums, not logits — the
                # student runs its pure step (pipeline overhead is the
                # metric). jnp.asarray is a no-op on already-placed
                # device arrays (the TPU overlapped path).
                x, y, t_out = placed
                if args.backend == "jax":
                    return dstep_raw(
                        s,
                        (jnp.asarray(x), (jnp.asarray(y), jnp.asarray(t_out))),
                    )
                return step(s, (jnp.asarray(x), jnp.asarray(y)))

            def placed_epoch():
                # on TPU, batch N+1's host->device upload overlaps batch
                # N's step: without this the upload sits serialized in
                # the timed loop and inflates the above-floor gap
                return overlapped(reader())

            s = state
            # warmup epoch (compile + pipeline spin-up)
            for placed in placed_epoch():
                s, m = consume(s, placed)
            float(jax.device_get(m["loss"]))  # honest sync (see run_pure)
            if killer:
                killer.start()
            t0 = time.perf_counter()
            n = 0
            for _ in range(args.epochs):
                for placed in placed_epoch():
                    s, m = consume(s, placed)
                    n += placed[0].shape[0]
            float(jax.device_get(m["loss"]))  # honest sync (see run_pure)
            return n / (time.perf_counter() - t0)

    # -- the serialization floor -------------------------------------------
    # On a host where teachers share the student's compute (1 CPU core, or
    # colocated same-chip), the best any service pipeline can do is the
    # FULLY SERIALIZED rate: each batch pays student step + teacher
    # forward with zero overlap. Measure teacher-only throughput and
    # derive that floor, so the ratio below is interpretable — the gap
    # between measured ratio and floor is the actual machinery overhead,
    # not "distillation is slow".
    def measure_teacher_sps():
        if args.backend == "echo":
            return None  # echo teacher is ~free; the floor is ~1.0
        t_params = teacher.init(jax.random.PRNGKey(7), sample_x)

        def t_step(acc, x):
            # accumulate a scalar so the iterations form a dependency
            # chain: one final fetch then waits for every forward (each
            # t_fwd alone is independent of the others)
            logits = teacher.apply(t_params, x, **teacher_kwargs)
            return acc + jnp.sum(logits.astype(jnp.float32))

        t_fwd = jax.jit(t_step)
        acc = t_fwd(jnp.float32(0), sample_x)
        float(jax.device_get(acc))
        acc = jnp.float32(0)
        t0 = time.perf_counter()
        n = 0
        for _ in range(args.epochs):
            for x, _ in gen():
                acc = t_fwd(acc, jnp.asarray(x))
                n += x.shape[0]
        float(jax.device_get(acc))
        return n / (time.perf_counter() - t0)

    teacher_sps = measure_teacher_sps()

    def measure_reader_sps():
        """End-to-end pipeline capacity WITHOUT the student: the same
        serving stack as run_distill (shared ``pipeline_stack``),
        streamed dry. harmonic(pure, reader) is then the fully-
        serialized floor for THIS backend — socket copies, framing and
        thread handoffs included, which the teacher-only number can't
        see."""
        with pipeline_stack("retention-floor") as (reader, _srv, _regs):
            for _ in reader():  # warmup epoch (pipeline spin-up)
                pass
            t0 = time.perf_counter()
            n = 0
            for _ in range(args.epochs):
                for x, _y, _t in reader():
                    n += x.shape[0]
            return n / (time.perf_counter() - t0)

    # bracketed like pure: scheduler noise during a single window would
    # deflate the floor and with it the overhead-above-floor claim
    reader_sps = max(measure_reader_sps(), measure_reader_sps())

    # bracket the distill run with two pure measurements and keep the
    # faster one: on CPU the timed region is small enough that one-sided
    # scheduler noise can otherwise report distill "faster" than pure
    ratios, pures, distills = [], [], []
    for _ in range(max(1, args.trials)):
        pure_sps = run_pure()
        distill_sps = run_distill()
        pure_sps = max(pure_sps, run_pure())
        pures.append(pure_sps)
        distills.append(distill_sps)
        ratios.append(distill_sps / pure_sps)
    ratio = sum(ratios) / len(ratios)
    pure_sps = sum(pures) / len(pures)
    distill_sps = sum(distills) / len(distills)

    record = {
        "metric": "distill_retention",
        "value": round(ratio, 3),
        "unit": "x",
        "vs_baseline": round(ratio / REFERENCE_RATIO, 3),
        "pure_sps": round(pure_sps, 1),
        "distill_sps": round(distill_sps, 1),
        "platform": "tpu" if on_tpu else "cpu",
        "backend": args.backend,
        "teachers": args.teachers,
        "teacher_killed": bool(args.kill_teacher and args.teachers > 1),
        "batch": batch,
        "units": args.units,
        "student_hidden": args.student_hidden,
        "epochs": args.epochs,
    }
    if args.trials > 1:
        record["trials"] = [round(r, 3) for r in ratios]
        record["spread_pct"] = round(
            (max(ratios) - min(ratios)) / max(ratios) * 100, 2
        )
    if teacher_sps is not None:
        record["teacher_sps"] = round(teacher_sps, 1)
    if reader_sps:
        # fully-serialized floor on a shared core: each sample pays one
        # student step AND one trip through the serving pipeline with
        # zero overlap — harmonic combination of the two measured rates
        floor_sps = 1.0 / (1.0 / pure_sps + 1.0 / reader_sps)
        floor = floor_sps / pure_sps
        record["reader_sps"] = round(reader_sps, 1)
        record["serialized_floor"] = round(floor, 3)
        # >1.0 means the overlap machinery costs more than perfect
        # serialization; ≈1.0 means the measured ratio IS the
        # co-location floor and the machinery itself adds nothing
        record["overhead_above_floor"] = round(floor / max(ratio, 1e-9), 3)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
