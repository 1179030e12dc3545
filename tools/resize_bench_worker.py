"""Instrumented elastic training worker for the resize-cost benchmark.

A REAL collective train job (jitted SPMD step, dp mesh over every global
device, multi-process via ``jax.distributed``) that feeds the stage
telemetry: per-stage ``first_step`` events and steady-state samples/s
meters (``edl_tpu/utils/telemetry.py``). The launcher kills and respawns
it across resizes; each incarnation measures its own stage.

Model scales with the platform: ImageNet-shaped ResNet50_vd on TPU, a
tiny ResNet on CPU so transition timing dominates compile time, not
FLOPs. Runs ``--steps`` steps then exits 0 (the job completes when every
stage's budget is spent) or forever if ``--steps 0``.
"""

import argparse
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=0, help="0 = run forever")
    parser.add_argument("--batch_per_worker", type=int, default=None)
    args = parser.parse_args()

    from edl_tpu.train import (
        create_state, cross_entropy_loss, init, make_train_step,
    )
    from edl_tpu.utils.telemetry import WorkerMeter

    env = init()

    import jax
    import jax.numpy as jnp
    import optax

    from edl_tpu.models import MLP, ResNet50_vd
    from edl_tpu.parallel import make_mesh, shard_batch

    on_tpu = jax.devices()[0].platform != "cpu"
    batch_per_worker = args.batch_per_worker or (128 if on_tpu else 32)

    # LOCAL-rows contract (shard_batch/device_put_local_rows): each
    # process contributes ITS batch_per_worker rows; the global batch is
    # their concatenation (batch_per_worker * world). Rank-seeded so
    # workers feed distinct rows.
    local_batch = batch_per_worker
    rng = jax.random.PRNGKey(env.global_rank)
    if on_tpu:
        model = ResNet50_vd(num_classes=1000)
        num_classes = 1000
        x = jax.random.normal(rng, (local_batch, 224, 224, 3), jnp.float32)
        apply_kwargs = {"train": True}
    else:  # flat MLP: compile stays in seconds even on one CPU core
        num_classes = 100
        model = MLP(hidden=(256, 256), features=num_classes)
        x = jax.random.normal(rng, (local_batch, 256), jnp.float32)
        apply_kwargs = None
    y = jax.random.randint(rng, (local_batch,), 0, num_classes)

    mesh = make_mesh({"dp": -1})
    # Params MUST be identical across processes (same cross-process value
    # contract as examples/resnet_collective.py): constant seed for init,
    # keeping the rank-seeded key only for the data above.
    state = create_state(model, jax.random.PRNGKey(0), x, optax.sgd(0.1, momentum=0.9))
    step = make_train_step(cross_entropy_loss, apply_kwargs)
    meter = WorkerMeter(env, batch_per_step=batch_per_worker)

    from edl_tpu.train import aot
    from edl_tpu.utils.telemetry import record_cache_stats, record_event

    ladder = None
    with mesh:
        from edl_tpu.parallel import device_put_global, replicated

        # mesh-place the state BEFORE the first step (loop.py's contract):
        # every stage then compiles exactly ONE step executable — the
        # steady-state one the AOT ladder pre-compiles for its neighbors —
        # instead of a host-placed variant followed by a mesh-sharded one
        rep = replicated(mesh)
        state = jax.tree.map(lambda s: device_put_global(s, rep), state)
        batch = shard_batch(mesh, (x, y))
        # 'ready' splits the restage lane for analyze(): publish ->
        # ready is process+import+init+state build ("restore"),
        # ready -> first_step is the jit (compile or cache load)
        client = meter._store()
        if client is not None:
            record_event(
                client, env.job_id, env.stage, "ready",
                "w%d" % env.global_rank,
            )
        import time as _time

        from edl_tpu.obs import events as obs_events
        from edl_tpu.obs import goodput as obs_goodput

        last_flight = 0.0
        if os.environ.get("EDL_DEBUG_STEP_HLO") == "1":
            # cache-debug probe: identical shas across two workers mean
            # their step executables share persistent-cache keys up to
            # compile options
            import hashlib
            text = step.lower(state, batch).as_text()
            print("step-hlo sha=%s len=%d world=%d" % (
                hashlib.sha256(text.encode()).hexdigest()[:16],
                len(text), env.world_size))
        k = 0
        while args.steps == 0 or k < args.steps:
            state, metrics = step(state, batch)
            # a per-step fetch: the metered sps must count finished
            # steps, not dispatched ones
            float(jax.device_get(metrics["loss"]))
            # goodput: the first step closes the restage interval
            # context.init opened (init -> first step IS the restage
            # lane this bench measures); the throttled heartbeat
            # bounds a SIGKILLed incarnation's open train interval
            # to <= 1 s (loop.py's idiom) — so an archived bench
            # run's flight segments attribute wall-clock like a real
            # job's and edl_report --diff names the restage lane,
            # not "down"
            if k == 0:
                obs_goodput.enter("train", cause="first_step")
            now = _time.monotonic()
            if now - last_flight >= 1.0:
                last_flight = now
                obs_events.record("train_heartbeat", step=k)
            if k == 0:
                # first step done: publish this stage's cache ledger
                # (hit = loaded a speculated/peer-compiled executable,
                # miss+write = paid a real compile) and arm the AOT
                # ladder for the neighbor worlds
                client = meter._store()
                if client is not None:
                    record_cache_stats(
                        client, env.job_id, env.stage, env.global_rank,
                        aot.cache_event_counts(),
                    )
                if aot.aot_enabled() and env.compile_cache_dir:
                    try:
                        ladder = aot.AotLadder(
                            env,
                            aot.make_neighbor_compiler(
                                step, state, batch, {"dp": -1},
                                devices_per_proc=aot.devices_per_process(env),
                            ),
                        ).start()
                    except Exception as exc:  # noqa: BLE001
                        print("aot ladder unavailable: %s" % exc)
            meter.step()
            k += 1
    meter.close()
    obs_goodput.close(cause="bench_done")
    if ladder is not None:
        ladder.close()
    if env.is_rank0:
        print("bench worker done: %d steps, %.1f samples/s/worker"
              % (k, meter.samples_per_s() or 0.0))


if __name__ == "__main__":
    main()
