"""ResNet50_vd elastic collective training — the flagship benchmark job.

Capability parity with the reference's headline workload
(example/collective/resnet50/train_with_fleet.py: fleet init from env →
build program → load checkpoint → epoch loop → rank-0 save), re-built
TPU-first: a dp×fsdp mesh instead of NCCL allreduce flags, Orbax sharded
checkpoints instead of HDFS files (resume works across *different* world
sizes — the mesh is rebuilt and Orbax reshards), and the lr re-adjustment
on resize expressed through the AdjustRegistry hook (the reference only
sketches this in test_train.py's ``register_adjust_function``).

Synthetic ImageNet-shaped data by default; shapes shrink automatically
off-TPU so the script smoke-runs anywhere. Elastic run::

    python -m edl_tpu.store.server --port 2379 &
    python -m edl_tpu.harness.resize --store 127.0.0.1:2379 --job_id rn50 \
        --schedule 2,4,2 --interval 120 -- examples/resnet_collective.py
"""

import argparse
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import optax

from edl_tpu.checkpoint import (
    AdjustRegistry,
    CheckpointManager,
    TrainStatus,
    linear_scaled_lr,
)
from edl_tpu.data import batched, prefetch_to_device
from edl_tpu.models import ResNet50_vd
from edl_tpu.parallel import (
    batch_sharding,
    device_put_global,
    make_mesh,
    replicated,
    shard_params_fsdp,
)
from edl_tpu.train import (
    create_state,
    init,
    make_cross_entropy_loss,
    make_train_step,
    worker_barrier,
)

adjusts = AdjustRegistry()


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--epochs", type=int, default=3)
    parser.add_argument("--steps_per_epoch", type=int, default=10)
    parser.add_argument("--base_lr", type=float, default=0.1)
    parser.add_argument("--batch_per_worker", type=int, default=None)
    args = parser.parse_args()

    env = init()
    on_tpu = jax.devices()[0].platform != "cpu"
    batch = args.batch_per_worker or (128 if on_tpu else 8)
    size = 224 if on_tpu else 32

    # lr scales linearly with world size, re-resolved every (re)start —
    # the elastic hyper-parameter adjustment contract
    adjusts.register(linear_scaled_lr(args.base_lr, base_world_size=1))

    model = ResNet50_vd(num_classes=1000)
    # constant seed: params must INIT IDENTICALLY on every process (the
    # cross-process placement helpers assemble global params assuming the
    # same host value everywhere); per-worker data divergence comes from
    # the rank term in records(), not from init
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (batch, size, size, 3), jnp.float32)

    ckpt_dir = env.ckpt_path or os.path.join(tempfile.gettempdir(), "rn50_ckpt")
    mesh = make_mesh({"dp": -1, "fsdp": 1})
    with CheckpointManager(ckpt_dir) as mngr, mesh:
        resolved = adjusts.resolve(None, env.world_size)
        lr = resolved.get("lr", args.base_lr)
        state = create_state(
            model, rng, x, optax.sgd(lr, momentum=0.9, nesterov=True)
        )
        rep = replicated(mesh)
        state = state.replace(
            params=shard_params_fsdp(mesh, state.params),
            opt_state=shard_params_fsdp(mesh, state.opt_state),
            # remaining leaves (step scalar, BN stats) must land on the
            # mesh too — a leaf committed to device 0 clashes with
            # mesh-placed args at jit time in multi-worker stages
            step=device_put_global(state.step, rep),
            batch_stats=jax.tree.map(
                lambda v: device_put_global(v, rep), state.batch_stats
            ),
        )
        state, status = mngr.restore(state)
        start_epoch = status.next_epoch() if status else 0
        if env.is_rank0 and status:
            print(
                "resumed at epoch %d (world=%d, lr=%.4f)"
                % (start_epoch, env.world_size, lr)
            )

        # acc1 + acc5, the reference table metrics (README.md:70)
        step = make_train_step(make_cross_entropy_loss(5), {"train": True})

        def records(epoch):
            # pass_id-as-seed (reference train_with_fleet.py:458-464):
            # for a FIXED world size, the (epoch, rank) seed makes every
            # epoch's stream deterministic, so an epoch-boundary resume
            # replays the exact data the killed job would have seen; a
            # resized job reshuffles (as the reference's does when its
            # file shards are re-dealt), which is why resumes happen at
            # epoch boundaries
            rs = np.random.RandomState(1000 * (epoch + 1) + env.global_rank)
            for _ in range(args.steps_per_epoch * batch):
                img = rs.standard_normal((size, size, 3)).astype(np.float32)
                yield img, np.int64(rs.randint(1000))

        sharding = batch_sharding(mesh, "dp")
        worker_barrier("train-start")
        for epoch in range(start_epoch, args.epochs):
            # input pipeline: fixed-shape host batches, transfers kept in
            # flight behind the step (depth=2 double buffering)
            src = (
                b for b, _ in batched(records(epoch), batch, drop_remainder=True)
            )
            for device_batch in prefetch_to_device(src, depth=2, sharding=sharding):
                state, metrics = step(state, device_batch)
            jax.block_until_ready(metrics["loss"])
            if env.is_rank0:
                print(
                    "epoch %d loss %.4f acc %.3f"
                    % (epoch, float(metrics["loss"]), float(metrics["accuracy"]))
                )
            # collective: every process writes its shards, Orbax finalizes
            mngr.save(state, TrainStatus(epoch=epoch, step=int(state.step)))
        mngr.wait()


if __name__ == "__main__":
    main()
