"""Distillation student: train ResNet against discovered teacher fleet.

Capability parity with the reference's flagship service-distill workload
(README.md:72 — ResNeXt teachers on separate GPUs feeding ResNet50_vd
students at 1514 img/s): the student's ``DistillReader`` streams batches
through the teacher fleet (discovered live from the store; teachers can
join/leave mid-epoch) and the train step distills on the returned
``soft_label`` alongside the hard labels.

    python -m edl_tpu.store.server --port 2379 &
    python -m edl_tpu.distill.discovery_server --store 127.0.0.1:2379 &
    python examples/distill_teacher.py --store 127.0.0.1:2379 --small &
    python examples/distill_student.py --store 127.0.0.1:2379 --small
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax

from edl_tpu.distill import DistillReader
from edl_tpu.models import ResNet, ResNet50_vd
from edl_tpu.train import create_state, init, make_train_step


def distill_loss(logits, targets):
    """targets = (hard_label, soft_label): CE + KL to teacher."""
    hard, soft = targets
    log_p = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(
        jnp.take_along_axis(log_p, hard[:, None], axis=-1)
    )
    kl = jnp.mean(jnp.sum(soft * (jnp.log(soft + 1e-8) - log_p), axis=-1))
    accuracy = (jnp.argmax(logits, -1) == hard).mean()
    return ce + kl, {"accuracy": accuracy, "kl": kl}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--store", required=True)
    parser.add_argument("--job_id", default="distill")
    parser.add_argument("--service", default="teacher")
    parser.add_argument("--epochs", type=int, default=2)
    parser.add_argument("--batch", type=int, default=32)
    parser.add_argument("--small", action="store_true", help="tiny CPU model")
    args = parser.parse_args()

    env = init()
    if args.small:
        model = ResNet(stage_sizes=(1, 1), num_classes=10, width=8)
        size, classes = 32, 10
    else:
        model = ResNet50_vd(num_classes=1000)
        size, classes = 224, 1000

    rng = np.random.RandomState(env.global_rank)

    def sample_generator():
        for _ in range(args.batch * 8):
            image = rng.randn(size, size, 3).astype(np.float32)
            label = np.int64(rng.randint(classes))
            yield image, label

    reader = DistillReader(
        feeds=["image", "label"],
        fetchs=["soft_label"],
        teacher_batch_size=args.batch,
    )
    reader.set_dynamic_teacher(args.store, args.job_id, args.service)
    reader.set_sample_generator(sample_generator)

    x0 = jnp.zeros((args.batch, size, size, 3), jnp.float32)
    state = create_state(
        model, jax.random.PRNGKey(0), x0, optax.sgd(0.01, momentum=0.9)
    )
    step = make_train_step(distill_loss, {"train": True})

    try:
        for epoch in range(args.epochs):
            for batch in _batched(reader(), args.batch):
                images, labels, soft = batch
                state, metrics = step(
                    state, (images, (labels, soft))
                )
            print(
                "epoch %d loss %.4f acc %.3f kl %.4f"
                % (
                    epoch,
                    float(metrics["loss"]),
                    float(metrics["accuracy"]),
                    float(metrics["kl"]),
                )
            )
    finally:
        reader.stop()


def _batched(stream, batch_size):
    """Group (image, label, soft_label) samples into fixed-size jnp batches;
    drops the ragged tail (static shapes keep XLA recompilation away)."""
    images, labels, softs = [], [], []
    for sample in stream:
        image, label, soft = sample
        images.append(image)
        labels.append(label)
        softs.append(soft)
        if len(images) == batch_size:
            yield (
                jnp.asarray(np.stack(images)),
                jnp.asarray(np.asarray(labels, np.int32)),
                jnp.asarray(np.stack(softs)),
            )
            images, labels, softs = [], [], []


if __name__ == "__main__":
    main()
