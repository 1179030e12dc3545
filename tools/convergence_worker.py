"""Convergence worker: real-data training through the full elastic stack.

Launched under ``edl_tpu.launch`` by ``tools/convergence_churn.py``. Trains
an MLP classifier on scikit-learn's digits dataset (1797 real 8x8
handwritten-digit scans — the in-image-classification, no-egress analogue
of the reference's ImageNet runs, reference README.md:144-147) via
``ElasticTrainer``: per-epoch Orbax checkpointing, stop-resume across
resizes, epoch-seeded deterministic shuffling (the reference's
``pass_id_as_seed`` contract, train_with_fleet.py:458-464).

The GLOBAL batch is fixed (``TEST_GLOBAL_BATCH``); each incarnation takes
``global/world`` rows per process from its ``[rank::world]`` shard, so the
optimization trajectory is world-size-invariant up to record order — the
property that makes "churn must not change the final metric" a fair
assert. After training, every rank joins a sharded evaluate() over the
held-out split and rank 0 writes ``final.json``.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = os.environ["TEST_OUT_DIR"]
EPOCHS = int(os.environ.get("TEST_EPOCHS", "40"))
GLOBAL_BATCH = int(os.environ.get("TEST_GLOBAL_BATCH", "56"))
EPOCH_PAUSE = float(os.environ.get("TEST_EPOCH_PAUSE", "0"))


def main():
    import numpy as np
    import optax
    from sklearn.datasets import load_digits

    from edl_tpu.cluster.job_env import WorkerEnv
    from edl_tpu.models import MLP
    from edl_tpu.train import (
        ElasticTrainer, current_env, init, make_cross_entropy_loss,
    )

    # incarnation marker FIRST (before the jax.distributed bootstrap, which
    # can outlive a short-lived stage): the driver counts distinct stages =
    # cluster generations this job actually ran under, proving churn landed
    pre = WorkerEnv()
    marker = "inc.%s.%d.%d" % (pre.stage or "solo", pre.global_rank, pre.world_size)
    with open(os.path.join(OUT, marker), "w") as f:
        f.write("1")

    env = init()
    world = max(env.world_size, 1)
    rank = env.global_rank
    assert GLOBAL_BATCH % world == 0, (GLOBAL_BATCH, world)
    local_batch = GLOBAL_BATCH // world

    digits = load_digits()
    x = (digits.data / 16.0).astype(np.float32)  # [1797, 64] in [0, 1]
    y = digits.target.astype(np.int32)
    split = np.random.RandomState(0).permutation(len(x))
    # 1344 = 24 * GLOBAL_BATCH(56), and divisible by every scheduled world
    # size (1..4): every epoch is exactly 24 full global steps with zero
    # records dropped, for any world — step counts agree across processes
    # in every stage and the trajectory is world-size-invariant
    n_train = 1344
    assert n_train % GLOBAL_BATCH == 0
    train_idx, test_idx = split[:n_train], split[n_train : n_train + 360]

    def train_records(epoch):
        order = np.random.RandomState(1000 + epoch).permutation(train_idx)
        shard = order[rank::world]
        for i in shard:
            yield (x[i], y[i])

    def test_records():
        for i in test_idx[rank::world]:
            yield (x[i], y[i])

    def on_epoch_end(epoch, _metrics):
        if EPOCH_PAUSE:
            time.sleep(EPOCH_PAUSE)  # stretch the run so churn lands mid-training

    trainer = ElasticTrainer(
        MLP(hidden=(64,), features=10),
        optax.sgd(0.1, momentum=0.9),
        make_cross_entropy_loss(),
        sample_input=np.zeros((1, 64), np.float32),
        batch_size=local_batch,
        ckpt_dir=os.environ["EDL_CKPT_PATH"],
        seed=0,
        log=False,
    )
    state = trainer.fit(train_records, epochs=EPOCHS, on_epoch_end=on_epoch_end)
    metrics = trainer.evaluate(state, test_records)
    if current_env().is_rank0:
        with open(os.path.join(OUT, "final.json"), "w") as f:
            json.dump(
                {
                    "test_accuracy": metrics.get("accuracy"),
                    "test_loss": metrics.get("loss"),
                    "steps": int(state.step),
                    "epochs": EPOCHS,
                    "world_at_finish": world,
                },
                f,
            )


if __name__ == "__main__":
    main()
