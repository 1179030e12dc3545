"""Minimal end-to-end job: linear regression under the elastic launcher.

The smallest runnable slice (≙ reference example/fit_a_line — its smoke
workload), now expressed through the high-level ``ElasticTrainer``: one
constructor + one ``fit`` call covers env join, mesh build, checkpoint
restore/save, device-prefetched input, stage barrier, and rank-0 logs.
(See examples/resnet_collective.py for the same loop hand-assembled from
the primitives.) Run standalone::

    python examples/fit_a_line.py

or elastically (any pod count; kill/add pods mid-run)::

    python -m edl_tpu.store.server --port 2379 &
    python -m edl_tpu.launch --job_id fit --store 127.0.0.1:2379 \
        --nodes_range 1:4 examples/fit_a_line.py
"""

import argparse
import os
import tempfile

import numpy as np
import optax

from edl_tpu.models import LinearRegression
from edl_tpu.train import ElasticTrainer, mse_loss

D = 13


def records(epoch):
    """Epoch+rank-seeded synthetic stream: resumes replay the exact order
    a killed run would have seen (pass_id-as-seed), and each worker feeds
    DISTINCT rows (local-rows contract: the global batch concatenates
    every worker's rows)."""
    from edl_tpu.train.context import current_env

    rs = np.random.RandomState(1000 * (epoch + 1) + current_env().global_rank)
    w = np.arange(1.0, D + 1.0, dtype=np.float32)
    for _ in range(1024):
        x = rs.randn(D).astype(np.float32)
        y = np.float32(x @ w + 0.1 * rs.randn())
        yield x, np.asarray([y], np.float32)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--epochs", type=int, default=10)
    parser.add_argument("--batch", type=int, default=128)
    args = parser.parse_args()

    ckpt_dir = os.environ.get("EDL_CKPT_PATH") or os.path.join(
        tempfile.gettempdir(), "fit_a_line_ckpt"
    )
    trainer = ElasticTrainer(
        LinearRegression(features=1),
        optax.sgd(1e-2),
        mse_loss,
        # numpy on purpose: device arrays built before fit() would
        # initialise the backend ahead of jax.distributed in
        # multi-worker stages
        sample_input=np.zeros((args.batch, D), np.float32),
        batch_size=args.batch,
        ckpt_dir=ckpt_dir,
    )
    state = trainer.fit(records, epochs=args.epochs)
    from edl_tpu.train.context import current_env

    if current_env().is_rank0:
        print("done at step %d" % int(state.step))


if __name__ == "__main__":
    main()
