"""Multi-process ElasticTrainer.evaluate worker (ragged final batch).

Spawned by the launcher as a real 2-process jax.distributed stage: builds
a deterministic initial state (fit with epochs=0 only places it on the
mesh — no training, so every rank and any world size holds identical
params), then runs ``evaluate`` over a record stream whose tail batch is
ragged. The masked static-shape eval path (train/step.py) must hold
under cross-process collectives — the round-2 advisor's shape-divergence
scenario — and every rank must report the same global metrics.

Each rank writes its metrics to ``$TEST_OUT_DIR/eval.<rank>.json``.
"""

import json
import os

import numpy as np
import optax

from edl_tpu.models import MLP
from edl_tpu.train import ElasticTrainer, cross_entropy_loss

out_dir = os.environ["TEST_OUT_DIR"]
rank = os.environ.get("EDL_WORKER_RANK", "0")

N_RECORDS = 20  # per process; batch 8 -> 2 full batches + ragged 4


def records():
    rs = np.random.RandomState(7)  # same stream on every rank: uniform
    # duplication across dp groups preserves the weighted metric mean
    for _ in range(N_RECORDS):
        yield rs.randn(8).astype(np.float32), rs.randint(0, 4)


trainer = ElasticTrainer(
    MLP(hidden=(16,), features=4),
    optax.sgd(0.05),
    cross_entropy_loss,
    sample_input=np.zeros((8, 8), np.float32),
    batch_size=8,
    log=False,
)
state = trainer.fit(lambda epoch: iter(()), epochs=0)
metrics = trainer.evaluate(state, records)
with open(os.path.join(out_dir, "eval.%s.json" % rank), "w") as f:
    json.dump({k: float(v) for k, v in metrics.items()}, f)
