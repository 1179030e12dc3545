"""CTR training: DeepFM with mesh-sharded embedding tables + streaming AUC.

Capability parity with the reference's CTR workload (example/ctr/ctr/
train.py — wide&deep CTR under Paddle's pserver/trainer transpiler,
reporting AUC). TPU re-design per SURVEY §2: no parameter servers — the
embedding tables shard their vocab axis over the ``mp`` mesh axis and XLA
inserts the gather collectives; the deep MLP runs bf16 on the MXU.

Synthetic Criteo-shaped data (26 sparse fields, 13 dense). Elastic run::

    python -m edl_tpu.store.server --port 2379 &
    python -m edl_tpu.launch --job_id ctr --store 127.0.0.1:2379 \
        examples/ctr_train.py
"""

import argparse

import jax
import jax.numpy as jnp
import optax

from edl_tpu.models import (
    CTR_EMBEDDING_RULES,
    DeepFM,
    binary_cross_entropy_loss,
)
from edl_tpu.parallel import make_mesh, shard_batch, shard_params_by_rules
from edl_tpu.train import (
    auc_compute,
    auc_init,
    auc_update,
    create_state,
    init,
    make_train_step,
)

FIELDS, DENSE = 26, 13


def synthetic_batch(rng, batch, vocab):
    """Criteo-shaped synthetic click data with learnable structure: the
    label depends on a few 'strong' feature ids, so AUC should rise."""
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    sparse = jax.random.randint(k1, (batch, FIELDS), 0, vocab)
    dense = jax.random.normal(k2, (batch, DENSE))
    signal = jnp.mean((sparse % 7 == 0).astype(jnp.float32), axis=1)
    logit = 3.0 * signal + 0.5 * dense[:, 0] - 1.0
    labels = (
        jax.random.uniform(k3, (batch,)) < jax.nn.sigmoid(logit)
    ).astype(jnp.int32)
    del k4
    return (sparse, dense), labels


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=200)
    parser.add_argument("--batch", type=int, default=256)
    parser.add_argument("--vocab", type=int, default=100_000)
    parser.add_argument("--embed_dim", type=int, default=16)
    args = parser.parse_args()

    env = init()
    model = DeepFM(
        vocab_size=args.vocab,
        embed_dim=args.embed_dim,
        num_fields=FIELDS,
        dense_features=DENSE,
    )
    rng = jax.random.PRNGKey(env.global_rank)
    x0, _ = synthetic_batch(rng, args.batch, args.vocab)
    state = create_state(model, jax.random.PRNGKey(0), x0, optax.adam(1e-3))

    # dp for the batch; mp shards the embedding vocab when >1 device
    n = jax.device_count()
    mp = 2 if n % 2 == 0 and n > 1 else 1
    mesh = make_mesh({"dp": -1, "mp": mp})
    # the loss head also surfaces the step's logits so the (train-)AUC
    # accumulator reuses the forward pass the gradient already paid for
    def loss_with_logits(logits, labels):
        loss, m = binary_cross_entropy_loss(logits, labels)
        return loss, {**m, "logits": logits}

    with mesh:
        state = state.replace(
            params=shard_params_by_rules(mesh, state.params, CTR_EMBEDDING_RULES)
        )
        step = make_train_step(loss_with_logits)
        update_auc = jax.jit(auc_update)
        auc_state = auc_init()
        for i in range(args.steps):
            rng, sub = jax.random.split(rng)
            x, y = synthetic_batch(sub, args.batch, args.vocab)
            batch = shard_batch(mesh, (x, y))
            state, metrics = step(state, batch)
            auc_state = update_auc(auc_state, metrics.pop("logits"), batch[1])
            if env.is_rank0 and (i + 1) % 50 == 0:
                print(
                    "step %d loss %.4f train-auc %.4f"
                    % (i + 1, float(metrics["loss"]), float(auc_compute(auc_state)))
                )
        if env.is_rank0:
            print("final train-auc %.4f" % float(auc_compute(auc_state)))


if __name__ == "__main__":
    main()
