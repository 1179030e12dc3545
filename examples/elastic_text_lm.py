"""LM training over the elastic data layer: dispatcher + exact resume.

The end-to-end story the reference's data layer never reached (SURVEY §2
C21/C22 — its DistributedDataReader and Go master are both non-functional
skeletons): rank 0 hosts the data dispatcher and publishes its endpoint
in the store; every worker streams its share of the file list through
``ElasticDataLoader``, packing text lines into fixed-shape token batches.
A worker that dies mid-file times out and its task is re-dispatched to a
survivor *at the exact record offset*; a joining worker starts pulling
tasks immediately — no global re-shard, no repeated or dropped records.

Under the launcher::

    python -m edl_tpu.store.server --port 2379 &
    python -m edl_tpu.launch --job_id lm --store 127.0.0.1:2379 \
        --nodes_range 1:4 examples/elastic_text_lm.py --data_dir corpus/

Standalone (single process, synthetic corpus): just run it.
"""

import argparse
import hashlib
import os
import tempfile

import numpy as np

VOCAB = 256  # byte-level tokens


def ensure_corpus(data_dir, files=4, lines_per_file=200):
    os.makedirs(data_dir, exist_ok=True)
    paths = []
    for i in range(files):
        path = os.path.join(data_dir, "part-%02d.txt" % i)
        if not os.path.exists(path):
            with open(path, "w") as f:
                for j in range(lines_per_file):
                    f.write("file %d line %d: the quick brown fox\n" % (i, j))
        paths.append(path)
    return paths


def token_batches(loader, batch, seq):
    """Pack byte-tokenized records into fixed [batch, seq] arrays (ragged
    tail dropped — static shapes for XLA)."""
    buf = []
    for _file_idx, _rec_idx, record in loader.epoch():
        tokens = np.frombuffer(record[:seq], dtype=np.uint8)
        if len(tokens) < seq:
            tokens = np.pad(tokens, (0, seq - len(tokens)))
        buf.append(tokens.astype(np.int32))
        if len(buf) == batch:
            yield np.stack(buf)
            buf = []


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--data_dir", default=None)
    parser.add_argument("--epochs", type=int, default=2)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq", type=int, default=64)
    parser.add_argument(
        "--ckpt_dir", default=None,
        help="rank 0 checkpoints model + dispatcher progress together "
        "each epoch and rewinds both on restart (the reference's rank-0 "
        "per-epoch save contract, train_with_fleet.py:563-570, plus the "
        "data offsets its WIP DataCheckpoint only sketched)",
    )
    args = parser.parse_args()

    import jax.numpy as jnp
    import optax

    from edl_tpu.data import (
        DataDispatcher,
        DispatcherClient,
        ElasticDataLoader,
        TxtFileSplitter,
        discover_dispatcher,
        publish_dispatcher,
    )
    from edl_tpu.discovery.registry import Registry
    from edl_tpu.models import TransformerLM
    from edl_tpu.store import StoreClient
    from edl_tpu.train import (
        create_state,
        cross_entropy_loss,
        init,
        make_train_step,
        worker_barrier,
    )

    env = init()
    data_dir = args.data_dir or os.path.join(
        tempfile.gettempdir(), "elastic_lm_corpus"
    )
    files = ensure_corpus(data_dir)

    dispatcher = None
    leader_client = None
    store = registry = None
    if env.store_endpoint:
        store = StoreClient(env.store_endpoint)
        registry = Registry(store, env.job_id or "lm")
    if env.is_rank0:
        # registry-backed: snapshot per mutation, recover on restart — a
        # re-elected leader resumes the epoch at the exact task offsets
        dispatcher = DataDispatcher(registry=registry).start()
        leader_client = DispatcherClient(dispatcher.endpoint, "leader")
        if leader_client.state()["files"] == 0:  # fresh job, not a recovery
            leader_client.add_dataset(files)
        if registry is not None:
            publish_dispatcher(registry, dispatcher.endpoint, ttl=5.0)
        endpoint = dispatcher.endpoint
    else:
        # liveness-probed: a dead stage's endpoint may linger until its
        # lease expires (see edl_tpu.data.discover_dispatcher)
        endpoint = discover_dispatcher(registry, timeout=60.0)

    mgr = None
    if args.ckpt_dir and env.is_rank0:
        if env.world_size > 1:
            # the example trains per-worker replicas (no global arrays), and
            # Orbax saves are collective once jax.distributed is up — the
            # sharded multi-host path is exercised in tests/test_checkpoint.py
            print("--ckpt_dir supported for single-worker runs only; skipping")
        else:
            from edl_tpu.checkpoint import CheckpointManager, TrainStatus
            from edl_tpu.data import DataCheckpoint

            mgr = CheckpointManager(args.ckpt_dir, max_to_keep=2)

    worker_barrier("data-ready")

    model = TransformerLM(
        vocab_size=VOCAB, d_model=64, num_heads=4, num_layers=2,
        d_ff=256, dtype=jnp.float32,
    )
    import jax

    tokens0 = jnp.zeros((args.batch, args.seq), jnp.int32)
    state = create_state(
        model, jax.random.PRNGKey(0), tokens0, optax.adamw(1e-3)
    )

    def lm_loss(logits, labels):
        return cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), labels.reshape(-1)
        )

    step = make_train_step(lm_loss)
    client = DispatcherClient(
        endpoint, "worker-%d-%s" % (env.global_rank, env.pod_id or "solo")
    )
    loader = ElasticDataLoader(client, TxtFileSplitter())

    if mgr is not None:
        state_r, status = mgr.restore(state)
        if status is not None:
            # one atomic restore covers model AND data position; rewinding
            # the dispatcher keeps them consistent (stop-resume exactness)
            state = state_r
            dc = DataCheckpoint.from_dict(status.meta.get("data", {}))
            leader_client.set_progress(
                dc.epoch, dc.offsets, sorted(dc.done_files)
            )
            print("rank 0 resumed from step %d epoch %d" % (status.step, status.epoch))
        else:
            # recovered dispatcher but NO checkpoint (died before the
            # first save): model restarts from scratch, so rewind the
            # data to scratch too — consistency cuts both ways
            leader_client.set_progress(0, {}, [])

    # a recovered dispatcher may already be mid-epoch N: rejoin it there
    start_epoch = client.state()["epoch"]
    digest = hashlib.sha256()
    for epoch in range(start_epoch, args.epochs):
        n = 0
        metrics = None
        for batch_tokens in token_batches(loader, args.batch, args.seq):
            digest.update(batch_tokens.tobytes())
            x = jnp.asarray(batch_tokens)
            # next-token targets without the roll-around on the last column
            state, metrics = step(state, (x[:, :-1], x[:, 1:]))
            n += 1
        if metrics is not None:
            print(
                "rank %d epoch %d: %d batches, loss %.4f"
                % (env.global_rank, epoch, n, float(metrics["loss"]))
            )
        # everyone must be drained BEFORE the leader refills the queues,
        # or a straggler would steal next epoch's tasks into this one
        worker_barrier("epoch-done-%d" % epoch)
        if env.is_rank0 and epoch + 1 < args.epochs:
            leader_client.new_epoch(epoch + 1)
        if mgr is not None:
            prog = leader_client.progress()
            dc = DataCheckpoint(
                epoch=prog["epoch"], offsets=prog["offsets"],
                done_files=prog["done"],
            )
            mgr.save(
                state,
                TrainStatus(
                    epoch=epoch + 1, step=int(state.step),
                    world_size=env.world_size,
                    meta={"data": dc.to_dict()},
                ),
                step=int(state.step),
            )
            mgr.wait()
        worker_barrier("epoch-advanced-%d" % epoch)
    print("rank %d data digest %s" % (env.global_rank, digest.hexdigest()[:12]))

    if mgr is not None:
        mgr.close()
    client.close()
    if leader_client is not None:
        leader_client.close()
    if dispatcher is not None:
        dispatcher.stop()
    if store is not None:
        store.close()


if __name__ == "__main__":
    main()
