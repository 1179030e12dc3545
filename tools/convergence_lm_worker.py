"""Char-LM convergence worker: churn that actually perturbs data order.

The digits workload (tools/convergence_worker.py) is world-size-invariant
by construction — every stage sees identical global batches, so its
0.0pp gap proves stop-resume mechanics, not robustness to perturbed
data. THIS worker feeds a byte-level TransformerLM through the elastic
data layer (``DataDispatcher`` + ``ElasticDataLoader`` mid-file task
offsets): workers PULL uneven record shares whose assignment depends on
world size and timing, so a churn schedule provably changes which rows
land in which global batch (the driver asserts the batch digests differ
between static and churn runs) — the scaled analogue of the reference's
ResNet50-under-900s-churn accuracy claim (README.md:144-147).

Global sync-SGD over uneven shares rides ``make_masked_train_step``:
each epoch the workers drain their dispatcher share into memory,
agree on the global step count through the store, and pad+mask their
tail batches — one static shape, one collective schedule, gradients
equal to plain sync-SGD over exactly the valid rows.

Per-incarnation markers: ``inc.<stage>.<rank>.<world>`` containing the
resume step and rows consumed; rank 0 writes ``digest.<stage>.<epoch>``
per epoch (sha256 over the epoch's global batch stream) and
``final.json`` with held-out next-char accuracy.
"""

import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = os.environ["TEST_OUT_DIR"]
DATA_DIR = os.environ["TEST_DATA_DIR"]
EPOCHS = int(os.environ.get("TEST_EPOCHS", "6"))
GLOBAL_BATCH = int(os.environ.get("TEST_GLOBAL_BATCH", "36"))
SEQ = int(os.environ.get("TEST_SEQ", "48"))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from edl_tpu.checkpoint import CheckpointManager, TrainStatus
    from edl_tpu.cluster.job_env import WorkerEnv
    from edl_tpu.data import (
        DataCheckpoint,
        DataDispatcher,
        DispatcherClient,
        ElasticDataLoader,
        TxtFileSplitter,
        discover_dispatcher,
        publish_dispatcher,
    )
    from edl_tpu.discovery.registry import Registry
    from edl_tpu.models import TransformerLM
    from edl_tpu.parallel import (
        device_put_global, make_mesh, replicated, shard_batch,
    )
    from edl_tpu.store import StoreClient
    from edl_tpu.train import (
        create_state,
        cross_entropy_loss,
        init,
        make_masked_train_step,
        worker_barrier,
    )
    from edl_tpu.train.step import make_masked_eval_step

    pre = WorkerEnv()
    env = init()
    world = max(env.world_size, 1)
    rank = env.global_rank
    assert GLOBAL_BATCH % world == 0, (GLOBAL_BATCH, world)
    local_batch = GLOBAL_BATCH // world

    store = StoreClient(env.store_endpoint)
    registry = Registry(store, env.job_id or "convlm")

    # -- data plane: rank 0 hosts the dispatcher, everyone pulls ----------
    train_files = sorted(
        os.path.join(DATA_DIR, f)
        for f in os.listdir(DATA_DIR)
        if f.startswith("part-")
    )
    dispatcher = leader_client = None
    if env.is_rank0:
        dispatcher = DataDispatcher(registry=registry).start()
        leader_client = DispatcherClient(dispatcher.endpoint, "leader")
        if leader_client.state()["files"] == 0:
            leader_client.add_dataset(train_files)
        publish_dispatcher(registry, dispatcher.endpoint, ttl=2.0)
        endpoint = dispatcher.endpoint
    else:
        # liveness-probed: a dead stage's endpoint may linger until its
        # lease expires, and adopting it would crash-loop this stage
        endpoint = discover_dispatcher(registry, timeout=60.0)

    # -- model on the dp mesh ---------------------------------------------
    mesh = make_mesh({"dp": -1})
    model = TransformerLM(
        vocab_size=256, d_model=48, num_heads=4, num_layers=2,
        d_ff=128, dtype=jnp.float32,
    )
    tokens0 = np.zeros((local_batch, SEQ), np.int32)
    state = create_state(
        model, jax.random.PRNGKey(0), tokens0, optax.adamw(3e-3)
    )
    rep = replicated(mesh)
    state = jax.tree.map(lambda x: device_put_global(x, rep), state)
    tstep = make_masked_train_step(cross_entropy_loss, donate=False)
    estep = make_masked_eval_step(cross_entropy_loss)

    mgr = CheckpointManager(os.environ["EDL_CKPT_PATH"], max_to_keep=2)
    client = DispatcherClient(endpoint, "worker-%d-%s" % (rank, env.pod_id or "solo"))
    loader = ElasticDataLoader(client, TxtFileSplitter())

    start_epoch = 0
    state_r, status = mgr.restore(state)
    if status is not None:
        state = state_r
        start_epoch = status.epoch
        if env.is_rank0:
            dc = DataCheckpoint.from_dict(status.meta.get("data", {}))
            leader_client.set_progress(dc.epoch, dc.offsets, sorted(dc.done_files))
    elif env.is_rank0:
        # NO checkpoint but a RECOVERED dispatcher (kill before the first
        # save): the model restarts from scratch, so the data must too —
        # leaving the dispatcher mid-epoch 0 would hide the already-
        # consumed rows from the fresh model (observed: one epoch's worth
        # of steps silently missing from the churn run)
        leader_client.set_progress(0, {}, [])
    worker_barrier("data-ready")

    marker = "inc.%s.%d.%d" % (pre.stage or "solo", rank, world)
    with open(os.path.join(OUT, marker), "w") as f:
        f.write(json.dumps({"resume_step": int(state.step),
                            "resume_epoch": start_epoch}))

    def row_to_tokens(record: bytes) -> np.ndarray:
        t = np.frombuffer(record[: SEQ + 1], dtype=np.uint8)
        if len(t) < SEQ + 1:
            t = np.pad(t, (0, SEQ + 1 - len(t)))
        return t.astype(np.int32)

    def agree_steps(epoch: int, n_rows: int) -> int:
        """All ranks publish their local row counts for this (stage,
        epoch) and take the max step count — so every process runs the
        same number of collective steps even with uneven shares."""
        svc = "convsteps/%s:%d" % (env.stage or "solo", epoch)
        registry.register(svc, str(rank), str(n_rows).encode(), ttl=120.0)
        deadline = time.time() + 120
        while time.time() < deadline:
            entries = registry.get_service(svc)
            if len(entries) >= world:
                counts = [int(e.value.decode()) for e in entries]
                import math
                return max(
                    math.ceil(c / max(local_batch, 1)) for c in counts
                )
            time.sleep(0.1)
        raise RuntimeError("step agreement timed out")

    digest_all = hashlib.sha256()
    start_epoch = client.state()["epoch"]  # a recovered dispatcher may be mid-epoch
    for epoch in range(start_epoch, EPOCHS):
        rows = [row_to_tokens(rec) for _f, _r, rec in loader.epoch()]
        steps = agree_steps(epoch, len(rows))
        epoch_digest = hashlib.sha256()
        # row->global-step assignment in a world- and stage-independent
        # form: "<epoch> <rowhash> <step>" lines. The driver compares the
        # sorted union across ranks/stages between the static and churn
        # runs — equal multisets would mean churn did NOT perturb which
        # rows shared a batch; different ones are the perturbation proof.
        pair_lines = []
        metrics = None
        for s in range(steps):
            chunk = rows[s * local_batch : (s + 1) * local_batch]
            for row in chunk:
                pair_lines.append(
                    "%d %s %d"
                    % (epoch,
                       hashlib.sha256(row.tobytes()).hexdigest()[:12], s)
                )
            mask = np.zeros((local_batch,), bool)
            mask[: len(chunk)] = True
            while len(chunk) < local_batch:
                chunk.append(np.zeros(SEQ + 1, np.int32))
            t = np.stack(chunk)
            epoch_digest.update(t.tobytes())
            placed = shard_batch(mesh, (t[:, :-1], t[:, 1:]))
            placed_mask = shard_batch(mesh, mask)
            with mesh:
                state, metrics, _n = tstep(state, placed, placed_mask)
        if metrics is not None:
            jax.block_until_ready(metrics["loss"])
        digest_all.update(epoch_digest.digest())
        with open(
            os.path.join(OUT, "pairs.%s.%d.%d" % (
                pre.stage or "solo", rank, epoch)), "w",
        ) as f:
            f.write("\n".join(pair_lines))
        # drain BEFORE the leader refills, or a straggler steals tasks
        worker_barrier("epoch-done-%d" % epoch)
        if env.is_rank0 and epoch + 1 < EPOCHS:
            leader_client.new_epoch(epoch + 1)
        prog = None
        if env.is_rank0:
            prog = leader_client.progress()
        dc = DataCheckpoint(
            epoch=prog["epoch"] if prog else epoch + 1,
            offsets=prog["offsets"] if prog else {},
            done_files=prog["done"] if prog else [],
        )
        mgr.save(
            state,
            TrainStatus(
                epoch=epoch + 1, step=int(state.step), world_size=world,
                meta={"data": dc.to_dict()},
            ),
            step=int(state.step),
        )
        mgr.wait()
        worker_barrier("epoch-advanced-%d" % epoch)

    # -- held-out eval: every rank covers eval rows [rank::world] ----------
    with open(os.path.join(DATA_DIR, "heldout.txt"), "rb") as f:
        eval_rows = [
            row_to_tokens(line) for line in f.read().splitlines()
            if len(line) >= SEQ + 1
        ]
    mine = eval_rows[rank::world]
    import math
    esteps = agree_steps(10_000, len(mine))
    loss_sum = acc_sum = n_sum = 0.0
    for s in range(esteps):
        chunk = mine[s * local_batch : (s + 1) * local_batch]
        mask = np.zeros((local_batch,), bool)
        mask[: len(chunk)] = True
        while len(chunk) < local_batch:
            chunk.append(np.zeros(SEQ + 1, np.int32))
        t = np.stack(chunk)
        placed = shard_batch(mesh, (t[:, :-1], t[:, 1:]))
        placed_mask = shard_batch(mesh, mask)
        with mesh:
            m, n_valid = estep(state, placed, placed_mask)
        n = float(np.asarray(n_valid))
        loss_sum += float(np.asarray(m["loss"])) * n
        acc_sum += float(np.asarray(m["accuracy"])) * n
        n_sum += n
    if env.is_rank0:
        with open(os.path.join(OUT, "final.json"), "w") as f:
            json.dump(
                {
                    "test_accuracy": acc_sum / max(n_sum, 1.0),
                    "test_loss": loss_sum / max(n_sum, 1.0),
                    "eval_rows": int(n_sum),
                    "steps": int(state.step),
                    "epochs": EPOCHS,
                    "world_at_finish": world,
                    "batch_digest": digest_all.hexdigest(),
                },
                f,
            )

    mgr.close()
    client.close()
    loader  # keep referenced
    if leader_client is not None:
        leader_client.close()
    if dispatcher is not None:
        dispatcher.stop()
    store.close()


if __name__ == "__main__":
    main()
