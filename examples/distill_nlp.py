"""NLP distillation: a TransformerLM teacher distills a smaller student.

Capability parity with the reference's NLP distill example
(example/distill/nlp — an ERNIE teacher served via Paddle Serving feeding
a lighter student for sentence classification): here both sides are
TransformerLMs; the teacher serves per-token soft distributions from its
final layer, the student (half the depth/width) trains on pure
soft-target KL. Teacher and student run as separate processes so the
teacher fleet scales independently.

    python -m edl_tpu.store.server --port 2379 &
    python -m edl_tpu.distill.discovery_server --store 127.0.0.1:2379 &
    python examples/distill_nlp.py --role teacher --store 127.0.0.1:2379 &
    python examples/distill_nlp.py --role student --store 127.0.0.1:2379
"""

import argparse
import signal
import threading

import numpy as np

VOCAB = 1024
SEQ = 64


def build_lm(num_layers, d_model, rng_seed=0):
    import jax
    import jax.numpy as jnp
    import optax

    from edl_tpu.models import TransformerLM
    from edl_tpu.train import create_state

    model = TransformerLM(
        vocab_size=VOCAB, d_model=d_model, num_heads=4,
        num_layers=num_layers, d_ff=4 * d_model, dtype=jnp.float32,
    )
    tokens = jnp.zeros((1, SEQ), jnp.int32)
    state = create_state(
        model, jax.random.PRNGKey(rng_seed), tokens, optax.adamw(3e-4)
    )
    return model, state


def run_teacher(args):
    import jax

    from edl_tpu.distill import JaxPredictBackend, PredictServer
    from edl_tpu.distill.discovery import TeacherRegister

    model, state = build_lm(num_layers=4, d_model=128)

    def apply(feeds):
        logits = model.apply({"params": state.params}, feeds["tokens"])
        return {"soft_label": jax.nn.softmax(logits, axis=-1)}

    server = PredictServer(JaxPredictBackend(apply), port=args.port).start()
    print("nlp teacher serving on %s" % server.endpoint)
    reg = TeacherRegister(args.store, args.job_id, args.service, server.endpoint)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    stop.wait()
    reg.stop()
    server.stop()


def run_student(args):
    import jax
    import jax.numpy as jnp

    from edl_tpu.distill import DistillReader
    from edl_tpu.train import init, make_train_step

    init()
    model, state = build_lm(num_layers=2, d_model=64, rng_seed=1)

    rng = np.random.RandomState(0)

    def batches():
        for _ in range(args.batches):
            tokens = rng.randint(0, VOCAB, (args.batch, SEQ)).astype(np.int32)
            yield (tokens,)

    reader = DistillReader(
        feeds=["tokens"], fetchs=["soft_label"],
        teacher_batch_size=args.batch,
    )
    reader.set_dynamic_teacher(args.store, args.job_id, args.service)
    reader.set_batch_generator(batches)

    def kd_loss(logits, soft):
        """Pure soft-target distillation: per-token KL to the teacher."""
        log_p = jax.nn.log_softmax(logits, axis=-1)
        kl = jnp.mean(
            jnp.sum(soft * (jnp.log(soft + 1e-8) - log_p), axis=-1)
        )
        return kl, {}

    step = make_train_step(kd_loss)
    try:
        for epoch in range(args.epochs):
            metrics = None
            for (tokens, soft) in reader():
                state, metrics = step(
                    state, (jnp.asarray(tokens), jnp.asarray(soft))
                )
            if metrics is not None:
                print("epoch %d kd-loss %.4f" % (epoch, float(metrics["loss"])))
    finally:
        reader.stop()


def main():
    import jax
    parser = argparse.ArgumentParser()
    parser.add_argument("--role", choices=("teacher", "student"), required=True)
    parser.add_argument("--store", required=True)
    parser.add_argument("--job_id", default="distill-nlp")
    parser.add_argument("--service", default="nlp-teacher")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--epochs", type=int, default=2)
    parser.add_argument("--batches", type=int, default=8)
    parser.add_argument("--batch", type=int, default=16)
    args = parser.parse_args()
    if args.role == "teacher":
        run_teacher(args)
    else:
        run_student(args)


if __name__ == "__main__":
    main()
