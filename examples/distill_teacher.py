"""Distillation teacher: serve a JAX model's soft targets, self-register.

Capability parity with the reference's teacher side (a Paddle Serving
instance registered via ``python -m edl.discovery.register``, reference
doc test_distill_reader.sh:17): here the teacher is a jitted JAX model
behind the framed-TCP predict server, heartbeating its endpoint into the
coordination store so students discover it dynamically. Start/stop any
number of these at any time — the student's balance loop adapts.

    python -m edl_tpu.store.server --port 2379 &
    python -m edl_tpu.distill.discovery_server --store 127.0.0.1:2379 &
    python examples/distill_teacher.py --store 127.0.0.1:2379
"""

import argparse
import os
import signal
import threading

import jax
import jax.numpy as jnp
import optax

from edl_tpu.distill import CoalescingBackend, JaxPredictBackend, PredictServer
from edl_tpu.distill.discovery import TeacherRegister
from edl_tpu.models import ResNet, ResNet50_vd
from edl_tpu.train import create_state


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--store", required=True)
    parser.add_argument("--job_id", default="distill")
    parser.add_argument("--service", default="teacher")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--small", action="store_true", help="tiny CPU model")
    parser.add_argument(
        "--model_uri", default=None,
        help="fetch trained params from this URI (local/file/http/gs; "
        "flax to_bytes msgpack of {'params', 'batch_stats'}); also read "
        "from EDL_DISTILL_MODEL_URI — the TPU-native counterpart of the "
        "reference teacher's HDFS model download",
    )
    parser.add_argument("--model_sha256", default=None)
    parser.add_argument(
        "--coalesce_ms", type=float, default=0.0,
        help="megabatching window: coalesce concurrent student requests "
        "into one device call (0 = off)",
    )
    args = parser.parse_args()

    if args.small:
        model = ResNet(stage_sizes=(1, 1), num_classes=10, width=8)
        shape = (1, 32, 32, 3)
    else:
        model = ResNet50_vd(num_classes=1000)
        shape = (1, 224, 224, 3)
    rng = jax.random.PRNGKey(0)
    x = jnp.zeros(shape, jnp.float32)
    state = create_state(model, rng, x, optax.sgd(0.0))

    from flax import serialization

    from edl_tpu.distill import fetch_model

    uri = args.model_uri or os.environ.get("EDL_DISTILL_MODEL_URI")
    if uri:
        path = fetch_model(
            uri,
            sha256=args.model_sha256
            or os.environ.get("EDL_DISTILL_MODEL_SHA256"),
        )
        with open(path, "rb") as f:
            loaded = serialization.from_bytes(
                {"params": state.params, "batch_stats": state.batch_stats},
                f.read(),
            )
        state = state.replace(
            params=loaded["params"], batch_stats=loaded["batch_stats"]
        )
        print("teacher params loaded from %s" % uri)

    def apply(feeds):
        logits = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            feeds["image"],
            train=False,
        )
        return {"soft_label": jax.nn.softmax(logits, axis=-1)}

    backend = JaxPredictBackend(apply)
    if args.coalesce_ms > 0:
        backend = CoalescingBackend(backend, max_wait_ms=args.coalesce_ms)
    server = PredictServer(backend, port=args.port).start()
    print("teacher serving on %s" % server.endpoint)

    reg = TeacherRegister(args.store, args.job_id, args.service, server.endpoint)

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    stop.wait()
    reg.stop()
    server.stop()


if __name__ == "__main__":
    main()
