"""Co-located distillation benchmark: teacher + student on the SAME chip.

The reference's middle benchmark row (README.md:71): ResNeXt101_32x16d_wsl
teacher and ResNet50_vd student sharing the same 8x V100 drop pure-train
throughput from 1828 to 656 img/s (ratio 0.359) for +1.9 acc1. There the
teacher runs behind Paddle Serving on the same GPUs; here co-location is
TPU-native — the frozen teacher forward is FUSED into the student's jitted
KD train step, so XLA schedules teacher inference and student train as one
program (no RPC, no host round-trip, one compiled artifact).

Measures on the current backend:
  1. pure student train step (CE loss) img/s
  2. fused co-located KD step (teacher fwd + student fwd/bwd/update) img/s
and prints ONE JSON line with both, the retention ratio, and vs_baseline =
ratio / 0.359 (>1.0 means we retain MORE throughput under co-location than
the reference did).

Each timed region ends with a scalar host fetch that depends on all of it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REF_PURE = 1828.0 / 8  # img/s per V100, reference README.md:70
REF_COLOC_RATIO = 656.0 / 1828.0  # README.md:71


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--alpha", type=float, default=0.5)
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument(
        "--teacher_dtype", choices=("bf16", "f32"), default="bf16",
        help="storage dtype for the frozen teacher's params/stats: bf16 "
        "halves the ~776MB-per-step HBM param traffic of the 194M-param "
        "teacher (compute is already bf16; the fp32 logits head "
        "upcasts, so soft targets stay fp32). f32 is the round-4 "
        "behavior for A/B.",
    )
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    from edl_tpu.train import (
        create_state,
        cross_entropy_loss,
        make_kd_loss,
        make_train_step,
    )

    dev = jax.devices()[0]
    on_tpu = dev.platform not in ("cpu",)
    batch = args.batch or (256 if on_tpu else 4)
    size = 224 if on_tpu else 24
    steps = args.steps if on_tpu else 2
    warmup = 5 if on_tpu else 1

    if on_tpu:
        from edl_tpu.models import ResNet50_vd, ResNeXt101_32x16d

        student = ResNet50_vd(num_classes=1000)
        teacher = ResNeXt101_32x16d(num_classes=1000)
        classes = 1000
    else:
        from edl_tpu.models import ResNet
        from edl_tpu.models.resnet import ResNeXt

        student = ResNet(stage_sizes=(1, 1), num_classes=100, width=8)
        teacher = ResNeXt(
            stage_sizes=(1, 1), cardinality=4, base_width=4, num_classes=100
        )
        classes = 100

    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (batch, size, size, 3), jnp.float32)
    y = jax.random.randint(rng, (batch,), 0, classes)

    state = create_state(student, rng, x, optax.sgd(0.1, momentum=0.9))
    tvars = teacher.init(jax.random.PRNGKey(1), x, train=False)
    if args.teacher_dtype == "bf16":
        # a frozen KD teacher tolerates bf16 running stats/weights: the
        # student consumes softmax(T-logits), and the fp32 Dense head
        # keeps the logits themselves fp32
        tvars = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 else a,
            tvars,
        )

    def timed(compiled, state, fetch):
        for _ in range(warmup):
            state, metrics = compiled(state, (x, y))
        float(jax.device_get(fetch(metrics)))
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = compiled(state, (x, y))
        float(jax.device_get(fetch(metrics)))
        return batch * steps / (time.perf_counter() - t0)

    # --- phase 1: pure train ---
    pure_step = make_train_step(cross_entropy_loss, {"train": True})
    pure_compiled = pure_step.lower(state, (x, y)).compile()
    pure = timed(pure_compiled, state, lambda m: m["loss"])

    # --- phase 2: fused co-located KD ---
    kd_step = make_train_step(
        make_kd_loss(args.alpha, args.temperature), {"train": True}
    )

    # tvars is an ARGUMENT, not a closure capture: closed-over arrays
    # become jaxpr constants (slow lowering + a duplicate ~776MB fp32
    # copy of the 194M-param teacher in HBM)
    def coloc(tv, state, batch):
        xb, yb = batch
        tlogits = teacher.apply(tv, xb, train=False)
        return kd_step(state, (xb, (yb, tlogits)))

    state2 = create_state(student, rng, x, optax.sgd(0.1, momentum=0.9))
    coloc_jit = jax.jit(coloc, donate_argnums=(1,))
    coloc_lowered = coloc_jit.lower(tvars, state2, (x, y)).compile()
    coloc_compiled = lambda st, b: coloc_lowered(tvars, st, b)  # noqa: E731
    co = timed(coloc_compiled, state2, lambda m: m["kd_kl"])

    ratio = co / pure
    out = {
        "metric": "colocated_distill_retention_%s" % ("tpu" if on_tpu else "cpu_debug"),
        "value": round(ratio, 3),
        "unit": "coloc/pure throughput ratio",
        "vs_baseline": round(ratio / REF_COLOC_RATIO, 3) if on_tpu else 0.0,
        "pure_img_s": round(pure, 1),
        "coloc_img_s": round(co, 1),
        "ref_ratio": round(REF_COLOC_RATIO, 3),
        "ref_pure_img_s_per_gpu": round(REF_PURE, 1),
        "ref_coloc_img_s_per_gpu": round(656.0 / 8, 1),
        "device": dev.device_kind,
        "batch": batch,
        "steps": steps,
        "teacher_dtype": args.teacher_dtype,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
