"""TransformerLM training throughput: tokens/s + MFU on one chip.

The long-context flagship's counterpart of the ResNet headline in
bench.py: a jitted AdamW train step on a GPT-style decoder (RoPE, SwiGLU,
bf16 compute, attention through the measured dispatch table — see
ops/attention.py) with XLA cost-analysis
FLOPs for the MFU denominator. The timed window ends with a fetch of the
final loss, which depends on every step.

Prints ONE JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))



def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--seq", type=int, default=None)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--d_model", type=int, default=None)
    p.add_argument("--layers", type=int, default=None)
    p.add_argument(
        "--kv_heads", type=int, default=None,
        help="GQA: fewer kv heads than query heads; the grouped kernels "
        "read them without a materialized repeat",
    )
    p.add_argument(
        "--remat", choices=("save_flash", "save_flash_qkv", "full", "none"),
        default="save_flash",
        help="activation strategy: save_flash (default) recomputes all "
        "but the attention kernel's out+lse; 'none' saves everything "
        "(no recompute at all — fastest when activations fit HBM); "
        "'full' is recompute-everything",
    )
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    from edl_tpu.models import TransformerLM
    from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

    dev = jax.devices()[0]
    on_tpu = dev.platform not in ("cpu",)
    batch = args.batch or (8 if on_tpu else 2)
    seq = args.seq or (2048 if on_tpu else 128)
    d_model = args.d_model or (1024 if on_tpu else 64)
    layers = args.layers or (12 if on_tpu else 2)
    steps = args.steps if on_tpu else 3

    model = TransformerLM(
        vocab_size=32000 if on_tpu else 256,
        d_model=d_model,
        num_heads=max(1, d_model // 64),
        num_layers=layers,
        d_ff=int(d_model * 8 / 3 / 128) * 128 or 128,
        remat=args.remat != "none",
        remat_policy=None if args.remat in ("none", "full") else args.remat,
        num_kv_heads=args.kv_heads,
    )
    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (batch, seq + 1), 0, model.vocab_size)
    x, y = tokens[:, :-1], tokens[:, 1:]
    state = create_state(model, rng, x, optax.adamw(1e-3))
    lm_loss = lambda logits, t: cross_entropy_loss(
        logits.reshape(-1, logits.shape[-1]), t.reshape(-1)
    )
    step = make_train_step(lm_loss, donate=False)
    compiled = step.lower(state, (x, y)).compile()
    flops = None
    cost = {}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0)) or None
    except Exception:
        pass

    for _ in range(3):
        state, m = compiled(state, (x, y))
    float(jax.device_get(m["loss"]))
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = compiled(state, (x, y))
    final = float(jax.device_get(m["loss"]))
    dt = time.perf_counter() - t0
    assert final == final, "NaN loss"

    tok_s = batch * seq * steps / dt
    out = {
        "metric": "transformer_lm_train_tokens_per_s_%s"
        % ("tpu" if on_tpu else "cpu_debug"),
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": 0.0,  # net-new workload: the reference has no LM
        "device": dev.device_kind,
        "batch": batch,
        "seq": seq,
        "d_model": d_model,
        "layers": layers,
        "remat": args.remat,
        "kv_heads": args.kv_heads,
        "loss": round(final, 3),
    }
    # ordered list, not a dict: "v5" must not shadow "v5p"
    from edl_tpu.obs.profile import peak_flops, roofline

    peak = peak_flops(dev.device_kind)
    if flops and peak and on_tpu:
        out["mfu"] = round(flops * (steps / dt) / peak, 4)
        out["step_tflops"] = round(flops / 1e12, 2)
        # roofline context from XLA's own cost model: the on-chip artifact
        # self-carries its MFU ceiling (see bench.py::roofline)
        out.update(roofline(cost, dev.device_kind, peak, mfu=out["mfu"]))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
