"""Per-op attribution of the TransformerLM train step: where every ms goes.

VERDICT r4 located the LM's MFU gap (0.358 vs a 0.906 roofline ceiling) in
the flash kernels, by inference from separate artifacts. This tool measures
the attribution directly, with the substitution method (component removed →
step re-timed → difference attributed); a device trace reduced by code
(ROADMAP S2) is to replace it:

- ``attention``: step time minus the step with attention replaced by a
  passthrough (``lambda q,k,v: v`` — keeps every shape and the projections,
  removes only the kernel fwd+bwd and its remat behavior);
- ``lm_head``: step time minus the step with vocab cut to d_model-size
  (the head matmul shrinks ~vocab/d_model-fold; embed shrinks with it, so
  this row slightly overstates the head);
- ``kernels standalone``: the dispatch's fwd and fwd+bwd at the exact
  model shape, per layer — the cross-check for the attention row (they
  should roughly agree; a large mismatch means the step's attention cost
  is scheduling, not kernel time);
- ``rest``: what no substitution explains (matmuls, norms, rope, optimizer,
  remat recompute of the non-attention forward).

Every timed region ends in a scalar fetch whose value depends on all
prior work.

Prints one JSON line; ``--out`` also appends it to a file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _timed_steps(compiled, state, batch, steps):
    import jax

    for _ in range(2):
        state, m = compiled(state, batch)
    float(jax.device_get(m["loss"]))
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = compiled(state, batch)
    float(jax.device_get(m["loss"]))
    return (time.perf_counter() - t0) / steps


def _build_step(model, rng, x, y):
    import optax

    from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

    state = create_state(model, rng, x, optax.adamw(1e-3))
    lm_loss = lambda logits, t: cross_entropy_loss(
        logits.reshape(-1, logits.shape[-1]), t.reshape(-1)
    )
    step = make_train_step(lm_loss, donate=False)
    return state, step.lower(state, (x, y)).compile()


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--seq", type=int, default=None)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--d_model", type=int, default=None)
    p.add_argument("--layers", type=int, default=None)
    p.add_argument("--remat_policy", default="save_flash")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    import importlib

    # edl_tpu.ops re-exports the attention FUNCTION under the same name as
    # the submodule, shadowing it on the package — import the module by path
    A = importlib.import_module("edl_tpu.ops.attention")
    from edl_tpu.models import TransformerLM

    dev = jax.devices()[0]
    on_tpu = dev.platform not in ("cpu",)
    batch = args.batch or (16 if on_tpu else 2)
    seq = args.seq or (2048 if on_tpu else 128)
    d_model = args.d_model or (1024 if on_tpu else 64)
    layers = args.layers or (12 if on_tpu else 2)
    steps = args.steps if on_tpu else 2
    vocab = 32000 if on_tpu else 256
    heads = max(1, d_model // 64)

    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (batch, seq + 1), 0, vocab)
    x, y = tokens[:, :-1], tokens[:, 1:]

    def lm(**kw):
        cfg = dict(
            vocab_size=vocab, d_model=d_model, num_heads=heads,
            num_layers=layers, d_ff=int(d_model * 8 / 3 / 128) * 128 or 128,
            remat=True, remat_policy=args.remat_policy,
        )
        cfg.update(kw)
        return TransformerLM(**cfg)

    rows = {}
    state, compiled = _build_step(lm(), rng, x, y)
    rows["step_ms"] = _timed_steps(compiled, state, (x, y), steps) * 1e3
    cost = {}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
    except Exception:
        pass

    # attention removed: passthrough keeps shapes + projections
    no_attn = lambda q, k, v, causal=False, scale=None: v
    state2, compiled2 = _build_step(lm(attention_fn=no_attn), rng, x, y)
    rows["step_no_attention_ms"] = (
        _timed_steps(compiled2, state2, (x, y), steps) * 1e3
    )

    # head shrunk: vocab -> d_model (embed shrinks too — slight overstate)
    tokens_s = jax.random.randint(rng, (batch, seq + 1), 0, d_model)
    xs, ys = tokens_s[:, :-1], tokens_s[:, 1:]
    state3, compiled3 = _build_step(lm(vocab_size=d_model), rng, xs, ys)
    rows["step_small_head_ms"] = (
        _timed_steps(compiled3, state3, (xs, ys), steps) * 1e3
    )

    # standalone kernels at the model's attention shape, via the dispatch
    q = jax.random.normal(rng, (batch, heads, seq, d_model // heads),
                          jnp.bfloat16)
    fwd = jax.jit(lambda q: A.attention(q, q, q, causal=True).sum(
        dtype=jnp.float32))
    bwd = jax.jit(jax.grad(lambda q: A.attention(q, q, q, causal=True).sum(
        dtype=jnp.float32)))
    for name, fn in (("fwd", fwd), ("fwd_bwd", bwd)):
        r = fn(q)
        float(jnp.sum(r, dtype=jnp.float32) if r.ndim else r)
        t0 = time.perf_counter()
        acc = None
        for _ in range(steps):
            r = fn(q)
            acc = r if acc is None else acc + r
        float(jnp.max(acc))
        rows["kernel_%s_ms_per_layer" % name] = (
            (time.perf_counter() - t0) / steps * 1e3
        )

    attn_ms = rows["step_ms"] - rows["step_no_attention_ms"]
    head_ms = rows["step_ms"] - rows["step_small_head_ms"]
    out = {
        "metric": "lm_step_profile",
        "platform": "tpu" if on_tpu else "cpu",
        "device": dev.device_kind,
        "batch": batch, "seq": seq, "d_model": d_model, "layers": layers,
        "remat_policy": args.remat_policy,
        "step_ms": round(rows["step_ms"], 3),
        "attention_ms": round(attn_ms, 3),
        "attention_pct": round(100 * attn_ms / rows["step_ms"], 1),
        "lm_head_ms": round(head_ms, 3),
        "lm_head_pct": round(100 * head_ms / rows["step_ms"], 1),
        "rest_ms": round(rows["step_ms"] - attn_ms - head_ms, 3),
        "kernel_fwd_ms_per_layer": round(
            rows["kernel_fwd_ms_per_layer"], 3),
        "kernel_fwd_bwd_ms_per_layer": round(
            rows["kernel_fwd_bwd_ms_per_layer"], 3),
        "kernel_fwd_bwd_ms_total": round(
            rows["kernel_fwd_bwd_ms_per_layer"] * layers, 3),
        "raw": {k: round(v, 3) for k, v in rows.items()},
    }
    if cost:
        flops = float(cost.get("flops", 0.0))
        if flops:
            from edl_tpu.obs.profile import peak_flops

            peak = peak_flops(dev.device_kind)
            out["step_tflops"] = round(flops / 1e12, 2)
            if peak and on_tpu:
                out["mfu"] = round(
                    flops / (rows["step_ms"] / 1e3) / peak, 4)
    line = json.dumps(out)
    print(line)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
