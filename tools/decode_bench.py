"""KV-cached decode throughput: prefill + per-token step, MHA vs GQA/MQA.

The decode path is where grouped K/V pays in BANDWIDTH (the cache is
``num_kv_heads/num_heads`` the bytes and every generated token re-reads
it); this measures tokens/s for the single-token step and ms for the
bulk prefill, per num_kv_heads config, on whatever backend is up.

The WHOLE generate is jitted (one program per call) and the default
measures a ``--new_tokens 32`` scan. Each timed region ends by fetching
the final tokens, whose value depends on every step.

Prints one JSON line per config.
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
    p.add_argument("--prompt", type=int, default=None)
    p.add_argument("--new_tokens", type=int, default=None)
    p.add_argument("--d_model", type=int, default=None)
    p.add_argument("--layers", type=int, default=None)
    p.add_argument(
        "--kv_heads", type=int, nargs="+", default=None,
        help="num_kv_heads configs to sweep (default: H, H//4, 1)",
    )
    p.add_argument("--iters", type=int, default=5)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from edl_tpu.models import TransformerLM
    from edl_tpu.models.decode import greedy_generate

    dev = jax.devices()[0]
    on_tpu = dev.platform not in ("cpu",)
    batch = args.batch or (8 if on_tpu else 2)
    prompt_len = args.prompt or (512 if on_tpu else 16)
    new_tokens = args.new_tokens or (32 if on_tpu else 4)
    d_model = args.d_model or (1024 if on_tpu else 64)
    layers = args.layers or (12 if on_tpu else 2)
    heads = max(1, d_model // 64)
    kv_list = args.kv_heads or sorted(
        {heads, max(1, heads // 4), 1}, reverse=True
    )
    vocab = 32000 if on_tpu else 256

    rng = jax.random.PRNGKey(0)
    prompt = jax.random.randint(rng, (batch, prompt_len), 0, vocab)

    skipped = [kv for kv in kv_list if heads % kv]
    if skipped:
        print(
            "decode_bench: skipping kv_heads %s (must divide num_heads %d)"
            % (skipped, heads),
            file=sys.stderr,
        )
    kv_list = [kv for kv in kv_list if heads % kv == 0]
    if not kv_list:
        print("decode_bench: no valid kv_heads configs", file=sys.stderr)
        return 1

    for kv in kv_list:
        model = TransformerLM(
            vocab_size=vocab, d_model=d_model, num_heads=heads,
            num_layers=layers, d_ff=int(d_model * 8 / 3 / 128) * 128 or 128,
            num_kv_heads=None if kv == heads else kv,
            decode=True, max_decode_len=prompt_len + new_tokens,
        )
        params = model.init(
            jax.random.PRNGKey(1), prompt[:, :1],
            positions=jnp.zeros((batch, 1), jnp.int32),
        )["params"]

        # prefill and decode timed SEPARATELY: lumping them would wash
        # out the KV-cache bandwidth difference this sweep exists to
        # show (prefill cost is nearly identical across kv_heads). Each
        # is jitted whole and offset by carry so iterations form a
        # dependency chain — one final fetch waits for them all.
        def prefill_only(params, prompt, carry):
            from edl_tpu.models.decode import decode_model, init_cache

            dm = decode_model(model, prompt_len + new_tokens)
            cache = init_cache(model, batch, prompt_len + new_tokens)
            logits, _ = dm.apply(
                {"params": params, "cache": cache},
                (prompt + carry) % vocab,
                positions=jnp.broadcast_to(
                    jnp.arange(prompt_len)[None, :], (batch, prompt_len)
                ),
                mutable=["cache"],
            )
            return jnp.argmax(logits[:, -1, :], -1).astype(prompt.dtype)

        # edl: donate-ok(bench reuses the same params every iteration)
        pre = jax.jit(prefill_only)
        gen = jax.jit(
            lambda params, prompt, carry: greedy_generate(
                model, params, (prompt + carry) % vocab, new_tokens
            )
        )

        def timed(fn, result_of):
            carry = jnp.zeros((), prompt.dtype)
            r = fn(params, prompt, carry)             # compile
            carry = result_of(r)
            int(jax.device_get(carry))                # honest sync
            t0 = time.perf_counter()
            for _ in range(args.iters):
                r = fn(params, prompt, carry)
                carry = result_of(r)                  # chain iterations
            int(jax.device_get(carry))
            return (time.perf_counter() - t0) / args.iters

        prefill_s = timed(pre, lambda r: r[0])
        full_s = timed(gen, lambda r: r[0, -1])
        # per-token decode cost = (prefill+decode) minus prefill-only
        decode_s = max(full_s - prefill_s, 1e-9)
        per_iter = full_s
        tok_s = batch * new_tokens / decode_s
        cache_mb = (
            2 * layers * batch * (prompt_len + new_tokens) * kv
            * (d_model // heads) * 2 / 1e6
        )
        print(json.dumps({
            "metric": "decode_tokens_per_s_%s" % ("tpu" if on_tpu else "cpu_debug"),
            "value": round(tok_s, 1),
            "unit": "tokens/s",
            "vs_baseline": 0.0,  # net-new: the reference has no decoder
            "device": dev.device_kind,
            "batch": batch, "prompt": prompt_len, "new_tokens": new_tokens,
            "d_model": d_model, "layers": layers,
            "num_heads": heads, "num_kv_heads": kv,
            "kv_cache_mb": round(cache_mb, 1),
            "prefill_ms": round(prefill_s * 1e3, 2),
            "decode_ms_per_token": round(
                decode_s * 1e3 / new_tokens, 3
            ),
            "iter_ms": round(per_iter * 1e3, 2),
        }))


if __name__ == "__main__":
    main()
