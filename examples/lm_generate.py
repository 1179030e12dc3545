"""Train-then-generate: the LM round trip on one chip.

Net-new versus the reference (which has no LMs): a GQA TransformerLM
trains briefly on a repeating token pattern, then generates from a prompt
with the KV-cached greedy decoder (`edl_tpu.models.greedy_generate`) —
one bulk prefill pass plus a static-shape single-token step, compiled
once. A model that learned the pattern continues it, which the script
asserts, making this a self-checking smoke of the full
train → decode → sample loop.

Smoke-runs on CPU::

    JAX_PLATFORMS=cpu python examples/lm_generate.py --steps 60
"""

import argparse
import sys

import numpy as np


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=60)
    parser.add_argument("--vocab", type=int, default=32)
    parser.add_argument("--seq", type=int, default=24)
    parser.add_argument("--period", type=int, default=4)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    from edl_tpu.models import TransformerLM, greedy_generate
    from edl_tpu.train import create_state, make_train_step

    # the "dataset": sequences cycling 0,1,..,period-1,0,1,... from random
    # phase offsets — learnable in a few dozen steps by a tiny model
    def batch(rs, n=16):
        phase = rs.randint(0, args.period, (n, 1))
        pos = np.arange(args.seq + 1)[None, :]
        seq = (phase + pos) % args.period
        return jnp.asarray(seq[:, :-1]), jnp.asarray(seq[:, 1:])

    model = TransformerLM(
        vocab_size=args.vocab, d_model=48, num_heads=4, num_kv_heads=2,
        num_layers=2, d_ff=96, dtype=jnp.float32,
    )

    def loss(logits, y):
        oh = jax.nn.one_hot(y, args.vocab)
        return optax.softmax_cross_entropy(logits, oh).mean(), {}

    rs = np.random.RandomState(0)
    x0, _ = batch(rs)
    state = create_state(
        model, jax.random.PRNGKey(0), x0, optax.adam(3e-3)
    )
    step = make_train_step(loss, donate=False)
    for i in range(args.steps):
        state, metrics = step(state, batch(rs))
        if i % 20 == 0 or i == args.steps - 1:
            print("step %3d loss %.4f" % (i, float(metrics["loss"])))

    prompt = jnp.asarray((np.arange(args.period) % args.period)[None, :])
    out = np.asarray(
        greedy_generate(model, state.params, prompt, max_new_tokens=12)
    )[0]
    expect = np.arange(args.period + 12) % args.period
    print("prompt   :", out[: args.period].tolist())
    print("generated:", out[args.period:].tolist())
    if not (out == expect).all():
        print("model did not learn the pattern (loss too high?)")
        return 1
    print("OK: generation continues the learned pattern")
    return 0


if __name__ == "__main__":
    sys.exit(main())
