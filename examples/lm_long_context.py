"""Long-context LM training: ring attention + tensor parallelism.

Net-new versus the reference (SURVEY §5: it has no long-context or
sequence-parallel support) — first-class here per the build charter. A
TransformerLM trains over a dp×tp×sp mesh: Megatron-style tensor-parallel
weights (column/row PartitionSpec rules), the sequence sharded over
``sp`` with KV shards rotating via ``lax.ppermute`` (ring attention), and
per-block rematerialisation — so max context scales linearly with the
ring size and the MXU sees only large bf16 matmuls.

Smoke-runs on the 8-device CPU mesh::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/lm_long_context.py --seq_len 512
"""

import argparse
import functools

import jax
import jax.numpy as jnp
import optax

from edl_tpu.models import TransformerLM
from edl_tpu.parallel import (
    TRANSFORMER_TP_RULES,
    make_mesh,
    ring_attention_sharded,
    shard_batch,
    shard_params_by_rules,
)
from edl_tpu.train import create_state, cross_entropy_loss, init, make_train_step


def lm_loss(logits, labels):
    return cross_entropy_loss(
        logits.reshape(-1, logits.shape[-1]), labels.reshape(-1)
    )


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq_len", type=int, default=2048)
    parser.add_argument("--d_model", type=int, default=256)
    parser.add_argument("--num_layers", type=int, default=4)
    parser.add_argument("--num_heads", type=int, default=8)
    parser.add_argument(
        "--kv_heads", type=int, default=None,
        help="GQA: fewer kv heads than query heads — the grouped k/v "
        "ride the ring directly, cutting its ppermute volume",
    )
    parser.add_argument("--vocab", type=int, default=32000)
    parser.add_argument("--tp", type=int, default=2)
    parser.add_argument("--sp", type=int, default=2)
    args = parser.parse_args()

    env = init()
    n = jax.device_count()
    tp, sp = args.tp, args.sp
    if n % (tp * sp) != 0:
        tp = sp = 1
    mesh = make_mesh({"dp": n // (tp * sp), "tp": tp, "sp": sp})
    attn = functools.partial(ring_attention_sharded, mesh=mesh, sp_axis="sp")

    model = TransformerLM(
        vocab_size=args.vocab,
        d_model=args.d_model,
        num_heads=args.num_heads,
        num_layers=args.num_layers,
        d_ff=4 * args.d_model,
        remat=True,
        attention_fn=attn,
        num_kv_heads=args.kv_heads,
    )
    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (args.batch, args.seq_len), 0, args.vocab)
    labels = jnp.roll(tokens, -1, axis=1)
    state = create_state(
        model, rng, tokens, optax.adamw(3e-4, weight_decay=0.1)
    )

    with mesh:
        state = state.replace(
            params=shard_params_by_rules(mesh, state.params, TRANSFORMER_TP_RULES)
        )
        batch = shard_batch(mesh, (tokens, labels))
        step = make_train_step(lm_loss)
        for i in range(args.steps):
            state, metrics = step(state, batch)
            if env.is_rank0 and (i + 1) % 5 == 0:
                print("step %d loss %.4f" % (i + 1, float(metrics["loss"])))
        jax.block_until_ready(metrics["loss"])
        if env.is_rank0:
            print(
                "trained %d steps @ seq_len=%d on mesh %s"
                % (args.steps, args.seq_len, dict(mesh.shape))
            )


if __name__ == "__main__":
    main()
