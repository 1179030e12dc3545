"""Where do a share's routers go? A CPU probe of ``solar_open2_250b``'s routing.

    JAX_PLATFORMS=cpu python3 bench_results/router_drift_probe.py VARIANT [SEED]

Four layers (gated attention without a position term, then three Kimi delta
attention mixers), each with 320 sigmoid-routed experts of which 8 are held
(top-8, a bias at rate 0.001, a shared expert), at a width of 512 in float32,
one sequence of 1024 a step, AdamW. After every tenth step it prints the share
of the (token, choice) pairs that fell on held experts, layer by layer, over
the balanced share 8 / 320: past 2.0 a layer leaves ``DroplessMoE``'s buffer.

The rate: a consistent gradient moves a router's logits by ``lr * width`` a
step and a noisy one by ``lr * sqrt(width)``, so ``3.2e-3`` stands for the
cell's 4e-4 where the model learns (two batches) and ``1.13e-3`` where it
cannot (distinct batches); the second reproduced the chip's onset of drift,
step 130-150 (PERF.md section 6, PR 51). Variants: ``two`` (the other LM
cells' two batches), ``two_table`` (and the table at rms 1.0), ``two_held``
(routers not trained: what an amended issue could ask for), ``distinct``
(256 batches and the table), ``distinct_zero`` (and the head at zero: the
cell as handed in).
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from edl_tpu.models import ArchSpec, KimiDeltaSpec, MoESpec, TransformerLM  # noqa: E402
from edl_tpu.train import cross_entropy_loss  # noqa: E402

WIDTH, SEQ, VOCAB, EXPERTS, HELD = 512, 1024, 2048, 320, 8
VARIANTS = {
    "two": dict(batches=2, lr=3.2e-3, steps=40),
    "two_table": dict(batches=2, lr=3.2e-3, steps=40, table_rms=1.0),
    "two_held": dict(batches=2, lr=3.2e-3, steps=60, routers_held=True),
    "distinct": dict(batches=256, lr=1.13e-3, steps=200, table_rms=1.0),
    "distinct_zero": dict(batches=256, lr=1.13e-3, steps=200, table_rms=1.0, zero_head=True),
}


def run(tag, seed, batches, lr, steps, table_rms=None, zero_head=False, routers_held=False):
    kda = KimiDeltaSpec(num_heads=2, key_dim=64, value_dim=64, d_conv=4, chunk=64,
                        lower_bound=None, neg_eigval=True, gate_rank=64)
    arch = ArchSpec(layer_types=("attention", "kda", "kda", "kda"), kda=kda, head_dim=64,
                    rope=False, attn_gate=True, dense_layers=0)
    moe = MoESpec(num_experts=EXPERTS, top_k=8, d_ff=160, norm_topk_prob=True, aux_weight=0.0,
                  z_weight=0.0, score_func="sigmoid", bias_rate=0.001, shared_d_ff=160,
                  held=(0, HELD))
    model = TransformerLM(dtype=jnp.float32, vocab_size=VOCAB, d_model=WIDTH, num_heads=4,
                          num_kv_heads=1, num_layers=4, d_ff=2 * WIDTH, remat=False,
                          norm_eps=1e-5, moe=moe, arch=arch)
    rs = np.random.default_rng(seed)
    pool = [rs.integers(0, VOCAB, (1, SEQ + 1)).astype(np.int32) for _ in range(batches)]
    variables = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, SEQ), jnp.int32))
    params, stats = dict(variables["params"]), variables["batch_stats"]
    if table_rms:
        table = params["embed"]["embedding"]
        params["embed"] = {"embedding": table * (table_rms / jnp.sqrt(jnp.mean(table * table)))}
    if zero_head:
        params["lm_head"] = jax.tree.map(jnp.zeros_like, params["lm_head"])
    opt = optax.adamw(lr)
    if routers_held:
        labels = jax.tree_util.tree_map_with_path(
            lambda path, _: "held" if "router" in jax.tree_util.keystr(path) else "trained", params
        )
        opt = optax.multi_transform({"trained": opt, "held": optax.set_to_zero()}, labels)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, stats, opt_state, tokens):
        def loss_fn(p):
            logits, sown = model.apply(
                {"params": p, "batch_stats": stats}, tokens[:, :-1],
                mutable=["batch_stats", "metrics", "losses", "intermediates"],
            )
            loss = cross_entropy_loss(logits.reshape(-1, VOCAB), tokens[:, 1:].reshape(-1))[0]
            return loss, sown

        (loss, sown), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        held = [
            jnp.ravel(v)[0] for k, v in sorted(
                jax.tree_util.tree_leaves_with_path(sown["metrics"]), key=lambda kv: str(kv[0])
            ) if "moe_rows_held" in str(k)
        ]
        return optax.apply_updates(params, updates), sown["batch_stats"], opt_state, loss, jnp.stack(held)

    start = time.time()
    for i in range(steps):
        params, stats, opt_state, loss, held = step(params, stats, opt_state, pool[i % batches])
        if i % 10 == 0 or i == steps - 1:
            print(tag, "seed", seed, "step", i, "loss %.4f" % float(loss), "held / balanced",
                  np.round(np.asarray(held) * EXPERTS / HELD, 2), "%.0f s" % (time.time() - start),
                  flush=True)


if __name__ == "__main__":
    run(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 0, **VARIANTS[sys.argv[1]])
