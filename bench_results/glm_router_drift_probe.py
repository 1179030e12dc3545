"""Where do the routers of ``glm_4_7_flash.steady`` go in a run's 130 steps?

    python3 bench_results/glm_router_drift_probe.py VARIANT [VARIANT ...] [--seed N] [--rehearse]

The cell's own configuration, model, optimizer, batches and step
(``families/mla_mtp_lm.py:build`` / ``host_batches``, ``create_state``,
``make_train_step``), with one thing changed a variant, for ``--steps`` steps
(130: ten of warm-up, twelve traced, a window of 30 s at 0.33 s a step). After
every tenth step it prints what the model sowed: the loss, the module's loss,
``moe_load_max`` (the busiest expert's load over the mean: 1 balanced, 16 when
every token picks the same four of 64), ``moe_rows_held`` (0.125 balanced; past
0.25 a layer leaves ``DroplessMoE``'s buffer) and ``moe_bias_absmax``; at the end
``rows_held`` and ``load_max`` layer by layer on a fresh sequence. One JSON line
a print, on stdout and in ``chiprun_out/glm_router_drift.jsonl``.
"""

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.families import mla_mtp_lm  # noqa: E402
from benchmark.reference import mla_mtp_lm as reference  # noqa: E402
from edl_tpu.train import create_state, make_train_step  # noqa: E402


def changed(config, **train):
    config = copy.deepcopy(config)
    for key, value in train.items():
        target = config["train"]
        *parents, leaf = key.split("__")
        for p in parents:
            target = target[p]
        target[leaf] = value
    return config


VARIANTS = {
    "as_timed": {},
    "bias_0_01": {"expert_bias_rate": 0.01},
    "bias_0_003": {"expert_bias_rate": 0.003},
    "lr_4e_4": {"optimizer__lr": 4e-4},  # the issue's rate, the other sigmoid-routed cells'
    "lr_1e_4": {"optimizer__lr": 1e-4},
    "lr_4e_5": {"optimizer__lr": 4e-5},
    "no_module_term": {"mtp_loss_weight": 0.0},
    "head_as_drawn": {"start": {"embedding_rms": 1.0}},
}


def looker(apply_fn, names):
    """``look(params, stats, tokens)``: one forward on a sequence no step has seen, expert layer by expert layer:
    the share of rows on held experts and the busiest expert's load, the rms of
    what the router reads and how much of it every token shares (the norm of the
    tokens' mean over their rms norm: 0.01 for 8192 independent tokens, 1 when
    the stream is one direction), the same of the router's logits, their
    largest, and the rms of the router's kernel."""
    import jax.numpy as jnp

    @jax.jit
    def measure(p, s, t):
        _, left = apply_fn(
            {"params": p, "batch_stats": s}, t, mutable=["metrics", "intermediates", "batch_stats"]
        )

        def shared(x):  # [N, C] -> the tokens' mean's norm over their rms norm
            x = x.astype(jnp.float32)
            return jnp.linalg.norm(jnp.mean(x, axis=0)) / jnp.sqrt(jnp.mean(jnp.sum(x * x, axis=-1)))

        out = {k: [] for k in ("rows_held", "load_max", "in_rms", "in_shared", "logit_shared",
                               "logit_absmax", "router_rms")}
        for n in names:
            seen, sown = left["intermediates"][n]["moe"], left["metrics"][n]["moe"]
            fed, logits = seen["router_in"][0], seen["router_logits"][0]
            fed, logits = fed.reshape(-1, fed.shape[-1]), logits.reshape(-1, logits.shape[-1])
            out["rows_held"].append(sown["moe_rows_held"][0])
            out["load_max"].append(sown["moe_load_max"][0])
            out["in_rms"].append(jnp.sqrt(jnp.mean(jnp.square(fed.astype(jnp.float32)))))
            out["in_shared"].append(shared(fed))
            out["logit_shared"].append(shared(logits))
            out["logit_absmax"].append(jnp.max(jnp.abs(logits)))
            out["router_rms"].append(jnp.sqrt(jnp.mean(jnp.square(p[n]["moe"]["router"]["kernel"]))))
        return out

    def look(state, tokens):
        out = measure(state.params, state.batch_stats, tokens)
        return dict(layers=names, **{k: [round(float(x), 4) for x in v] for k, v in out.items()})

    return look


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("variants", nargs="+", choices=sorted(VARIANTS))
    parser.add_argument("--seed", type=int, default=3000005531)
    parser.add_argument("--steps", type=int, default=130)
    parser.add_argument("--check", action="store_true", help="the family's check at the end")
    parser.add_argument("--look-every", type=int, default=40)
    parser.add_argument("--rehearse", action="store_true", help="the toy twin, on the CPU")
    args = parser.parse_args()
    where = ("rehearsal", "configs") if args.rehearse else ("configs",)
    with open(os.path.join(ROOT, "benchmark", *where, "glm_4_7_flash.json")) as f:
        base = json.load(f)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "glm_router_drift.jsonl"), "a")

    def say(**line):
        text = json.dumps(line, default=float)
        print(text, flush=True)
        out.write(text + "\n")
        out.flush()

    for name in args.variants:
        config = changed(base, **VARIANTS[name])
        job = mla_mtp_lm.build(config, 1, args.seed)
        pool = mla_mtp_lm.host_batches(config, 1, args.seed)
        state = create_state(
            job["model"], jax.random.PRNGKey(args.seed % (2 ** 31)), job["sample_input"],
            job["optimizer"],
        )
        step = make_train_step(job["loss"], numerics=False)
        look = looker(state.apply_fn, reference.expert_blocks(config))
        fresh = mla_mtp_lm.host_batches(config, 1, args.seed + 1, n_batches=1)[0][0]
        start = time.time()
        for i in range(args.steps):
            if i % args.look_every == 0:
                say(variant=name, seed=args.seed, step=i, **look(state, fresh))
            state, metrics = step(state, pool[i % len(pool)])
            if i % 10 == 0 or i == args.steps - 1:
                got = {k: float(v) for k, v in jax.device_get(metrics).items() if np.ndim(v) == 0}
                say(variant=name, seed=args.seed, step=i, seconds=round(time.time() - start, 1),
                    **{k: got[k] for k in ("loss", "mtp_loss", "moe_load_max", "moe_rows_held",
                                           "moe_held_load_max", "moe_bias_absmax")})
        say(variant=name, seed=args.seed, step=args.steps, **look(state, fresh))
        if args.check:
            result = mla_mtp_lm.check(config, state, args.seed)
            say(variant=name, seed=args.seed, check={
                k: v for k, v in result.items() if k not in ("kernel", "grouped_matmul")
            })
        del state, step


if __name__ == "__main__":
    main()
