"""The precision below, at the cell's own sizes, through the family's ``check``.

    python3 bench_results/sdar_precision_below.py [--rehearse] [--seed N]

``benchmark/tests/test_block_diffusion_lm.py`` reads the 8-bit control at a
width of 256 on the CPU; this reads it where the cell runs
(``smallthinker_precision_below.py``'s way): ``sdar_30b_a3b``'s configuration
file as it is timed, freshly drawn parameters with the head as the class draws
it (a control needs no training: the limits are on one forward pass, and the
start's zero head would compare 0 with 0), the program's model once in its
stated ``bfloat16`` and once in ``float8_e4m3fn``, each handed to
``families/block_diffusion_lm.py:check`` as ``run.py`` hands its trained state.
The 8-bit program's kernels take bfloat16 operands and their results are
rounded back to 8 bits (no Pallas kernel here takes an 8-bit float). One JSON
line a precision, on stdout and in ``chiprun_out/``.
"""

import argparse
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench_results.smallthinker_precision_below import rotating_in_float32  # noqa: E402
from bench_results.solar_precision_below import in_bfloat16  # noqa: E402
from benchmark.families import block_diffusion_lm as family  # noqa: E402
from edl_tpu.models import moe, transformer  # noqa: E402

LIMITS = dict(logits_rel_err=family.LOGITS_REL_TOL,
              router_logits_rel_err=family.ROUTER_LOGITS_REL_TOL,
              flipped_share=family.ROUTE_FLIP_LIMIT,
              loss_rel_err=family.LOSS_REL_TOL)
READ = tuple(LIMITS) + ("tokens_misrouted", "aux_rel_err", "router_arithmetic_rel_err",
                        "router_logits_rel_err_by_layer", "flipped_share_by_layer",
                        "rows_held", "rows_dropped", "load_max", "held_load_max",
                        "loss_head_metrics", "forward_process")


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--rehearse", action="store_true", help="the toy twin, on the CPU")
    parser.add_argument("--seed", type=int, default=3000006101)
    args = parser.parse_args()
    where = ("rehearsal", "configs") if args.rehearse else ("configs",)
    with open(os.path.join(ROOT, "benchmark", *where, "sdar_30b_a3b.json")) as f:
        config = json.load(f)

    moe.grouped_matmul = in_bfloat16(moe.grouped_matmul)
    transformer.attention = in_bfloat16(transformer.attention)
    transformer.rope = rotating_in_float32()

    model = family.build(family.as_drawn(config), 1, args.seed)["model"]
    tokens = family.host_batches(config, 1, args.seed, n_batches=1)[0][0]
    variables = jax.jit(model.init)(jax.random.PRNGKey(args.seed % (2 ** 31)), tokens)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "sdar_precision_below.jsonl"), "a")
    for dtype in ("bfloat16", "float8_e4m3fn"):
        t0 = time.time()
        line = {"dtype": dtype, "seed": args.seed, "backend": jax.default_backend(),
                "seq_len": config["train"]["seq_len"], "hidden": config["hidden_size"],
                "layers": config["num_hidden_layers"], "limits": LIMITS}
        try:
            coarse = model.clone(dtype=getattr(jnp, dtype))
            state = types.SimpleNamespace(params=variables["params"], apply_fn=coarse.apply)
            result = family.check(config, state, args.seed)
            line.update(ok=result["ok"], **{k: result[k] for k in READ})
            line["over_limit"] = {k: result[k] / v for k, v in LIMITS.items()}
            line["kernel"] = {k: v for k, v in result["kernel"].items() if k != "shape"}
            line["kernel_membership"] = result["kernel_membership"]
        except Exception as exc:  # noqa: BLE001 — the other precision still reads
            line["error"] = repr(exc)[:2000]
        line["seconds"] = round(time.time() - t0, 1)
        text = json.dumps(line, default=float)
        print(text, flush=True)
        out.write(text + "\n")
        out.flush()


if __name__ == "__main__":
    main()
