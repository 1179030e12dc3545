"""The precision below, at a cell's own sizes, through the family's ``check``.

    python3 bench_results/glm_precision_below.py [--rehearse] [--seed N]

``benchmark/tests/test_mla_mtp_lm.py`` reads the 8-bit control at a width of 256
on the CPU; this reads it where the cell runs (``solar_precision_below.py``'s
way): ``glm_4_7_flash``'s configuration file as it is timed, freshly drawn
parameters with the head as the class draws it (a control needs no training:
the limits are on one forward pass, and the start's zero head would compare 0
with 0), the program's model once in its stated ``bfloat16`` and once in
``float8_e4m3fn``, each handed to ``families/mla_mtp_lm.py:check`` as
``run.py`` hands its trained state. The 8-bit program's kernels take bfloat16
operands and their results are rounded back to 8 bits (no Pallas kernel here
takes an 8-bit float). One JSON line a precision, on stdout and in
``chiprun_out/``.
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

from bench_results.solar_precision_below import in_bfloat16  # noqa: E402
from benchmark.families import mla_mtp_lm  # noqa: E402
from edl_tpu.models import moe, transformer  # noqa: E402

READ = ("logits_rel_err", "mtp_logits_rel_err", "router_logits_rel_err", "flipped_share",
        "query_rel_err", "loss_rel_err", "mtp_loss_rel_err", "tokens_misrouted")
LIMITS = dict(logits_rel_err=mla_mtp_lm.LOGITS_REL_TOL,
              mtp_logits_rel_err=mla_mtp_lm.LOGITS_REL_TOL,
              router_logits_rel_err=mla_mtp_lm.ROUTER_LOGITS_REL_TOL,
              flipped_share=mla_mtp_lm.ROUTE_FLIP_LIMIT,
              query_rel_err=mla_mtp_lm.QUERY_REL_TOL)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--rehearse", action="store_true", help="the toy twin, on the CPU")
    parser.add_argument("--seed", type=int, default=3000005501)
    args = parser.parse_args()
    where = ("rehearsal", "configs") if args.rehearse else ("configs",)
    with open(os.path.join(ROOT, "benchmark", *where, "glm_4_7_flash.json")) as f:
        config = json.load(f)

    moe.grouped_matmul = in_bfloat16(moe.grouped_matmul)
    transformer.attention = in_bfloat16(transformer.attention)
    rope = transformer.rope  # jax promotes no 8-bit float: rotate it as float32

    def rotated(x, positions, theta):
        if x.dtype.itemsize > 1:
            return rope(x, positions, theta)
        return rope(x.astype(jnp.float32), positions, theta).astype(x.dtype)

    transformer.rope = rotated

    built = mla_mtp_lm.build(mla_mtp_lm.as_drawn(config), 1, args.seed)
    model = built["model"]
    tokens = mla_mtp_lm.host_batches(config, 1, args.seed, n_batches=1)[0][0]
    variables = jax.jit(model.init)(jax.random.PRNGKey(args.seed % (2 ** 31)), tokens)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "glm_precision_below.jsonl"), "a")
    for dtype in ("bfloat16", "float8_e4m3fn"):
        t0 = time.time()
        line = {"dtype": dtype, "seed": args.seed, "backend": jax.default_backend(),
                "seq_len": config["train"]["seq_len"], "hidden": config["hidden_size"],
                "limits": LIMITS}
        try:
            coarse = model.clone(dtype=getattr(jnp, dtype))
            state = types.SimpleNamespace(
                params=variables["params"], batch_stats=variables["batch_stats"],
                apply_fn=coarse.apply,
            )
            result = mla_mtp_lm.check(config, state, args.seed)
            line.update(ok=result["ok"], **{k: result[k] for k in READ})
            line["over_limit"] = {k: result[k] / v for k, v in LIMITS.items()}
            line["flipped_share_by_layer"] = result["flipped_share_by_layer"]
            line["kernel"] = {k: v for k, v in result["kernel"].items() if "err" in k}
        except Exception as exc:  # noqa: BLE001 — the other precision still reads
            line["error"] = repr(exc)[:2000]
        line["seconds"] = round(time.time() - t0, 1)
        text = json.dumps(line, default=float)
        print(text, flush=True)
        out.write(text + "\n")
        out.flush()


if __name__ == "__main__":
    main()
