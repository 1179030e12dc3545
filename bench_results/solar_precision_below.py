"""The precision below, at a cell's own sizes, through the family's ``check``.

    python3 bench_results/solar_precision_below.py [--rehearse] [--seed N]

``benchmark/tests/test_solar_lm.py`` reads the 8-bit control at a width of 256
on the CPU; this reads it where the cell runs: ``solar_open2_250b``'s
configuration file as it is timed, freshly drawn parameters (a control needs no
training: the limits are on one forward pass), the program's model once in its
stated ``bfloat16`` and once in ``float8_e4m3fn``, each handed to
``families/solar_lm.py:check`` as ``run.py`` hands its trained state. The 8-bit
program's kernels take bfloat16 operands and their results are rounded back to
8 bits (no Pallas kernel here takes an 8-bit float), which is the CPU test's
way. One JSON line a precision, on stdout and in ``chiprun_out/``.
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

from benchmark.families import solar_lm  # noqa: E402
from edl_tpu.models import gated_delta, moe, transformer  # noqa: E402

STREAM = ("logits_rel_err", "router_logits_rel_err", "flipped_share", "loss_rel_err",
          "tokens_misrouted")
LIMITS = dict(logits_rel_err=solar_lm.LOGITS_REL_TOL,
              router_logits_rel_err=solar_lm.ROUTER_LOGITS_REL_TOL,
              flipped_share=solar_lm.ROUTE_FLIP_LIMIT)


def _is_8_bit(a):
    return hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating) and a.dtype.itemsize == 1


def in_bfloat16(fn):
    """``fn`` on bfloat16 copies of its 8-bit operands, its bfloat16 results
    rounded to the operands' 8 bits; every other call goes through as it is."""
    def wrapped(*args, **kwargs):
        small = [a for a in args if _is_8_bit(a)]
        if not small:
            return fn(*args, **kwargs)
        out = fn(*(a.astype(jnp.bfloat16) if _is_8_bit(a) else a for a in args), **kwargs)
        return jax.tree.map(
            lambda o: o.astype(small[0].dtype) if o.dtype == jnp.bfloat16 else o, out
        )
    return wrapped


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--rehearse", action="store_true", help="the toy twin, on the CPU")
    parser.add_argument("--seed", type=int, default=3000005601)
    args = parser.parse_args()
    where = ("rehearsal", "configs") if args.rehearse else ("configs",)
    with open(os.path.join(ROOT, "benchmark", *where, "solar_open2_250b.json")) as f:
        config = json.load(f)

    gated_delta.kda_rule = in_bfloat16(gated_delta.kda_rule)
    gated_delta.causal_conv_silu = in_bfloat16(gated_delta.causal_conv_silu)
    moe.grouped_matmul = in_bfloat16(moe.grouped_matmul)
    transformer.attention = in_bfloat16(transformer.attention)

    # the head as the class draws it: the cell's start puts it at zero, and a
    # control on fresh parameters would compare 0 with 0
    built = solar_lm.build(solar_lm.as_drawn(config), 1, args.seed)
    model = built["model"]
    tokens = solar_lm.host_batches(config, 1, args.seed, n_batches=1)[0][0]
    variables = jax.jit(model.init)(jax.random.PRNGKey(args.seed % (2 ** 31)), tokens)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "solar_precision_below.jsonl"), "a")
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
            result = solar_lm.check(config, state, args.seed)
            line.update(ok=result["ok"], **{k: result[k] for k in STREAM})
            line["over_limit"] = {k: result[k] / v for k, v in LIMITS.items()}
            line["rule_inputs"] = result["rule"]["inputs"]
            line["rule_rel_err"] = result["rule"]["rel_err"]
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
