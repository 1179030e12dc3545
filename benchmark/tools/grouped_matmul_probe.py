"""Times ``edl_tpu.ops.grouped_matmul`` on the chip at a cell's own shapes:
both implementations (``pallas``: Megablox, at a few tilings; ``ragged_dot``:
XLA's own), the value alone and the value with both gradients, for the gate/up
shape and the down shape. Run by hand, through the chip tool:

    python3 benchmark/tools/grouped_matmul_probe.py <cell> [tm,tk,tn ...]

Prints one JSON line a measurement (milliseconds a call, the median of a few,
and the TFLOP/s that is of the needed work). It judges nothing.
"""

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import run as bench_run
    from edl_tpu.ops import grouped_matmul as gm

    finder = bench_run.Finder(os.path.join(ROOT, "BENCHMARK.json"))
    cell = bench_run.find(finder.bench["workloads"], argv[0], "workload")
    entry = bench_run.find(finder.bench["configs"], cell["config"], "configuration")
    config = bench_run.load_json(finder.base, entry["file"])
    tilings = [tuple(int(v) for v in a.split(",")) for a in argv[1:]] or [gm.TILING]
    e, k = config["num_experts"], config["num_experts_per_tok"]
    d, f = config["hidden_size"], config["intermediate_size"]
    tokens = config["train"]["batch_per_chip"] * config["train"]["seq_len"]
    rows = tokens * k
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    _, chosen = jax.lax.top_k(jax.random.normal(keys[0], (tokens, e)), k)
    sizes = jnp.asarray(np.bincount(np.asarray(chosen).reshape(-1), minlength=e), jnp.int32)
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind, "rows": rows, "groups": e,
                      "largest_group": int(sizes.max())}), flush=True)

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))  # compiles
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append(time.perf_counter() - t0)
        return statistics.median(times)

    for shape, (kk, nn) in (("up", (d, f)), ("down", (f, d))):
        lhs = jax.random.normal(keys[1], (rows, kk), jnp.bfloat16)
        rhs = jax.random.normal(keys[2], (e, kk, nn), jnp.bfloat16)
        w = jax.random.normal(keys[3], (rows, nn), jnp.bfloat16)
        flop = 2.0 * rows * kk * nn
        runs = [("ragged_dot", None)] + [("pallas", t) for t in tilings]
        for implementation, tiling in runs:
            if tiling is not None:
                gm.TILING = tiling

            def value(a, b, implementation=implementation):
                return gm.grouped_matmul(a, b, sizes, implementation)

            def with_grads(a, b, c, value=value):
                out, vjp = jax.vjp(value, a, b)
                return (out, *vjp(c))

            for what, fn, args, work in (
                ("value", jax.jit(value), (lhs, rhs), flop),
                ("value+grads", jax.jit(with_grads), (lhs, rhs, w), 3 * flop),
            ):
                try:
                    s = timed(fn, *args)
                    print(json.dumps({
                        "shape": shape, "implementation": implementation,
                        "tiling": tiling, "what": what, "ms": 1e3 * s,
                        "tflops": work / s / 1e12,
                    }), flush=True)
                except Exception as exc:  # noqa: BLE001 — a tiling the compiler refuses is a result
                    print(json.dumps({
                        "shape": shape, "implementation": implementation,
                        "tiling": tiling, "what": what,
                        "error": repr(exc)[:300],
                    }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
