"""A cell's train step compiled for a *described* v5e (no chip attached) from
a tree, and two such compiles compared: the ENTRY instructions'
``estimated_cycles`` summed by opcode and jax ``op_name`` (layer numbers struck
out), then the difference of two tables. The cycles are no milliseconds (PR 38
read 2.3 M of them a millisecond of a fused dW), a Pallas call has none, and a
loop's body is not in ENTRY; what the sign and the operations of a difference
say has agreed with the traced runs since PR 54 (a neighbour that loses its
operand's prefetch into VMEM shows as the same fusion at more cycles, its
``copy-done`` operand gone), and PR 58 chose its kernels' layout by it.

    JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \\
        python3 bench_results/step_cycles.py compile <tree> <cell> <out.hlo>     # 1-4 min
    python3 bench_results/step_cycles.py diff <parent.hlo> <change.hlo> [scope]

``scope`` (``gdn_scan``) folds every instruction outside it into one line.
"""
import collections
import os
import re
import sys


def compile_step(tree, cell_name, out):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    tree = os.path.abspath(tree)
    out = os.path.abspath(out)
    sys.path.insert(0, tree)
    os.chdir(tree)
    from unittest import mock

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import run as bench_run
    from edl_tpu.obs import numerics as obs_numerics
    from edl_tpu.train import create_state, make_train_step

    finder = bench_run.Finder(os.path.join(tree, "BENCHMARK.json"))
    cell = bench_run.find(finder.bench["workloads"], cell_name, "workload")
    entry = bench_run.find(finder.bench["configs"], cell["config"], "configuration")
    config = bench_run.load_json(finder.base, entry["file"])
    family = finder.module("families", config["family"])
    batch_size = config["train"]["batch_per_chip"]
    job = family.build(config, batch_size, 0)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]), ("dp",))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    state = jax.eval_shape(lambda: create_state(
        job["model"], jax.random.PRNGKey(0), job["sample_input"], job["optimizer"]
    ))
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), state)
    batch = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rows),
        family.host_batches(config, batch_size, 0, n_batches=1)[0],
    )
    step = make_train_step(job["loss"], job["apply_kwargs"], numerics=obs_numerics.enabled())
    # the dispatches ask jax.default_backend(): steered, in this script only
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        compiled = step.lower(state, batch).compile()
    text = compiled.as_text()
    with open(out, "w") as f:
        f.write(text)
    print(out, "temp_gb", compiled.memory_analysis().temp_size_in_bytes / 1e9,
          "custom_calls", text.count('custom_call_target="tpu_custom_call"'))


def table(path, scope):
    text = open(path).read()
    cycles, counts = collections.Counter(), collections.Counter()
    for line in text[text.index("\nENTRY"):].splitlines():
        found = re.search(r'"estimated_cycles":"(\d+)"', line)
        if not found:
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        opcode = re.search(r" ([\w-]+)\(", line[:4096])
        name = re.sub(r"layer_\d+", "layer_N", op_name.group(1)) if op_name else "?"
        if scope and scope not in name:
            name = "(elsewhere)"
        key = (opcode.group(1) if opcode else "?") + " " + name[-110:]
        cycles[key] += int(found.group(1))
        counts[key] += 1
    return cycles, counts, text.count('custom_call_target="tpu_custom_call"')


def diff(parent, change, scope=""):
    a, a_n, a_calls = table(parent, scope)
    b, b_n, b_calls = table(change, scope)
    print("total", sum(a.values()), "->", sum(b.values()), "custom calls", a_calls, "->", b_calls)
    rows = sorted(
        ((b[k] - a[k], a[k], b[k], a_n[k], b_n[k], k) for k in set(a) | set(b)),
        key=lambda row: -abs(row[0]),
    )
    for row in rows[:60]:
        print("%+10d %10d %10d  %3d %3d  %s" % row)


if __name__ == "__main__":
    {"compile": compile_step, "diff": diff}[sys.argv[1]](*sys.argv[2:])
