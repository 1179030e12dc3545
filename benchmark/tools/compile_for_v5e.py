"""Rehearsal 3: compile a cell's whole train step for a *described* v5e (no
chip attached) and print ``memory_analysis()``. Run by hand, here:

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_for_v5e.py <cell> [key=value ...]

``key=value`` overrides a number of the configuration file for one try
(``num_hidden_layers=2``, ``train.batch_per_chip=2``). Nothing runs, so this
says whether the step compiles and fits; it says nothing about time.
"""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv):
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import run as bench_run
    from edl_tpu.obs import numerics as obs_numerics
    from edl_tpu.train import create_state, make_train_step

    finder = bench_run.Finder(os.path.join(ROOT, "BENCHMARK.json"))
    cell = bench_run.find(finder.bench["workloads"], argv[0], "workload")
    entry = bench_run.find(finder.bench["configs"], cell["config"], "configuration")
    config = bench_run.load_json(finder.base, entry["file"])
    for override in argv[1:]:
        key, value = override.split("=")
        target = config
        *parents, leaf = key.split(".")
        for p in parents:
            target = target[p]
        target[leaf] = json.loads(value)
    family = finder.module("families", config["family"])
    chips = cell["chips"]
    global_batch = config["train"]["batch_per_chip"] * chips
    job = family.build(config, global_batch, 0)

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = np.array(topo.devices[:chips])
    mesh = Mesh(devices, ("dp",))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    state = jax.eval_shape(
        lambda: create_state(job["model"], jax.random.PRNGKey(0),
                             job["sample_input"], job["optimizer"])
    )
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), state
    )
    batch = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rows),
        family.host_batches(config, global_batch, 0, n_batches=1)[0],
    )
    step = make_train_step(job["loss"], job["apply_kwargs"],
                           numerics=obs_numerics.enabled())
    # the attention dispatch asks jax.default_backend() and would take its
    # CPU branch (dense attention) here: steer it, in this script only, to
    # the branch it takes on the chip
    from unittest import mock

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        compiled = step.lower(state, batch).compile()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(state.params))
    doc = {
        "cell": cell["name"], "chips": chips, "overrides": argv[1:],
        "parameters": params,
        "argument_gb": m.argument_size_in_bytes / 1e9,
        "output_gb": m.output_size_in_bytes / 1e9,
        "alias_gb": m.alias_size_in_bytes / 1e9,
        "temp_gb": m.temp_size_in_bytes / 1e9,
        "code_gb": m.generated_code_size_in_bytes / 1e9,
        "total_gb": (m.argument_size_in_bytes + m.output_size_in_bytes
                     - m.alias_size_in_bytes + m.temp_size_in_bytes
                     + m.generated_code_size_in_bytes) / 1e9,
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "all_reduce": text.count(" all-reduce(") + text.count(" all-reduce-start("),
    }
    print(json.dumps(doc))


if __name__ == "__main__":
    main(sys.argv[1:])
