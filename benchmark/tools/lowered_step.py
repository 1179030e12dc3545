"""Say whether a change to the program left a cell's train step what it was:
print, for each cell named, the sha256 of its lowered step (StableHLO text at
the configuration's real widths, for a described v5e, nothing compiled). Run
it from the root of each of two checkouts and compare the lines:

    JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \\
        python3 <tree>/benchmark/tools/lowered_step.py <cell> [<cell> ...]

The text holds no source location of its own, but a Pallas kernel's body is
Mosaic bytecode that does (file paths, line numbers): the bodies are cut out,
and counted, before the hash. Two trees whose kernels' sources differ need
those read by hand (``git diff`` of ``edl_tpu/ops/``). Cells share jax's caches
inside one process, so name them in the same order on both sides.
"""

import hashlib
import importlib
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

BODY = re.compile(r'\\22body\\22: \\22[^"\\]*\\22')


def main(cells):
    from unittest import mock

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import run as bench_run
    from edl_tpu.obs import numerics as obs_numerics
    from edl_tpu.train import create_state, make_train_step

    # a tree from before ``_vmem_capacity`` learnt to fall back asks the chip
    attention = importlib.import_module("edl_tpu.ops.attention")
    if hasattr(attention, "_VMEM_V5E"):
        attention._vmem_capacity = lambda: attention._VMEM_V5E
    finder = bench_run.Finder(os.path.join(ROOT, "BENCHMARK.json"))
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for name in cells:
        cell = bench_run.find(finder.bench["workloads"], name, "workload")
        entry = bench_run.find(finder.bench["configs"], cell["config"], "configuration")
        config = bench_run.load_json(finder.base, entry["file"])
        family = finder.module("families", config["family"])
        chips = cell["chips"]
        global_batch = config["train"]["batch_per_chip"] * chips
        job = family.build(config, global_batch, 0)
        mesh = Mesh(np.array(topo.devices[:chips]), ("dp",))
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
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            text = step.lower(state, batch).as_text()
        text, bodies = BODY.subn("BODY", text)
        print("%s lines=%d kernel_bodies=%d sha256=%s" % (
            name, len(text.splitlines()), bodies,
            hashlib.sha256(text.encode()).hexdigest(),
        ))


if __name__ == "__main__":
    main(sys.argv[1:])
