"""edl_tpu — a TPU-native elastic deep-learning framework.

A ground-up JAX/XLA re-design of the capabilities of wangxicoding/edl
(elastic collective training + elastic knowledge distillation):

- ``edl_tpu.store``      — built-in coordination store (lease/watch KV; the
  role etcd/redis play in the reference).
- ``edl_tpu.discovery``  — service registry, consistent hashing, liveness.
- ``edl_tpu.cluster``    — job environment and elastic-cluster data model.
- ``edl_tpu.launch``     — the elastic launcher: rank election, stage
  fencing, barriers, process supervision, stop-resume elasticity.
- ``edl_tpu.parallel``   — device meshes, sharding rules, collectives,
  sequence/context parallelism.
- ``edl_tpu.train``      — trainer loop: pjit train steps, bf16, remat.
- ``edl_tpu.checkpoint`` — sharded checkpoint/resume across topology change.
- ``edl_tpu.data``       — deterministic elastic data sharding service.
- ``edl_tpu.distill``    — elastic knowledge-distillation service layer.
- ``edl_tpu.models``     — model families (MLP, ResNet, Transformer, CTR).
- ``edl_tpu.ops``        — Pallas TPU kernels.

The compute path is JAX (jit/pjit/shard_map over ``jax.sharding.Mesh``,
collectives over ICI/DCN); the control plane is a framed-TCP protocol shared
by the Python and native C++ runtimes. Heavy deps (jax, orbax) are imported
lazily by the subpackages that need them so control-plane processes stay
lightweight.
"""

import sys as _sys
import time as _time

#: ``(time.monotonic(), len(sys.modules), jax loaded?)`` as this package's
#: import began: where the ring's ``process_boot`` span ends (obs/trace.py)
IMPORT_STAMP = (_time.monotonic(), len(_sys.modules), "jax" in _sys.modules)

__version__ = "0.1.0"

__all__ = ["__version__"]
