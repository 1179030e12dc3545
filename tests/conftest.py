"""Shared test config.

JAX tests run on a virtual 8-device CPU mesh (multi-chip shardings are
validated without TPU hardware); the env must be set before jax import, so
it is done here at conftest import time. Control-plane tests (store,
discovery, launch) never import jax.
"""

import os

# force-override: the session env may name the real TPU
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("EDL_LOG_LEVEL", "INFO")

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# -- shared launcher-test helpers (used by test_launch + test_harness) -------

from collections import defaultdict

import pytest

TOY_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy_worker.py")

# The expert layer's call shapes in the benchmark's expert cells: (held groups,
# the rows of the held experts' buffer as ``models/moe.py`` sizes it, the whole
# N * k, the width an expert reads, its own width).
EXPERT_CELL_SHAPES = {
    "olmoe_1b_7b": (64, 131072, 131072, 2048, 1024),  # all 64 held: no buffer
    "trinity_mini": (16, 16384, 65536, 2048, 1024),
    "lfm2_24b_a2b": (8, 8192, 32768, 2048, 1536),  # 1024 does not divide 1536
    "keye_vl_2_0_30b_a3b": (16, 32768, 131072, 2048, 768),
    "ling_3_0_flash_vl": (8, 2048, 65536, 2560, 768),
    "nemotron_3_super_120b_a12b": (8, 5632, 180224, 1024, 2688),  # 2688 = 21 lane tiles
    "solar_open2_250b": (8, 3280, 65536, 4096, 1280),  # no row tile divides 3280
    "glm_4_7_flash": (8, 8192, 32768, 2048, 1536),
}


@pytest.fixture()
def store():
    from edl_tpu.store.server import StoreServer

    srv = StoreServer(host="127.0.0.1", port=0).start()
    yield srv
    srv.stop()


@pytest.fixture()
def unfence(monkeypatch):
    """``unfence()`` takes the attention projections' dW fence
    (``models/transformer.py:_dw_apart``) off for the rest of the test: the
    layer then lowers as plain ``nn.DenseGeneral`` does. Build fresh functions
    after it: ``jax.checkpoint`` and ``jax.jit`` keep a traced one."""

    def off():
        from edl_tpu.models import transformer

        monkeypatch.setattr(transformer, "_dw_apart", lambda name, kernel: kernel)

    return off


def value_and_gradients(fn, *args, weight=None, argnums=(0, 1)):
    """``(value, gradients)`` of ``fn(*args)``, the gradients of its sum under
    the cotangent ``weight`` (ones without one) with respect to ``argnums``,
    as ONE jitted program. What the cases of a ``[value | gradients | <leaf>]``
    family compare is computed once a module through this (an eager run pays
    a compile for every primitive of the model, forward and backward, and
    once more in every case that runs it again)."""
    import jax
    import jax.numpy as jnp

    def scalar(*a):
        out = fn(*a)
        return jnp.sum(out if weight is None else out * weight), out

    (_, value), grads = jax.jit(
        jax.value_and_grad(scalar, argnums=argnums, has_aux=True)
    )(*args)
    return value, grads


def loss_logits_gradients(fn, params):
    """``(loss, logits, gradients)`` of ``fn(params) -> (loss, logits)`` as ONE
    jitted program: what the cases of a ``[logits | loss | gradients]`` family
    compare, computed once a module (see :func:`value_and_gradients`)."""
    import jax

    (loss, logits), grads = jax.jit(jax.value_and_grad(fn, has_aux=True))(params)
    return loss, logits, grads


def incarnations(out_dir):
    """toy_worker marker files -> {stage: {rank: world}}"""
    out = defaultdict(dict)
    for name in os.listdir(out_dir):
        if name.startswith("run."):
            _, stage, rank, world = name.split(".")
            out[stage][int(rank)] = int(world)
    return out
