"""Device meshes and sharding helpers — the TPU data plane.

Where the reference delegates its data plane to NCCL allreduce inside
Paddle fleet (SURVEY §2 comms row: EDL only passes ``nccl_comm_num`` and
endpoints through, train_with_fleet.py:92-93), the edl_tpu compute path is
jit/pjit over a ``jax.sharding.Mesh``: gradients of replicated parameters
against dp-sharded batches make XLA insert the all-reduce over ICI/DCN
itself; hierarchical allreduce, overlap, and topology mapping are the
compiler's job, not flags.

Axis conventions (used across models and train steps):
  ``dp``   data parallel (batch axis)
  ``fsdp`` parameter/optimizer sharding (zero-style)
  ``tp``   tensor parallel (hidden dims)
  ``sp``   sequence/context parallel (ring attention)
  ``ep``   expert parallel (MoE)
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DEFAULT_AXES = ("dp", "fsdp", "tp", "sp", "ep")


def make_mesh(
    axes: Optional[Dict[str, int]] = None, devices: Optional[Sequence] = None
) -> Mesh:
    """Build a mesh from an axis->size dict; one axis may be -1 (fill).

    ``make_mesh()`` = pure data parallel over every visible device.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if axes is None:
        axes = {"dp": n}
    axes = dict(axes)
    fills = [k for k, v in axes.items() if v == -1]
    if len(fills) > 1:
        raise ValueError("only one axis may be -1, got %r" % fills)
    fixed = math.prod(v for v in axes.values() if v != -1)
    if fills:
        if n % fixed:
            raise ValueError("cannot fill %r: %d devices / %d" % (fills[0], n, fixed))
        axes[fills[0]] = n // fixed
    if math.prod(axes.values()) != n:
        raise ValueError("axes %r do not cover %d devices" % (axes, n))
    shape = tuple(axes.values())
    try:
        # topology-aware placement: keeps inner axes on ICI neighbors
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(
            shape, devices=list(devices)
        )
    except (ImportError, ValueError, AssertionError):
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, tuple(axes))


def make_hybrid_mesh(
    dcn_axes: Dict[str, int],
    ici_axes: Dict[str, int],
    devices: Optional[Sequence] = None,
    slice_count: Optional[int] = None,
) -> Mesh:
    """Multi-slice mesh: ``dcn_axes`` span slices (data-center network),
    ``ici_axes`` stay within a slice (chip interconnect).

    The scaling-book recipe for multislice TPU: communication-heavy axes
    (tp/fsdp/sp) must ride ICI inside one slice; only gradient-size
    traffic (dp) should cross the slower DCN. Axis order in the mesh is
    dcn axes first, then ici axes, and device placement guarantees every
    ici-axis neighbor group lives inside a single slice.

    Slice membership comes from ``device.slice_index`` (real multislice
    TPU). ``slice_count`` overrides it by partitioning the device list
    evenly in order — how the CPU tests model 2 virtual slices; it also
    lets a single-slice job pretend N=1.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    dcn_size = math.prod(dcn_axes.values())
    ici_size = math.prod(ici_axes.values())
    if dcn_size * ici_size != len(devices):
        raise ValueError(
            "dcn %r x ici %r != %d devices" % (dcn_axes, ici_axes, len(devices))
        )
    if slice_count is None:
        groups: Dict[int, list] = {}
        for d in devices:
            groups.setdefault(getattr(d, "slice_index", 0), []).append(d)
        slices = [groups[k] for k in sorted(groups)]
    else:
        if len(devices) % slice_count:
            raise ValueError("%d devices / %d slices" % (len(devices), slice_count))
        per = len(devices) // slice_count
        slices = [devices[i * per : (i + 1) * per] for i in range(slice_count)]
    if len(slices) != dcn_size:
        raise ValueError(
            "dcn axes %r need %d slices, found %d" % (dcn_axes, dcn_size, len(slices))
        )
    if any(len(s) != ici_size for s in slices):
        raise ValueError("ici axes %r do not cover every slice" % (ici_axes,))
    if slice_count is None:
        # real multislice topology: let jax place devices ICI-optimally.
        # The helper requires mesh_shape and dcn_mesh_shape of EQUAL rank
        # (per-dim products give the final dims), so pad each side with 1s:
        # dims = (dcn..., 1...) * (1..., ici...) -> dcn dims then ici dims.
        try:
            from jax.experimental import mesh_utils

            n_dcn, n_ici = len(dcn_axes), len(ici_axes)
            dev_array = mesh_utils.create_hybrid_device_mesh(
                (1,) * n_dcn + tuple(ici_axes.values()),
                tuple(dcn_axes.values()) + (1,) * n_ici,
                devices=devices,
            )
            return Mesh(dev_array, tuple(dcn_axes) + tuple(ici_axes))
        except (ImportError, AttributeError):
            pass  # old jax: manual layout below
        except ValueError as exc:
            # jax raises ValueError both for missing slice metadata (CPU /
            # old runtimes — fallback is correct) and for genuine topology
            # misconfiguration (fallback would silently degrade ICI
            # locality), so the fallback must not be silent
            import warnings

            warnings.warn(
                "create_hybrid_device_mesh failed (%s); falling back to "
                "device-order layout whose intra-slice placement is not "
                "ICI-optimized" % (exc,),
                RuntimeWarning,
                stacklevel=2,
            )
    # slice_count override (virtual slices) — the documented in-order
    # partition IS the layout; the helper would regroup by real
    # slice_index and silently ignore the override
    per_slice = [
        np.asarray(s).reshape(tuple(ici_axes.values())) for s in slices
    ]
    dev_array = np.stack(per_slice).reshape(
        tuple(dcn_axes.values()) + tuple(ici_axes.values())
    )
    return Mesh(dev_array, tuple(dcn_axes) + tuple(ici_axes))


def batch_sharding(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    """Leading-dim sharding for batches over the data axis."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def device_put_global(x, sharding: NamedSharding):
    """Place a host value onto a (possibly multi-process) sharding.

    GLOBAL-value semantics: ``x`` is the whole array and EVERY process
    must pass the same value (the params case — each process computed or
    restored the identical tree). For per-process batch rows use
    ``shard_batch``/``prefetch_to_device``, whose cross-process path has
    local-rows semantics instead. Single-process meshes use plain
    ``device_put``; cross-process, the global array is assembled via
    ``make_array_from_callback`` so each process materializes only its
    addressable shards.
    """
    if getattr(sharding, "is_fully_addressable", True):
        return jax.device_put(x, sharding)
    arr = np.asarray(x)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx]
    )


def device_put_local_rows(x, sharding: NamedSharding):
    """Place per-process rows onto a (possibly multi-process) sharding.

    LOCAL-rows semantics: on a cross-process mesh each process passes
    ITS OWN rows and the global array is their concatenation — the
    dispatcher/loader pattern where every worker reads different
    records. Contrast ``device_put_global`` (same full value everywhere).
    Shared by ``shard_batch`` and ``prefetch_to_device``.
    """
    if getattr(sharding, "is_fully_addressable", True):
        return jax.device_put(x, sharding)
    return jax.make_array_from_process_local_data(sharding, np.asarray(x))


def shard_batch(mesh: Mesh, batch, axis: str = "dp"):
    """Place a batch pytree with its leading dim sharded over ``axis``
    (local-rows semantics on cross-process meshes, see
    ``device_put_local_rows``)."""
    sharding = batch_sharding(mesh, axis)
    return jax.tree.map(lambda x: device_put_local_rows(x, sharding), batch)


def _fsdp_spec(shape: Sequence[int], axis_size: int, axis: str) -> P:
    """Shard the largest divisible dim over ``axis``; replicate otherwise."""
    if not shape:
        return P()
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for dim in order:
        if shape[dim] >= axis_size and shape[dim] % axis_size == 0:
            spec = [None] * len(shape)
            spec[dim] = axis
            return P(*spec)
    return P()


def fsdp_shardings(mesh: Mesh, tree, axis: str = "fsdp"):
    """ZeRO-style shardings, one a leaf of ``tree`` (arrays or shapes):
    each tensor's largest divisible dim is split over the fsdp axis (the
    TPU-idiomatic replacement for the reference's parameter-server role
    split, SURVEY §2 C-PS row)."""
    axis_size = mesh.shape[axis]
    return jax.tree.map(
        lambda x: NamedSharding(mesh, _fsdp_spec(x.shape, axis_size, axis)),
        tree,
    )


def shard_params_fsdp(mesh: Mesh, params, axis: str = "fsdp"):
    """Place an existing host or device tree under ``fsdp_shardings``."""
    return jax.tree.map(
        device_put_global, params, fsdp_shardings(mesh, params, axis)
    )


def sharded_seq_attention(
    per_shard_fn,
    local_fn,
    q,
    k,
    v,
    mesh,
    sp_axis: str = "sp",
    dp_axis=None,
):
    """Shared jit-compatible wrapper for sequence-parallel attention
    (ring and Ulysses): ``[B, H, T, D]`` global arrays, batch over
    ``dp_axis`` when present, sequence over ``sp_axis``. ``per_shard_fn``
    runs under shard_map on ``[B, H, T/sp, D]`` shards; ``local_fn`` is
    the sp == 1 passthrough (and both must agree numerically)."""
    from jax.sharding import PartitionSpec as P

    if mesh.shape[sp_axis] == 1:
        return local_fn(q, k, v)
    batch = dp_axis if dp_axis in mesh.axis_names else None
    spec = P(batch, None, sp_axis, None)
    return jax.shard_map(
        per_shard_fn, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False,
    )(q, k, v)
