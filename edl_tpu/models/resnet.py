"""ResNet-vd family in Flax — the flagship collective-training model.

Capability parity with the reference's benchmark workloads: ResNet50
(example/collective/resnet50/train_with_fleet.py) and ResNet50_vd — the
student of the distillation benchmark and the model of every baseline row
(reference README.md:68-72, 144-147).

The *vd* ("bag of tricks", He et al. 2019) differences from vanilla
ResNet, implemented as in the paper (not ported from Paddle code):
  - deep stem: three 3x3 convs (stride 2 on the first) replacing the 7x7;
  - downsample shortcuts: stride-2 average-pool then 1x1 stride-1 conv, so
    no activations are discarded by strided 1x1 convs.

TPU notes: NHWC layouts (XLA:TPU native), bf16 compute with fp32
parameters/batch-norm statistics by default (the TPU replacement for the
reference's AMP/fp16 flags, train_with_fleet.py:68-73), and all convs are
static-shaped so they tile cleanly onto the MXU.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

from edl_tpu.obs import trace as obs_trace

ModuleDef = Any


class BottleneckVd(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck with the vd avg-pool downsample."""

    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = nn.relu(self.norm()(y))
        y = self.conv(self.filters, (3, 3), strides=(self.strides, self.strides))(y)
        y = nn.relu(self.norm()(y))
        y = self.conv(self.filters * 4, (1, 1))(y)
        # final BN of each block: scale init handled by norm factory
        y = self.norm(scale_init=nn.initializers.zeros)(y)

        if residual.shape != y.shape:
            if self.strides > 1:  # vd trick: pool first, then 1x1 stride-1
                residual = nn.avg_pool(
                    residual,
                    (self.strides, self.strides),
                    strides=(self.strides, self.strides),
                    padding="SAME",
                )
            residual = self.conv(self.filters * 4, (1, 1))(residual)
            residual = self.norm()(residual)
        return nn.relu(residual + y)


class BasicBlockVd(nn.Module):
    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), strides=(self.strides, self.strides))(x)
        y = nn.relu(self.norm()(y))
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            if self.strides > 1:
                residual = nn.avg_pool(
                    residual,
                    (self.strides, self.strides),
                    strides=(self.strides, self.strides),
                    padding="SAME",
                )
            residual = self.conv(self.filters, (1, 1))(residual)
            residual = self.norm()(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    """ResNet-vd. ``stage_sizes``: blocks per stage, e.g. (3,4,6,3)=50."""

    stage_sizes: Sequence[int]
    block: Callable = BottleneckVd
    num_classes: int = 1000
    width: int = 64
    dtype: jnp.dtype = jnp.bfloat16
    # recompute each residual block's activations in the backward instead
    # of saving them: ResNet50_vd training on v5e is HBM-BOUND (measured
    # arithmetic intensity ~80 flops/byte, roofline ceiling 0.331 — see
    # BENCH_r04), so trading recompute FLOPs for activation traffic can
    # RAISE throughput, not just cut memory
    remat: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(
            nn.Conv, use_bias=False, dtype=self.dtype, padding="SAME"
        )
        norm = partial(
            nn.BatchNorm,
            use_running_average=not train,
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.dtype,  # compute dtype; stats/params stay fp32
        )
        x = x.astype(self.dtype)
        # vd deep stem
        x = conv(self.width // 2, (3, 3), strides=(2, 2))(x)
        x = nn.relu(norm()(x))
        x = conv(self.width // 2, (3, 3))(x)
        x = nn.relu(norm()(x))
        x = conv(self.width, (3, 3))(x)
        x = nn.relu(norm()(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")

        block = nn.remat(self.block) if self.remat else self.block
        # explicit names matching the un-rematted auto-names: nn.remat
        # renames the module class (Checkpoint<Block>), which would fork
        # the param paths and make remat=True checkpoints incompatible
        block_name = getattr(self.block, "__name__", "Block")
        index = 0
        for stage, num_blocks in enumerate(self.stage_sizes):
            # host Python around the same calls: what a trace spends on a stage
            with obs_trace.span("model_trace", part="stage", layer=stage):
                for block_idx in range(num_blocks):
                    strides = 2 if stage > 0 and block_idx == 0 else 1
                    x = block(
                        filters=self.width * 2**stage,
                        strides=strides,
                        conv=conv,
                        norm=norm,
                        name="%s_%d" % (block_name, index),
                    )(x)
                    index += 1

        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x.astype(jnp.float32)


class BottleneckX(nn.Module):
    """ResNeXt bottleneck: grouped 3x3 (``cardinality`` groups) between
    1x1 projections, vd-style avg-pool downsample shortcut.

    Grouped convolutions map to ``feature_group_count`` on
    ``lax.conv_general_dilated``, which XLA:TPU tiles onto the MXU as a
    batch of small matmuls — no per-group Python loop.
    """

    filters: int  # channels of the grouped 3x3 conv
    out_filters: int
    strides: int
    cardinality: int
    conv: ModuleDef
    norm: ModuleDef

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = nn.relu(self.norm()(y))
        y = self.conv(
            self.filters,
            (3, 3),
            strides=(self.strides, self.strides),
            feature_group_count=self.cardinality,
        )(y)
        y = nn.relu(self.norm()(y))
        y = self.conv(self.out_filters, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)

        if residual.shape != y.shape:
            if self.strides > 1:
                residual = nn.avg_pool(
                    residual,
                    (self.strides, self.strides),
                    strides=(self.strides, self.strides),
                    padding="SAME",
                )
            residual = self.conv(self.out_filters, (1, 1))(residual)
            residual = self.norm()(residual)
        return nn.relu(residual + y)


class ResNeXt(nn.Module):
    """ResNeXt (Xie et al. 2017) with the vd stem/shortcuts.

    The distillation benchmark's TEACHER is ResNeXt101_32x16d_wsl
    (reference README.md:68-72, example/distill/resnet50 — served via
    Paddle Serving); here it is an in-framework Flax model served by
    ``edl_tpu.distill.serving.JaxPredictBackend``.
    """

    stage_sizes: Sequence[int]
    cardinality: int = 32
    base_width: int = 16  # group width at stage 0: 32x16d
    num_classes: int = 1000
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype, padding="SAME")
        norm = partial(
            nn.BatchNorm,
            use_running_average=not train,
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.dtype,
        )
        x = x.astype(self.dtype)
        x = conv(32, (3, 3), strides=(2, 2))(x)
        x = nn.relu(norm()(x))
        x = conv(32, (3, 3))(x)
        x = nn.relu(norm()(x))
        x = conv(64, (3, 3))(x)
        x = nn.relu(norm()(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")

        for stage, num_blocks in enumerate(self.stage_sizes):
            group_width = self.cardinality * self.base_width * 2**stage
            for block_idx in range(num_blocks):
                x = BottleneckX(
                    filters=group_width,
                    out_filters=256 * 2**stage,
                    strides=2 if stage > 0 and block_idx == 0 else 1,
                    cardinality=self.cardinality,
                    conv=conv,
                    norm=norm,
                )(x)

        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x.astype(jnp.float32)


ResNeXt101_32x16d = partial(ResNeXt, stage_sizes=(3, 4, 23, 3), base_width=16)
ResNeXt101_32x8d = partial(ResNeXt, stage_sizes=(3, 4, 23, 3), base_width=8)
ResNeXt50_32x4d = partial(ResNeXt, stage_sizes=(3, 4, 6, 3), base_width=4)

ResNet18_vd = partial(ResNet, stage_sizes=(2, 2, 2, 2), block=BasicBlockVd)
ResNet34_vd = partial(ResNet, stage_sizes=(3, 4, 6, 3), block=BasicBlockVd)
ResNet50_vd = partial(ResNet, stage_sizes=(3, 4, 6, 3))
ResNet101_vd = partial(ResNet, stage_sizes=(3, 4, 23, 3))
ResNet152_vd = partial(ResNet, stage_sizes=(3, 8, 36, 3))
