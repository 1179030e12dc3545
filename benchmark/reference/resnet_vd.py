"""Plain reference for the ``resnet_vd`` family: ResNet-D ("ResNet-vd") of
He et al., "Bag of Tricks for Image Classification with Convolutional
Neural Networks" (arXiv:1812.01187, section 4.2), forward pass in training
mode (batch statistics) and softmax cross-entropy, in straightforward
``jax.numpy`` and float32. Written from the paper; it imports nothing from
``edl_tpu.models``. It reads the program's parameter tree by its names
(flax auto-names: ``Conv_i`` / ``BatchNorm_i`` in order of use), which is
the one thing it has to share with the program.

ResNet-D against ResNet: (B) the stride of a downsampling block sits on its
3x3 convolution, not the first 1x1; (C) the 7x7 stem is three 3x3
convolutions (stride 2 on the first) of width/2, width/2, width channels;
(D) a downsampling shortcut is a 2x2 average pool of stride 2 followed by a
1x1 convolution of stride 1.

Departures from the paper, both the program's: SAME padding in the
TensorFlow sense, and the caller sets ``default_matmul_precision("highest")``
because a float32 convolution on a TPU otherwise runs in bfloat16 passes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _conv(x, kernel, stride=1):
    return jax.lax.conv_general_dilated(
        x, kernel.astype(jnp.float32), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _bn(x, p, eps):
    """Batch normalisation with the batch's own statistics (training)."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _max_pool_3x3_s2(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )


def _avg_pool(x, s):
    summed = jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, s, s, 1), (1, s, s, 1), "SAME"
    )
    return summed / float(s * s)


def _block(config, p, x, filters, stride):
    eps = config["bn_epsilon"]
    bottleneck = config["block"] == "bottleneck"
    y = x
    if bottleneck:
        y = jax.nn.relu(_bn(_conv(y, p["Conv_0"]["kernel"]), p["BatchNorm_0"], eps))
        y = jax.nn.relu(
            _bn(_conv(y, p["Conv_1"]["kernel"], stride), p["BatchNorm_1"], eps)
        )
        y = _bn(_conv(y, p["Conv_2"]["kernel"]), p["BatchNorm_2"], eps)
        n = 3
    else:
        y = jax.nn.relu(
            _bn(_conv(y, p["Conv_0"]["kernel"], stride), p["BatchNorm_0"], eps)
        )
        y = _bn(_conv(y, p["Conv_1"]["kernel"]), p["BatchNorm_1"], eps)
        n = 2
    if x.shape != y.shape:
        if stride > 1:
            x = _avg_pool(x, stride)
        x = _bn(_conv(x, p["Conv_%d" % n]["kernel"]), p["BatchNorm_%d" % n], eps)
    return jax.nn.relu(x + y)


def forward(config, params, x):
    """Logits [N, classes] for images ``x`` [N, H, W, 3], float32."""
    eps = config["bn_epsilon"]
    x = x.astype(jnp.float32)
    for i, stride in enumerate((2, 1, 1)):
        x = _conv(x, params["Conv_%d" % i]["kernel"], stride)
        x = jax.nn.relu(_bn(x, params["BatchNorm_%d" % i], eps))
    x = _max_pool_3x3_s2(x)
    name = "BottleneckVd_%d" if config["block"] == "bottleneck" else "BasicBlockVd_%d"
    index = 0
    for stage, n in enumerate(config["stage_sizes"]):
        for i in range(n):
            x = _block(
                config, params[name % index], x,
                config["width"] * 2 ** stage, 2 if stage > 0 and i == 0 else 1,
            )
            index += 1
    x = jnp.mean(x, axis=(1, 2))
    dense = params["Dense_0"]
    return x @ dense["kernel"].astype(jnp.float32) + dense["bias"]


def loss(logits, labels):
    """Mean softmax cross-entropy."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
