"""Family ``resnet_vd``: builds the program's ResNet-vd job from a
configuration file, makes its host batches, counts its operations from
shapes and checks the trained parameters against the plain reference.

A family is everything the harness needs to know about one kind of model.
``run.py`` imports it by the ``family`` key of the configuration file and
calls ``build``, ``host_batches``, ``flops_per_item`` and ``check``.
"""

from __future__ import annotations

import numpy as np

# Logits of the program (bfloat16 convolutions, float32 accumulation and
# batch statistics) against the float32 reference, as max |difference| over
# max |reference|. 53 convolutions, each rounding its input and kernel to
# bfloat16 (8 bits of mantissa), compound to 0.0039-0.0066 at the logits
# over 53 runs on the chip, every cell and seed (my chip runs, PR 22: PERF.md
# section 6). The tolerance is 2.3 times the largest of them: an 8-bit float
# type has 3 to 5 fewer bits of mantissa, so 8 to 32 times the rounding
# error a value, and cannot pass it.
LOGITS_REL_TOL = 0.015
LOSS_REL_TOL = 0.02


def _input_dtype(config):
    import ml_dtypes  # noqa: F401 — teaches numpy the name "bfloat16"

    return np.dtype(config["input_dtype"])


def _blocks(config):
    """(filters, stride) of every residual block, in order."""
    out = []
    for stage, n in enumerate(config["stage_sizes"]):
        for i in range(n):
            out.append((config["width"] * 2 ** stage, 2 if stage > 0 and i == 0 else 1))
    return out


def build(config, global_batch, seed):
    import optax

    from edl_tpu.models.resnet import BasicBlockVd, BottleneckVd, ResNet
    from edl_tpu.train import make_cross_entropy_loss

    block = {"bottleneck": BottleneckVd, "basic": BasicBlockVd}[config["block"]]
    model = ResNet(
        stage_sizes=tuple(config["stage_sizes"]), block=block,
        num_classes=config["num_classes"], width=config["width"],
    )
    opt = config["train"]["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError("resnet_vd: unknown optimizer %r" % opt["name"])
    size = config["image_size"]
    return {
        "model": model,
        "optimizer": optax.sgd(opt["lr"], momentum=opt["momentum"]),
        "loss": make_cross_entropy_loss(config["train"]["top_k"]),
        "sample_input": np.zeros((global_batch, size, size, 3), _input_dtype(config)),
        "apply_kwargs": {"train": True},
        "items_per_step": global_batch,
    }


def _items(config, seed, n):
    """``n`` images and labels from the seed (chip_smoke's generator)."""
    rs = np.random.default_rng(seed)
    size = config["image_size"]
    x = rs.standard_normal((n, size, size, 3), dtype=np.float32)
    y = rs.integers(0, config["num_classes"], (n,)).astype(np.int32)
    return x.astype(_input_dtype(config)), y


def host_batches(config, global_batch, seed, n_batches=2):
    """A pool of distinct host batches for the feed to cycle (bench.py's
    pipeline mode). Batches are drawn, by seeded index, from a set of
    ``distinct_items`` images: generating every image of a 1024-image batch
    anew would take longer than the rest of set-up."""
    x, y = _items(config, seed, config["train"]["distinct_items"])
    rs = np.random.default_rng(seed + 1)
    pool = []
    for _ in range(n_batches):
        idx = rs.permutation(len(x))
        idx = np.resize(idx, global_batch)
        pool.append((np.ascontiguousarray(x[idx]), y[idx]))
    return pool


def _conv_shapes(config):
    """Every convolution as (k, c_in, c_out, h_out) and the dense layer's
    (c_in, c_out), following the architecture of arXiv:1812.01187."""
    h = config["image_size"]
    w = config["width"]
    convs = []

    def conv(k, cin, cout, stride=1):
        nonlocal h
        h = -(-h // stride)  # SAME padding
        convs.append((k, cin, cout, h))

    conv(3, 3, w // 2, 2)
    conv(3, w // 2, w // 2)
    conv(3, w // 2, w)
    h = -(-h // 2)  # max pool
    cin = w
    bottleneck = config["block"] == "bottleneck"
    for filters, stride in _blocks(config):
        cout = filters * 4 if bottleneck else filters
        h_in = h
        if bottleneck:
            conv(1, cin, filters)
            conv(3, filters, filters, stride)
            conv(1, filters, cout)
        else:
            conv(3, cin, filters, stride)
            conv(3, filters, cout)
        if cin != cout or stride > 1:
            # shortcut: average pool, then 1x1 at stride 1
            convs.append((1, cin, cout, -(-h_in // stride)))
        cin = cout
    return convs, (cin, config["num_classes"])


def flops_per_item(config):
    """Operations the forward and backward passes need for one image:
    2 per multiply-add; the backward pass computes a gradient for the input
    and one for the kernel of every layer (twice the forward) except the
    first convolution, whose input needs none. Recomputation, batch norm,
    pooling and the optimizer are not counted."""
    convs, (d_in, d_out) = _conv_shapes(config)
    total = 0.0
    for i, (k, cin, cout, h) in enumerate(convs):
        fwd = 2.0 * k * k * cin * cout * h * h
        total += fwd * (2.0 if i == 0 else 3.0)
    return total + 3.0 * 2.0 * d_in * d_out


def check(config, state, seed):
    """The program's forward pass and loss on the trained parameters against
    the plain reference, on a seeded sample. Returns a dict with ``ok``."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import resnet_vd as reference
    from edl_tpu.train import make_cross_entropy_loss

    n = config["check"]["sample_items"]
    x, y = _items(config, seed + 7, n)
    one = jax.devices()[0]
    params = jax.device_put(jax.device_get(state.params), one)
    stats = jax.device_put(jax.device_get(state.batch_stats), one)
    x, y = jax.device_put((x, y), one)

    @jax.jit
    def program(params, stats, x, y):
        logits, _ = state.apply_fn(
            {"params": params, "batch_stats": stats}, x, train=True,
            mutable=["batch_stats"],
        )
        loss, _ = make_cross_entropy_loss(config["train"]["top_k"])(logits, y)
        return logits, loss

    got_logits, got_loss = program(params, stats, x, y)
    @jax.jit
    def plain(params, x, y):
        logits = reference.forward(config, params, x.astype(jnp.float32))
        return logits, reference.loss(logits, y)

    with jax.default_matmul_precision("highest"):
        want_logits, want_loss = plain(params, x, y)
    got = np.asarray(got_logits, np.float64)
    want = np.asarray(want_logits, np.float64)
    rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    loss_rel = float(abs(float(got_loss) - float(want_loss)) / abs(float(want_loss)))
    # the rehearsal's toy network states its own: 8 channels wide and fitted
    # to 16 images for hundreds of steps, its logits swing more a rounding
    tol = config["check"].get("logits_rel_tol", LOGITS_REL_TOL)
    ok = bool(np.isfinite(got).all() and rel <= tol and loss_rel <= LOSS_REL_TOL)
    return {
        "ok": ok, "logits_rel_err": rel, "logits_rel_tol": tol,
        "loss": float(got_loss), "reference_loss": float(want_loss),
        "loss_rel_err": loss_rel, "loss_rel_tol": LOSS_REL_TOL,
        "sample_items": n,
    }
