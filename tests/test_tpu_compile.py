"""The flash kernels against the real TPU compiler, without a TPU.

CPU interpret mode does not check Mosaic tiling, VMEM budgets or
``dimension_semantics``: a kernel can pass every interpret-mode test and
still be refused by the chip's compiler. libtpu compiles for a *described*
``v5e:2x2`` topology with no chip attached, so each kernel of the training
path is lowered with ``interpret=False`` at real widths and compiled here.

A compile that passes is a compile, not a chip run: nothing executes, so
these cases say nothing about values or time (``chip_smoke.py`` does).
The public entry points (``attention``, ``flash_attention``) ask
``jax.default_backend()``, see the CPU and take interpret mode or the dense
reference — the cases call the ``*_forward`` / ``*_backward_kernels``
functions directly.
"""

import importlib
import math
import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp

import jax
import jax.numpy as jnp
from conftest import EXPERT_CELL_SHAPES

A = importlib.import_module("edl_tpu.ops.attention")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 — no libtpu here: nothing to ask
        pytest.skip("TPU topology cannot be described here: %s" % exc)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A described-device executable is written to the persistent cache but
    cannot be read back without a chip (the next compile warns and compiles
    again) — keep these compiles out of whatever cache an earlier test
    armed in this process."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


# (b, h, h_kv, t, d), bf16, causal
LM = (16, 16, 16, 2048, 64)        # chip_smoke's lm phase: b16 x seq 2048
HD128 = (2, 8, 8, 4096, 128)
GQA = (2, 16, 4, 2048, 64)
LONG = (1, 16, 16, 8192, 64)       # chip_smoke's long comparison shape
GRANITE = (1, 32, 8, 8192, 64)     # granite_4_0_h_micro.steady's attention layer
TRINITY = (1, 32, 4, 8192, 128)    # trinity_mini.steady's attention layers
MISTRAL = (2, 32, 8, 4096, 128)    # mistral_7b.steady's, a half batch a call
OLMOE = (4, 16, 16, 4096, 128)     # olmoe_1b_7b.steady's
OLMO_HYBRID = (1, 15, 15, 8192, 128)  # olmo_hybrid_7b.steady's full layer: MHA

FWD_NAME = "_flash2_kernel"
# one fused kernel: a head's float32 dq accumulator (4 MB at 8192 x 128)
# stays in VMEM under a limit set from the shapes
BWD_NAMES = ("_flash2_bwd_kernel",)

CASES = [
    pytest.param(direction, shape, id="flash2-%s-%s" % (direction, name))
    for name, shape in (
        ("lm", LM),
        ("hd128", HD128),
        ("gqa", GQA),
        ("seq8192", LONG),
        ("granite", GRANITE),
        # the other LM cells' shapes, as their steps call the kernels
        ("trinity_full", TRINITY),
        ("mistral", MISTRAL),
        ("olmoe", OLMOE),
        ("olmo_hybrid", OLMO_HYBRID),
    )
    for direction in ("fwd", "bwd")
]


def _kernel_names(lowered_text):
    return re.findall(r'kernel_name = "(\w+)"', lowered_text)


@pytest.mark.parametrize("direction,shape", CASES)
def test_kernel_compiles_for_v5e(one_chip, direction, shape):
    b, h, h_kv, t, d = shape
    scale = d ** -0.5

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    q, kv = sds((b, h, t, d)), sds((b, h_kv, t, d))
    if direction == "fwd":
        bq, bk = A._FLASH2_BLOCKS_FWD
        fn = lambda q, k, v: A._flash2_forward(q, k, v, True, scale, bq, bk, False)
        args = (q, kv, kv)
        want = [FWD_NAME]
    else:
        bq, bk = (A._fit_block(blk, t) for blk in A._FLASH2_BLOCKS_BWD)
        fn = lambda q, k, v, g, lse, delta: A._flash2_backward_kernels(
            q, k, v, g, lse, delta, True, scale, bq, bk, False
        )
        row = sds((b * h, t), jnp.float32)
        args = (q, kv, kv, q, row, row)
        want = list(BWD_NAMES)

    lowered = jax.jit(fn).lower(*args)
    # the kernel itself was lowered — not the ragged-shape dense fallback
    assert _kernel_names(lowered.as_text()) == want
    compiled = lowered.compile()  # raises what the chip's compiler would
    assert compiled.as_text().count("tpu_custom_call") == len(want)


# (shape, tk where it is not tq, causal) of a call through `flash_attention`
CALLS = [
    pytest.param(MISTRAL, None, True, id="mistral"),
    pytest.param(OLMOE, None, True, id="olmoe"),
    # what the whole-KV kernels served until PR 53 (tq <= 2048, tk <= 4096):
    # the compiler's word that the one family takes every shape the two did
    pytest.param(LM, None, True, id="lm"),
    pytest.param((4, 16, 16, 1024, 64), None, True, id="t1024-d64"),
    pytest.param((4, 32, 8, 1024, 128), None, True, id="t1024-gqa8-d128"),
    pytest.param((4, 16, 16, 512, 64), None, True, id="t512-d64"),
    pytest.param((4, 32, 8, 512, 128), None, True, id="t512-gqa8-d128"),
    pytest.param((2, 32, 8, 2048, 128), None, True, id="t2048-gqa8-d128"),
    pytest.param((4, 16, 16, 1024, 64), None, False, id="t1024-not-causal"),
    pytest.param((4, 16, 16, 1024, 64), 4096, True, id="tq1024-tk4096"),
]


@pytest.mark.parametrize("shape,tk,causal", CALLS)
def test_a_call_compiles_for_v5e_as_one_forward_and_one_fused_backward(
    one_chip, monkeypatch, shape, tk, causal
):
    """A call through the entry point the cells' kernel check calls:
    `flash_attention` is `attention()`'s kernels, and value and gradients
    are two custom calls, the flash2 forward and the one fused backward,
    with the blocks `_flash2_blocks` gives the shape."""
    b, h, h_kv, t, d = shape
    monkeypatch.setattr(A, "_interpret", lambda: False)

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def value_and_grads(q, k, v, w):
        out, vjp = jax.vjp(lambda q, k, v: A.flash_attention(q, k, v, causal=causal), q, k, v)
        return (out, *vjp(w))

    q, kv = sds((b, h, t, d)), sds((b, h_kv, tk or t, d))
    lowered = jax.jit(value_and_grads).lower(q, kv, kv, q)
    assert _kernel_names(lowered.as_text()) == ["_flash2_kernel", "_flash2_bwd_kernel"]
    assert lowered.compile().as_text().count("tpu_custom_call") == 2


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("window", [2048, 1000], ids=["w2048", "w1000"])
def test_windowed_kernel_compiles_for_v5e(one_chip, direction, window):
    """The flash2 kernels under a window at Trinity-Mini's shape (GQA 32:4 x
    128, T = 8192, the published 2048 and a window nothing divides), with the
    blocks the dispatch gives a windowed call: spans that start at an element
    (``pl.Element`` in every dimension, an offset Mosaic is told is a whole
    tile's multiple) are Mosaic's to accept, not interpret mode's. The grid's
    innermost dimension is the spans a block needs, not every block: one
    forward update a q block at the published window."""
    b, h, h_kv, t, d = TRINITY

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    q, kv = sds((b, h, t, d)), sds((b, h_kv, t, d))
    fwd, dq, dkv = (A._flash2_blocks(kind, t, t, window) for kind in ("fwd", "dq", "bwd"))
    if window == 2048:
        assert (fwd, dq, dkv) == ((512, 2560), (256, 2304), (1280, 512))
        assert A._span_steps(window, *fwd, t, t)[0] == 1
    if direction == "fwd":
        fn = lambda q, k, v: A._flash2_forward(
            q, k, v, True, d ** -0.5, *fwd, False, window
        )
        args, want = (q, kv, kv), [FWD_NAME]
    else:
        fn = lambda q, k, v, g, lse, delta: A._flash2_backward_kernels(
            q, k, v, g, lse, delta, True, d ** -0.5, *dq, False, window, dkv
        )
        row = sds((b * h, t), jnp.float32)
        args, want = (q, kv, kv, q, row, row), list(BWD_NAMES)
    kv_steps, _ = A._span_steps(window, *dq, t, t)
    _, q_steps = A._span_steps(window, *dkv, t, t)
    assert kv_steps * dq[1] < t / 2 and q_steps * dkv[0] < t / 2
    lowered = jax.jit(fn).lower(*args)
    assert _kernel_names(lowered.as_text()) == want
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") == len(want)


@pytest.mark.parametrize("shape,window,capacity", [
    pytest.param(TRINITY, None, 0, id="trinity_full"),
    pytest.param(TRINITY, 2048, 0, id="trinity_w2048"),
    pytest.param(GRANITE, None, 0, id="granite"),
    # 4160 = 65 x 64: the largest block that divides it is half a lane tile,
    # which lse and delta cannot ride along the lanes
    pytest.param((1, 4, 2, 4160, 64), None, None, id="rows_of_half_a_lane_tile"),
])
def test_the_two_kernel_backward_compiles_for_v5e(
    one_chip, monkeypatch, shape, window, capacity
):
    """Where a head's dq accumulator does not fit the core's VMEM, or a span
    of rows is not whole lane tiles, the call keeps dq and dk/dv, with the
    blocks the dispatch gives them, under Mosaic's default limit."""
    if capacity is not None:
        monkeypatch.setattr(A, "_vmem_capacity", lambda: capacity)
    b, h, h_kv, t, d = shape

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    q, kv, row = sds((b, h, t, d)), sds((b, h_kv, t, d)), sds((b * h, t), jnp.float32)
    dq, dkv = (A._flash2_blocks(kind, t, t, window) for kind in ("dq", "bwd"))
    fn = lambda q, k, v, g, lse, delta: A._flash2_backward_kernels(
        q, k, v, g, lse, delta, True, d ** -0.5, *dq, False, window, dkv
    )
    lowered = jax.jit(fn).lower(q, kv, kv, q, row, row)
    assert _kernel_names(lowered.as_text()) == [
        "_flash2_bwd_dq_kernel", "_flash2_bwd_dkv_kernel"
    ]
    assert lowered.compile().as_text().count("tpu_custom_call") == 2


SSD_CELLS = {"granite": (256, 1), "nemotron": (128, 4)}     # chunk, groups


def _ssd_value_and_grads(one_chip, chunk, groups, path):
    """``ssd_scan``'s value and six gradients at one sequence of 8192, 64 heads
    of 64 over a state of 128, lowered as a CPU lowers it (``plain``) or as the
    chip does (``kernels``: ``jax.default_backend`` steered here, in the test):
    the lowered text's kernel names and the compiled program."""
    from unittest import mock

    from edl_tpu.ops import ssd_scan

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    t, h, p, n = 8192, 64, 64, 128
    args = (sds((1, t, h, p)), sds((1, t, h), jnp.float32), sds((h,), jnp.float32),
            sds((1, t, groups, n)), sds((1, t, groups, n)), sds((h,), jnp.float32))

    def value_and_grads(w, *a):
        out, vjp = jax.vjp(lambda *a: ssd_scan(*a, chunk=chunk), *a)
        return (out, *vjp(w))

    with mock.patch.object(jax, "default_backend", lambda: "tpu" if path == "kernels" else "cpu"):
        lowered = jax.jit(lambda w, *a: value_and_grads(w, *a)).lower(sds((1, t, h, p)), *args)
    return sorted(_kernel_names(lowered.as_text())), lowered.compile()


def _square_tiles(compiled, chunk):
    """The instructions of a compiled program, outside its Pallas calls, whose
    result holds a float32 ``[.., L, L]`` array of a million elements or more:
    the decay matrices and the scores of every chunk (``B`` and ``C`` of a
    chunk are ``[L, N]`` and ``N`` may be ``L``: bfloat16, or one chunk's
    ``dC`` inside the carry's loop, 64 K elements)."""
    square = re.compile(r"f32\[([0-9,]*%d,%d)\]" % (chunk, chunk))
    found = []
    for line in compiled.as_text().splitlines():
        if " = " not in line or "tpu_custom_call" in line:
            continue
        shape = square.search(line.split(" = ", 1)[1].split(" ", 1)[0])
        if shape and math.prod(int(d) for d in shape.group(1).split(",")) >= 1 << 20:
            found.append(line.strip()[:120])
    return found


def _between_the_scans_kernels(compiled, elements=8192 * 64 * 64):
    """What a compiled ``ssd_scan`` (value and gradients) holds outside its
    Pallas calls that the carry inside them took away: ``(the count of its
    while loops, the float32 arrays of y's size that XLA itself writes)``.
    ``Y_diag + D x`` and ``dy`` were ``[n, B, H P, L]`` and ``[B, .., H P, T]``
    float32 between the kernels and the loops."""
    from edl_tpu.obs import profile as obs_profile

    text, wide = compiled.as_text(), []
    for line in text[text.index("ENTRY"):].splitlines():  # what a fusion holds inside is no array
        if " = " not in line or "tpu_custom_call" in line:
            continue
        rest = line.split(" = ", 1)[1]
        if re.search(r"[\])}] (get-tuple-element|bitcast|tuple)\(", rest):
            continue        # a kernel's own result (the states the chunks inherit), handed on
        shape = re.match(r"\(?f32\[([0-9,]*)\]", rest)
        if shape and math.prod(int(d) for d in shape.group(1).split(",") if d) >= elements:
            wide.append(line.strip()[:120])
    return obs_profile.HloProgram(text).census()["totals"]["loops"], wide


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_ssd_scan_compiles_for_v5e_at_granites_widths(one_chip, path):
    """The chunked scan at one sequence of 8192, 64 heads of 64 over a state of
    128, chunk 256. Plain XLA with jax's own backward (a CPU backend, a shape
    the kernels refuse), what the chip's compiler can refuse is the memory: the
    float32 decay matrices of one pass are 537 MB; value and gradients together
    must stay a small part of the 16 GB the step shares. As the chip lowers it
    the whole scan is ``ssd_forward`` and ``ssd_backward``, each once, the state
    carried inside them: no ``[256, 256]`` array exists outside them, **no
    ``while``** and no float32 array of ``y``'s size (the plain form has all
    three), and the temporaries are the states the chunks inherit, ``y`` and
    the gradients."""
    kernels, compiled = _ssd_value_and_grads(one_chip, *SSD_CELLS["granite"], path)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == len(kernels)
    loops, wide = _between_the_scans_kernels(compiled)
    if path == "kernels":
        assert kernels == ["ssd_backward", "ssd_forward"] and temp < 1.5e9
        assert _square_tiles(compiled, 256) == []
        assert (loops, wide) == (0, [])
    else:
        assert kernels == [] and temp < 4e9
        assert _square_tiles(compiled, 256)
        assert loops == 2 and wide


def test_causal_conv_kernels_compile_for_v5e_at_granites_widths(one_chip):
    """Both kernels of ``ops/causal_conv.py`` at one sequence of 8192 and the
    4352 channels of ``xBC``, read in place out of the in projection's 8512
    (time along the lanes, as the kernels take it): two custom calls and no
    float32 ``[8192, 4352]`` array (143 MB) among the temporaries, which are
    the partial sums and little else."""
    cc = importlib.import_module("edl_tpu.ops.causal_conv")

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    t, wide, c, offset = 8192, 8512, 4352, 4096
    blocks = cc._blocks(sds((1, t, wide)), sds((4, c), jnp.float32), offset)
    assert blocks == (64, 8192)

    def value_and_grads(xt, w, b, dy):
        y = cc._forward(xt, w, b, offset, blocks, False)
        return (y, *cc._backward(xt, w, b, dy, offset, blocks, False))

    lowered = jax.jit(value_and_grads).lower(
        sds((1, wide, t)), sds((c, 4), jnp.float32), sds((c, 1), jnp.float32),
        sds((1, c, t)),
    )
    assert _kernel_names(lowered.as_text()) == ["causal_conv_fwd", "causal_conv_bwd"]
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_gated_delta_rule_compiles_for_v5e_at_the_hybrids_widths(one_chip, path):
    """The chunked rule with its backward at one sequence of 8192, the 15
    heads of 96 / 192 the cell holds, chunks of 64. Plain XLA (what a CPU
    backend and a shape the kernels refuse take), what the chip's compiler can
    refuse is the memory: a chunk's float32 system is 31 MB a layer and the
    128 states a chunk inherits 142 MB; with the inverse's own backward, value
    and gradients together stay under 2 GB (all 30 heads: 2.7 GB) of the 5 GB
    the step has beside its parameters. As the chip lowers it
    (``jax.default_backend`` steered here, in the test) the chunk-local stage
    is the three kernels, each once, and plans less."""
    from unittest import mock

    from edl_tpu.ops import gated_delta_rule

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    t, h, d_k, d_v = 8192, 15, 96, 192
    args = (sds((1, t, h, d_k)), sds((1, t, h, d_k)), sds((1, t, h, d_v)),
            sds((1, t, h), jnp.float32), sds((1, t, h), jnp.float32))

    def value_and_grads(w, *a):
        out, vjp = jax.vjp(lambda *a: gated_delta_rule(*a, chunk=64), *a)
        return (out, *vjp(w))

    with mock.patch.object(jax, "default_backend", lambda: "tpu" if path == "kernels" else "cpu"):
        lowered = jax.jit(lambda *a: value_and_grads(*a)).lower(sds((1, t, h, d_v)), *args)
    kernels = sorted(_kernel_names(lowered.as_text()))
    compiled = lowered.compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == len(kernels)
    if path == "kernels":
        assert kernels == [
            "delta_carry", "delta_carry_back", "gdn_backward", "gdn_inverse", "gdn_operands"
        ] and temp < 1.2e9
    else:
        assert kernels == [] and 1.2e9 < temp < 2e9


@pytest.mark.parametrize("heads", [15, 30], ids=["held", "published"])
@pytest.mark.parametrize("kernel", ["gdn_inverse", "gdn_operands", "gdn_backward"])
def test_gdn_chunk_local_kernels_compile_for_v5e_at_the_cells_shape(one_chip, kernel, heads):
    """The three kernels of the scalar rule's chunk-local stage at one
    sequence of 8192 and heads of 96 / 192, the cell's 15 held (an odd count:
    seven rounds of the loop over pairs and a last head by itself) and the
    published 30: the steps along the lanes (blocks of ``[heads * d, 128]``,
    two chunks a grid step, a head's rows taken by a dynamic slice of 96 or 192
    sublanes inside the body's loop), ``T`` a head's two chunks a ``[64, 128]``
    row, ``[heads, 64, d]`` tiles for the carry's operands at the true widths:
    the slices, the products over a contracting dimension of 96 and the VMEM
    limit set from the shapes are what the chip's compiler can refuse."""
    G = importlib.import_module("edl_tpu.ops.gated_delta")

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    t, d_k, d_v, f32 = 8192, 96, 192, jnp.float32
    nc = t // 64
    keys, values, steps = sds((1, heads * d_k, t)), sds((1, heads * d_v, t)), sds((1, heads, t), f32)
    inverse = sds((1, nc // 2, heads, 64, 128), f32)
    tiles = sds((nc, 1, heads, 64, d_k))
    operands = (tiles, sds((nc, 1, heads, 64, d_v), f32), tiles, sds((nc, 1, heads, 1), f32),
                sds((1, nc, heads, 64, d_k)), sds((1, nc, heads, 64, 64)))
    call, args = {
        "gdn_inverse": (lambda *a: G._scalar_inverse_call(*a, False), (keys, steps, steps)),
        "gdn_operands": (lambda *a: G._scalar_operands_call(*a, False),
                         (keys, keys, values, steps, steps, inverse)),
        "gdn_backward": (lambda *a: G._scalar_backward_call(*a, False),
                         (keys, keys, values, steps, steps, inverse, *operands)),
    }[kernel]
    compiled = jax.jit(lambda *a: call(*a)).lower(*args).compile()
    assert kernel in compiled.as_text()
    out = jax.eval_shape(call, *args)
    if kernel == "gdn_inverse":
        assert (out.shape, out.dtype) == (inverse.shape, inverse.dtype)
    elif kernel == "gdn_operands":
        assert [(a.shape, a.dtype) for a in out] == [(a.shape, a.dtype) for a in operands]
    else:
        assert [(a.shape, a.dtype) for a in out] == [
            (a.shape, a.dtype) for a in (keys, keys, values, steps, steps)
        ]


@pytest.mark.parametrize("bank", ["up", "down"])
@pytest.mark.parametrize("cell", [c for c in EXPERT_CELL_SHAPES if c != "glm_4_7_flash"])  # LFM2's shapes
def test_megablox_compiles_for_v5e_on_the_tiles_of_a_cells_held_share(one_chip, cell, bank):
    """The three Megablox kernels at a cell's held-share shape on the tiles
    ``_fit`` gives (PR 57: rows of 256 and 128, the contracting dimension
    whole), under the 16 MiB of scoped VMEM a kernel has by default: Megablox
    asks for no more, and what Mosaic adds to the counted working set is not
    in the count. Three custom calls."""
    gm = importlib.import_module("edl_tpu.ops.grouped_matmul")

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    groups, rows, _, d, f = EXPERT_CELL_SHAPES[cell]
    k, n = (d, f) if bank == "up" else (f, d)
    tilings = gm._tilings(rows, groups, k, n, 2)
    rows += -rows % math.lcm(*(t[0] for t in tilings))  # as ``grouped_matmul`` pads them
    assert [t[1] for t in tilings[:2]] == [k, n]  # K whole, forward and for d lhs

    def value_and_grads(lhs, rhs, sizes, dy):
        out, vjp = jax.vjp(lambda a, b: gm._pallas(a, b, sizes, tilings, False), lhs, rhs)
        return (out, *vjp(dy))

    compiled = jax.jit(value_and_grads).lower(
        sds((rows, k)), sds((groups, k, n)), sds((groups,), jnp.int32), sds((rows, n))
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3


# a held share's buffer summed by token: (buffer rows m, the width its experts
# write, tokens) of the two cells PR 60 claims in, and Solar's ragged buffer
SEGMENT_SUM_SHAPES = {
    "smallthinker_21b_a3b": (24576, 2560, 16384),
    "nemotron_3_super_120b_a12b": (5632, 1024, 8192),
    "solar_open2_250b": (3280, 4096, 8192),  # no row tile divides 3280
}


@pytest.mark.parametrize("cell", list(SEGMENT_SUM_SHAPES))
def test_the_buffers_sum_by_token_compiles_for_v5e_at_a_cells_shape(one_chip, cell):
    """``rows_summed_by_segment`` on the TPU's path at a cell's buffer: one sort
    of the buffer's keys, one gather of its rows (into whole row tiles: no pad
    of the result), one ``tgmm`` over the tiles of 128 tokens ``_fit_segments`` tiles,
    under the default scoped VMEM, and no array of more rows than the buffer's
    at the layer's width but the float32 result."""
    gm = importlib.import_module("edl_tpu.ops.grouped_matmul")
    rows, width, tokens = SEGMENT_SUM_SHAPES[cell]
    groups = tokens // gm.SEGMENT_TILE
    tiling = gm._fit_segments(rows, groups, width, 2)
    assert tiling == (128, 128, width)  # the whole width: the one-hot is read once

    def summed(buffer, token):
        return gm.rows_summed_by_segment(buffer, token, tokens, implementation="pallas")

    compiled = jax.jit(summed).lower(
        jax.ShapeDtypeStruct((rows, width), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "tgmm" in text
    entry = text[text.index("ENTRY "):]
    assert len(re.findall(r" sort\(", entry)) == 1
    assert not re.search(r"= bf16\[\d+,\d+\]\S* pad\(", entry)  # the keys alone are padded
    padded = rows + -rows % tiling[0]
    wide = {
        (int(r), dtype) for dtype, r in re.findall(r"(bf16|f32)\[(\d+),%d\]" % width, entry)
    }
    assert wide == {(rows, "bf16"), (padded, "bf16"), (tokens, "f32")}
    out = jax.eval_shape(summed, jnp.zeros((rows, width), jnp.bfloat16), jnp.zeros((rows,), jnp.int32))
    assert (out.shape, out.dtype) == ((tokens, width), jnp.float32)


def test_gated_causal_conv_compiles_for_v5e_at_the_hybrids_widths(one_chip):
    """The gated short convolution, plain XLA with jax's own backward, at one
    sequence of 8192 and 2048 channels read out of the in projection's 6144: no
    kernel, so what the chip's compiler can refuse is the memory. Value and
    gradients together hold a few float32 ``[8192, 2048]`` arrays (67 MB each)
    and no copy of the padded input for every tap."""
    from edl_tpu.ops import gated_causal_conv

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def value_and_grads(x, w, dy):
        out, vjp = jax.vjp(gated_causal_conv, x, w)
        return (out, *vjp(dy))

    compiled = jax.jit(value_and_grads).lower(
        sds((1, 8192, 6144)), sds((3, 2048), jnp.float32), sds((1, 8192, 2048))
    ).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


def test_causal_conv_kernels_compile_for_v5e_at_the_hybrids_widths(one_chip):
    """The kernels' second shape: the 5760 channels of ``[q | k | v]`` (15
    heads) at the START of the in projection's 8670 (offset 0, so the blocks
    are what divides the channels alone), no bias (zeros stand in), one
    sequence of 8192."""
    cc = importlib.import_module("edl_tpu.ops.causal_conv")

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    t, wide, c, offset = 8192, 8670, 5760, 0
    blocks = cc._blocks(sds((1, t, wide)), sds((4, c), jnp.float32), offset)
    assert blocks == (64, 8192)

    def value_and_grads(xt, w, b, dy):
        y = cc._forward(xt, w, b, offset, blocks, False)
        return (y, *cc._backward(xt, w, b, dy, offset, blocks, False))

    lowered = jax.jit(value_and_grads).lower(
        sds((1, wide, t)), sds((c, 4), jnp.float32), sds((c, 1), jnp.float32),
        sds((1, c, t)),
    )
    assert _kernel_names(lowered.as_text()) == ["causal_conv_fwd", "causal_conv_bwd"]
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


def test_a_linear_attention_step_on_the_tpu_path_names_its_kernels_under_gdn_conv(one_chip):
    """A toy linear-attention hybrid of a shape the kernels take (256 channels
    of ``[q | k | v]`` at column 0 of 456, 128 steps), lowered as the chip
    lowers it: each linear layer holds the forward convolution kernel three
    times (the value, the block's recomputation, the mixer's own) and the
    backward kernel once under ``gdn_conv``, and the full layer's flash
    kernels stay outside every ``gdn_*`` scope."""
    from unittest import mock

    import numpy as np
    import optax

    from edl_tpu.models import ArchSpec, GatedDeltaSpec, TransformerLM
    from edl_tpu.models.gated_delta import GDN_SCOPES
    from edl_tpu.obs import profile as obs_profile
    from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

    layers = ("linear_attention", "attention")
    lm = TransformerLM(
        vocab_size=64, d_model=64, num_heads=4, num_kv_heads=4, num_layers=len(layers),
        d_ff=48, dtype=jnp.bfloat16, remat=True, qk_norm=True,
        arch=ArchSpec(
            layer_types=layers, rope=False, post_norms="only",
            gated_delta=GatedDeltaSpec(num_heads=4, key_dim=16, value_dim=32, chunk=32),
        ),
    )
    tokens = np.zeros((1, 128), np.int32)
    state = jax.eval_shape(
        lambda: create_state(lm, jax.random.PRNGKey(0), tokens, optax.adamw(1e-3))
    )
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    loss = lambda logits, y: cross_entropy_loss(  # noqa: E731
        logits.reshape(-1, logits.shape[-1]), y.reshape(-1)
    )
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = make_train_step(loss, numerics=False).lower(
            described(state), described((tokens, tokens))
        )
    assert {"causal_conv_fwd", "causal_conv_bwd"} <= set(_kernel_names(lowered.as_text()))
    text = lowered.compile().as_text()
    table = obs_profile.scopes_of_hlo(text, GDN_SCOPES)
    convs = sorted(name.split(".")[0] for name in table if name.startswith("causal_conv_"))
    assert convs.count("causal_conv_bwd") == 1 and convs.count("causal_conv_fwd") >= 2
    assert {table[name] for name in table if name.startswith("causal_conv_")} == {"gdn_conv"}
    assert set(table.values()) == set(GDN_SCOPES)
    custom = [ln for ln in text.splitlines() if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(custom) > len(convs)                 # the flash kernels are there too


def test_a_linear_attention_step_on_the_tpu_path_runs_the_rule_as_kernels_under_gdn_scan(one_chip):
    """A toy linear-attention hybrid at the kernels' chunk of 64 (three heads
    of 16 / 32: an odd count, widths under a lane tile; 128 steps, one grid
    step), under the cell's ``save_flash``, lowered as the chip lowers it: the
    rule notes ``path="kernel"``; each linear layer holds ``gdn_inverse`` once,
    forward (``gdn_inverse`` by name is what the recomputation reads),
    ``gdn_operands`` forward and once more where the backward reaches the rule
    under the mixer's checkpoint, and ``gdn_backward`` once; all of them under
    ``gdn_scan``; the carry is ``delta_carry`` once forward (its outputs saved
    by name: the recomputation holds no second) and ``delta_carry_back`` once,
    and no loop is left; and ``STEP_PARTS`` places every matmul of the
    compiled step."""
    from unittest import mock

    import numpy as np
    import optax

    from edl_tpu.models import ArchSpec, GatedDeltaSpec, TransformerLM
    from edl_tpu.models.gated_delta import GDN_SCOPES
    from edl_tpu.obs import profile as obs_profile
    from edl_tpu.obs import trace as obs_trace
    from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

    layers = ("linear_attention", "linear_attention", "attention")
    lm = TransformerLM(
        vocab_size=64, d_model=64, num_heads=4, num_kv_heads=4, num_layers=len(layers),
        d_ff=48, dtype=jnp.bfloat16, remat=True, remat_policy="save_flash", qk_norm=True,
        arch=ArchSpec(
            layer_types=layers, rope=False, post_norms="only",
            gated_delta=GatedDeltaSpec(num_heads=3, key_dim=16, value_dim=32, chunk=64),
        ),
    )
    tokens = np.zeros((1, 128), np.int32)
    state = jax.eval_shape(
        lambda: create_state(lm, jax.random.PRNGKey(0), tokens, optax.adamw(1e-3))
    )
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    loss = lambda logits, y: cross_entropy_loss(  # noqa: E731
        logits.reshape(-1, logits.shape[-1]), y.reshape(-1)
    )
    tracer = obs_trace.get_tracer()
    tracer.reset_notes()
    before = len([e for e in tracer.to_events() if e["name"] == "gdn_chunks"])
    walked = len([e for e in tracer.to_events() if e["name"] == "delta_carry"])
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = make_train_step(loss, numerics=False).lower(
            described(state), described((tokens, tokens))
        )
    noted = [e["args"] for e in tracer.to_events() if e["name"] == "gdn_chunks"][before:]
    assert noted and all(a["path"] == "kernel" and "why" not in a for a in noted)
    text = lowered.compile().as_text()
    census = obs_profile.HloProgram(text).census()
    assert census["totals"]["matmuls"] > 0 and census["totals"]["unplaced_matmuls"] == 0
    linear = layers.count("linear_attention")
    ours = ("gdn_", "delta_carry")
    calls = {key: n for key, n in census["kernels"].items() if key.startswith(ours)}
    assert calls == {
        "gdn_inverse/forward": linear, "gdn_operands/forward": linear,
        "gdn_operands/backward": linear, "gdn_backward/backward": linear,
        "delta_carry/forward": linear, "delta_carry_back/backward": linear,
    }
    assert census["totals"]["loops"] == 0
    walks = [e["args"] for e in tracer.to_events() if e["name"] == "delta_carry"][walked:]
    assert walks and all(a["path"] == "kernel" and a["operands"] == "tiles" for a in walks)
    scopes = obs_profile.scopes_of_hlo(text, GDN_SCOPES)
    rule = {name: scope for name, scope in scopes.items() if name.startswith(ours)}
    assert len(rule) == 6 * linear and set(rule.values()) == {"gdn_scan"}


def test_a_hybrid_step_on_the_tpu_path_runs_the_conv_kernels_under_ssm_conv(one_chip):
    """A toy hybrid of a shape the kernels take (256 channels of ``xBC`` at
    column 128, 128 steps), lowered as the chip lowers it (``jax.default_backend``
    steered to ``tpu`` here, for this compile only): each Mamba-2 layer holds the forward kernel
    twice (the value and its recomputation under remat) and the backward
    kernel once, and the compiled step names all of them under ``ssm_conv``,
    where the trace's ``ssm_conv_ms`` finds them."""
    from unittest import mock

    import numpy as np
    import optax

    from edl_tpu.models import ArchSpec, MambaSpec, TransformerLM
    from edl_tpu.models.mamba import SSM_SCOPES
    from edl_tpu.obs import profile as obs_profile
    from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

    layers = ("mamba", "mamba")
    lm = TransformerLM(
        vocab_size=64, d_model=48, num_heads=4, num_kv_heads=2, num_layers=len(layers),
        d_ff=40, dtype=jnp.bfloat16, remat=True, norm_eps=1e-5,
        arch=ArchSpec(
            layer_types=layers, head_dim=16, rope=False, tie_embeddings=True,
            mamba=MambaSpec(num_heads=8, head_dim=16, d_state=32, n_groups=2, chunk=8),
        ),
    )
    tokens = np.zeros((1, 128), np.int32)
    state = jax.eval_shape(
        lambda: create_state(lm, jax.random.PRNGKey(0), tokens, optax.adamw(1e-3))
    )
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    loss = lambda logits, y: cross_entropy_loss(  # noqa: E731
        logits.reshape(-1, logits.shape[-1]), y.reshape(-1)
    )
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = make_train_step(loss, numerics=False).lower(
            described(state), described((tokens, tokens))
        )
    # the lowered text holds each kernel once or twice (the wrappers are
    # jitted: one function a kernel and a place in the pass), the compiled
    # step one custom call a use
    assert set(_kernel_names(lowered.as_text())) == {"causal_conv_fwd", "causal_conv_bwd"}
    table = obs_profile.scopes_of_hlo(lowered.compile().as_text(), SSM_SCOPES)
    calls = sorted(name.split(".")[0] for name in table if name.startswith("causal_conv_"))
    assert calls == ["causal_conv_bwd"] * len(layers) + ["causal_conv_fwd"] * 2 * len(layers)
    assert {table[name] for name in table if name.startswith("causal_conv_")} == {"ssm_conv"}


def test_a_hybrid_step_on_the_tpu_path_runs_the_scan_kernels_under_ssm_scan(one_chip):
    """A toy hybrid of a shape the scan's kernels take (16 heads of 16 in two
    groups over a state of 256, two chunks of 128), lowered as the chip lowers
    it: each Mamba-2 layer holds ``ssd_forward`` twice (the value and its
    recomputation under remat) and ``ssd_backward`` once, the compiled step
    names all of them under ``ssm_scan`` (where ``ssm_scan_ms`` and
    ``step_kernel_calls`` find them), no matmul is left unplaced, the one
    ``ssm_chunks`` instant reads ``path="kernel"`` (so ``step_plain_fallbacks``
    counts none for it) and ``carry="kernel"``, the step holds no ``while``
    (``step_loops``), and no float32 ``[128, 128]`` array exists outside the
    kernels."""
    from unittest import mock

    import numpy as np
    import optax

    from edl_tpu.models import ArchSpec, MambaSpec, TransformerLM
    from edl_tpu.models.mamba import SSM_SCOPES
    from edl_tpu.obs import profile as obs_profile
    from edl_tpu.obs import trace as obs_trace
    from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

    layers = ("mamba", "mamba")
    lm = TransformerLM(
        vocab_size=64, d_model=48, num_heads=4, num_kv_heads=2, num_layers=len(layers),
        d_ff=40, dtype=jnp.bfloat16, remat=True, norm_eps=1e-5,
        arch=ArchSpec(
            layer_types=layers, head_dim=16, rope=False, tie_embeddings=True,
            mamba=MambaSpec(num_heads=16, head_dim=16, d_state=256, n_groups=2, chunk=128),
        ),
    )
    tokens = np.zeros((1, 256), np.int32)
    state = jax.eval_shape(
        lambda: create_state(lm, jax.random.PRNGKey(0), tokens, optax.adamw(1e-3))
    )
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    loss = lambda logits, y: cross_entropy_loss(  # noqa: E731
        logits.reshape(-1, logits.shape[-1]), y.reshape(-1)
    )
    tracer = obs_trace.get_tracer()
    tracer.reset_notes()
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = make_train_step(loss, numerics=False).lower(
            described(state), described((tokens, tokens))
        )
    noted = [args for name, args in tracer.notes() if name == "ssm_chunks"]
    assert [(dict(n)["path"], dict(n)["carry"]) for n in noted] == [("kernel", "kernel")]
    assert {"ssd_forward", "ssd_backward"} <= set(_kernel_names(lowered.as_text()))
    compiled = lowered.compile()
    text = compiled.as_text()
    table = obs_profile.scopes_of_hlo(text, SSM_SCOPES)
    calls = sorted(name.split(".")[0] for name in table if name.startswith("ssd_"))
    assert calls == ["ssd_backward"] * len(layers) + ["ssd_forward"] * 2 * len(layers)
    assert {table[name] for name in table if name.startswith("ssd_")} == {"ssm_scan"}
    census = obs_profile.HloProgram(text).census()
    assert census["totals"]["unplaced_matmuls"] == 0
    assert census["kernels"]["ssd_forward/forward"] == len(layers)
    assert census["kernels"]["ssd_forward/backward"] == len(layers)
    assert census["kernels"]["ssd_backward/backward"] == len(layers)
    assert census["totals"]["loops"] == 0       # the carry is the kernels' (three a layer before)
    assert _square_tiles(compiled, 128) == []


def _head_projection_gradients(text):
    """``{projection: (holds a matmul, holds the optimizer's instructions)}``
    for the fusions of a compiled step that write an array of an attention
    projection's kernel shape and are named after its ``dot_general``."""
    from edl_tpu.obs import profile as obs_profile

    program = obs_profile.HloProgram(text)
    matmuls, updates = program.matmuls, program.updates
    found = {}
    for line in text.splitlines():
        named = re.search(
            r'op_name="jit\(step\)/transpose\(jvp\(forward\)\)/[^"]*'
            r'/attn/(?:attn_gate/)?([qkvgo])/dot_general"', line,
        )
        called = obs_profile._HLO_CALLS.search(line)
        written = line.split(" fusion(")[0]
        if named and called and " fusion(" in line and re.search(
            r"\[2048,(?:32|4),128\]|\[32,128,2048\]", written
        ):
            body = called.group(1)
            found[named.group(1)] = (body in matmuls, body in updates)
    return found


@pytest.mark.parametrize("fenced", [True, False], ids=["fenced", "left_to_xla"])
def test_trinitys_head_projections_write_their_gradient_by_a_plain_matmul(
    one_chip, unfence, fenced
):
    """One attention layer at ``trinity_mini``'s widths (2048 wide, GQA 32:4
    x 128 with the gate, a window of 2048 over 8192 tokens; the SwiGLU and the
    vocabulary cut to toys), AdamW and the numerics bundle, compiled for the
    described v5e: the weight gradients of ``q``, ``k``, ``v``, ``g`` and ``o``
    are fusions that hold the matmul and no instruction of the ``optimizer``
    scope. Without the fence XLA puts the update into that matmul's fusion,
    the form the chip ran at 26-34% of peak (PERF.md section 6, PR 38)."""
    from unittest import mock

    import numpy as np
    import optax

    from edl_tpu.models import ArchSpec, TransformerLM
    from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

    if not fenced:
        unfence()
    lm = TransformerLM(
        vocab_size=512, d_model=2048, num_heads=32, num_kv_heads=4, num_layers=1,
        d_ff=256, dtype=jnp.bfloat16, remat=True, qk_norm="head",
        arch=ArchSpec(
            layer_types=("sliding_attention",), head_dim=128, rope="sliding",
            sliding_window=2048, attn_gate=True,
        ),
    )
    tokens = np.zeros((1, 8192), np.int32)
    state = jax.eval_shape(
        lambda: create_state(lm, jax.random.PRNGKey(0), tokens, optax.adamw(4e-4))
    )
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    loss = lambda logits, y: cross_entropy_loss(  # noqa: E731
        logits.reshape(-1, logits.shape[-1]), y.reshape(-1)
    )
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        compiled = make_train_step(loss, numerics=True).lower(
            described(state), described((tokens, tokens))
        ).compile()
    found = _head_projection_gradients(compiled.as_text())
    assert set(found) == {"q", "k", "v", "g", "o"}
    assert all(matmul for matmul, _ in found.values())
    if fenced:
        assert not any(update for _, update in found.values())
    else:  # the check can see the fused form: the wide ones at least
        assert found["q"][1] and found["g"][1]


def _entry_fusions(text):
    """``[(name, what it writes, its operands' names)]`` of ENTRY's fusions."""
    return [
        (name, written, set(re.findall(r"%([\w.\-]+)", operands.split(")")[0])))
        for name, written, operands in re.findall(
            r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) fusion\((.*)$",
            text[text.index("\nENTRY"):], re.M,
        )
    ]


def _reductions_reading(text, shape):
    """ENTRY's fusions that hold a ``reduce`` and read an array of ``shape``
    (a tuple's element too); a matmul's own is left out: a row's max may ride
    it as a second output."""
    from edl_tpu.obs import profile as obs_profile

    program = obs_profile.HloProgram(text)
    reduces = {
        home for name, home in program.home.items() if program.opcode[name] == "reduce"
    }
    held = {
        found.group(1) for found in re.finditer(
            r"^\s*%%?([\w.\-]+) = \(?[^(]*%s" % re.escape(shape),
            text[text.index("\nENTRY"):], re.M,
        )
    }
    return [
        name for name, _, operands in _entry_fusions(text)
        if operands & held and program.calls[name] in reduces
        and program.calls[name] not in program.matmuls
    ]


def test_the_loss_reads_trinitys_logits_in_one_reduction(one_chip):
    """The head's matmul, ``cross_entropy_loss`` and their gradient at a
    vocabulary slice of Trinity's (25,024 columns: 195.5 lane tiles), compiled
    for the described v5e: ONE fusion with a reduction reads the float32
    logits in the forward (``optax.softmax_cross_entropy`` + ``jnp.argmax``
    made three: the max with the argmax, the sum of exponentials, the pick),
    none in the backward, and the gradient's bfloat16 array is written once.
    Each such pass is 4.35 ms of Granite's step (PERF.md section 6, PR 68)."""
    from edl_tpu.models.transformer import _head_matmul
    from edl_tpu.train import cross_entropy_loss

    def loss(x, w, y):
        return cross_entropy_loss(_head_matmul(x, w), y)

    described = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip
    )
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)).lower(
        described((1024, 256), jnp.bfloat16), described((256, 25024), jnp.bfloat16),
        described((1024,), jnp.int32),
    ).compile().as_text()
    reads = _reductions_reading(text, "f32[1024,25024]")
    writes = [name for name, written, _ in _entry_fusions(text) if "bf16[1024,25024]" in written]
    assert len(reads) == 1, reads
    assert len(writes) == 1, writes


def test_grid_pipeline_kwargs_carry_dimension_semantics():
    """jax 0.9.0 has ``pltpu.CompilerParams(dimension_semantics=...)``: the
    flash2 family must never run without it (the old guard dropped it
    silently on an API mismatch)."""
    params = A._grid_pipeline_kwargs()["compiler_params"]
    assert tuple(str(s) for s in params.dimension_semantics) == (
        "parallel", "parallel", "arbitrary",
    )


# -- attention over a learned selection (ops/sparse_attention.py) -------------

SPARSE = (32, 4, 16384, 128, 16, 64, 2048)  # keye_vl_2_0_30b_a3b.steady: H, Hkv, T, D, J, Di, topk
SPARSE_KERNELS = {
    "index_fwd": "_index_fwd_kernel", "select": "_select_kernel",
    "sparse_fwd": "_sparse_fwd_kernel", "sparse_bwd": "_sparse_bwd_kernel",
    "target": "_target_kernel", "target_grad": "_target_kernel",
    "index_bwd": "_index_bwd_kernel",
}


@pytest.mark.parametrize("kernel", list(SPARSE_KERNELS))
def test_sparse_attention_kernels_compile_for_v5e_at_the_cells_shape(one_chip, kernel):
    """Each kernel of the selection's path at the step's own shape (one
    sequence of 16,384, GQA 32:4 x 128, an indexer of 16 heads x 64, the blocks
    ``_kernel_plan`` gives): the int8 mask's tiles, the select kernel's row
    block and key scratch (8 MB each under a limit set from the shapes), the
    fused backward's 8 MB dq accumulator, the target's q block of all 32 heads
    (4 MB a buffer) with the heads' loop inside the kernel and, with a
    gradient, ``dL_I/dI`` written as its causal tiles alone, the indexer
    backward's read of those tiles and its whole-sequence key gradient."""
    S = importlib.import_module("edl_tpu.ops.sparse_attention")
    h, h_kv, t, d, j, di, topk = SPARSE
    plan = S._kernel_plan(t, d, 2)
    assert plan == {"fwd": (1024, 1024), "bwd": (1024, 1024), "index": (512, 512),
                    "rows": 128, "chunk": 2048}
    scale = d ** -0.5

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    f32, q, kv = jnp.float32, sds((h, t, d)), sds((h_kv, t, d))
    rect, mask, rows = sds((t, t), f32), sds((t, t), jnp.int8), sds((h, t), f32)
    iq, ik, iw = sds((j, t, di)), sds((t, di)), sds((t, j), f32)
    tiles = (S._packed_rows(t, plan["index"][0]), plan["index"][1])
    fn, args = {
        "index_fwd": (lambda a, b, c: S._index_scores_kernels(a, b, c, *plan["index"], False),
                      (iq, ik, iw)),
        "select": (lambda s: S._select_kernels(s, topk, plan["rows"], plan["chunk"], False),
                   (rect,)),
        "sparse_fwd": (lambda q, k, v, m: S._sparse_forward(q, k, v, m, scale, *plan["fwd"], False),
                       (q, kv, kv, mask)),
        "sparse_bwd": (lambda q, k, v, g, l, dl, m: S._sparse_backward_kernels(
            q, k, v, g, l, dl, m, scale, *plan["bwd"], False), (q, kv, kv, q, rows, rows, mask)),
        "target": (lambda q, k, l, m, s, li: S._target_kernels(
            q, k, l, m, s, li, scale, *plan["index"], False)[0],
            (q, kv, rows, mask, rect, sds((t,), f32))),
        "target_grad": (lambda q, k, l, m, s, li: S._target_kernels(
            q, k, l, m, s, li, scale, *plan["index"], False, jnp.bfloat16),
            (q, kv, rows, mask, rect, sds((t,), f32))),
        "index_bwd": (lambda dd, a, b, c: S._index_backward_kernels(
            dd, a, b, c, *plan["index"], False), (sds(tiles), iq, ik, iw)),
    }[kernel]
    lowered = jax.jit(fn).lower(*args)
    assert _kernel_names(lowered.as_text()) == [SPARSE_KERNELS[kernel]]
    if kernel == "target_grad":   # dL_I/dI as its 528 causal tiles, what index_bwd takes
        assert lowered.out_info[1].shape == tiles and tiles[0] * tiles[1] * 2 == 276_824_064
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    # nothing but a layout copy of the mask beside the kernel's own operands
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def test_the_select_kernel_gives_its_three_rows_inside_the_vmem_limit_it_had(one_chip):
    """At the cell's shape (``[16384, 16384]`` scores, 128 rows a block, 2048
    keys a pass) the select kernel, which the ``select`` case above compiles,
    has its third output, the selection's log-sum-exp, beside the two
    thresholds, and still asks for the 32 MB its row block, its key scratch and
    their buffers need: the sum reads the block already held and brings no
    buffer."""
    S = importlib.import_module("edl_tpu.ops.sparse_attention")
    t, topk = SPARSE[2], SPARSE[6]
    lowered = jax.jit(lambda s: S._select_kernels(s, topk, 128, 2048, False)).lower(
        jax.ShapeDtypeStruct((t, t), jnp.float32, sharding=one_chip)
    )
    assert _kernel_names(lowered.as_text()) == ["_select_kernel"]
    assert [(o.shape, str(o.dtype)) for o in lowered.out_info] == [
        ((t,), "int32"), ((t,), "int32"), ((t,), "float32"),
    ]
    (limit,) = re.findall(r'memory_space\\22: ?1, \\22offset\\22: 0, \\22size\\22: (\d+)', lowered.as_text())
    assert int(limit) == 4 * 128 * t * 4 == 32 << 20


def _plain_readers(entry, shape):
    """Instructions of a compiled entry computation, kernels aside, that take
    a value of ``shape`` (``"f32[16384,16384]"``): ``(name, opcode)`` each."""
    held, readers = set(), []
    for line in entry.splitlines():
        if " = " not in line:
            continue
        name, rest = line.strip().removeprefix("ROOT ").split(" = ", 1)
        op = re.match(r"(\(.*?\)|\S+) ([\w-]+)\(", rest)
        if not op:
            continue
        operands = set(re.findall(r"%[\w.-]+", rest[op.end():].split(")")[0]))
        if operands & held and op.group(2) not in ("custom-call", "get-tuple-element", "bitcast"):
            readers.append((name, op.group(2)))
        if rest.startswith(shape):
            held.add(name)
    return readers


def test_a_sparse_layers_step_reads_the_scores_outside_its_kernels_only_to_make_the_mask(one_chip):
    """One sparse-attention layer's value and gradient under ``save_flash`` at
    the cell's shape, compiled for the described v5e: seven kernels (the select
    kernel once and the target kernel once: its ``dI`` is made in the forward
    and kept by name), and of XLA's own operations only two take the 1.07 GB of
    scores, the mask's elementwise pass forward and again in the
    recomputation: no row sum for the indexer's loss, no second mask for the
    masked backward's transpose, and no copy of the scores into the layout that
    transpose would like (the backward holds the mask row-major: without that
    XLA copies the float32 scores column-major twice a layer)."""
    from unittest import mock

    from edl_tpu.models.transformer import _remat_policy

    S = importlib.import_module("edl_tpu.ops.sparse_attention")
    h, h_kv, t, d, j, di, topk = SPARSE

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def layer(*a):
        out, kl, _, _ = S.sparse_attention(*a, topk)
        return jnp.sum(out.astype(jnp.float32)) + kl

    step = jax.jit(jax.value_and_grad(
        jax.checkpoint(layer, policy=_remat_policy("save_flash")), argnums=tuple(range(6))
    ))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = step.lower(
            sds((1, h, t, d)), sds((1, h_kv, t, d)), sds((1, h_kv, t, d)),
            sds((1, j, t, di)), sds((1, t, di)), sds((1, t, j), jnp.float32),
        )
    kernels = _kernel_names(lowered.as_text())
    assert len(kernels) == 7 and kernels.count("_select_kernel") == 1
    assert kernels.count("_target_kernel") == 1
    text = lowered.compile().as_text()
    entry = text[text.index("\nENTRY "):]
    readers = _plain_readers(entry, "f32[%d,%d]" % (t, t))
    masks = {
        line.strip().split(" = ")[0] for line in entry.splitlines()
        if " fusion(" in line and "s8[%d,%d]" % (t, t) in line.split(" fusion(")[0]
    }
    assert len(masks) == 2 and {name for name, _ in readers} == masks


def test_the_target_takes_every_head_in_a_grid_step_under_a_limit_from_the_shapes(one_chip):
    """``sparse_attention`` traced at the cell's shape (nothing compiled)
    leaves the target's schedule on the ``dsa_shape`` instant, and the lowered
    call asks for the VMEM its blocks need, well under half a core's."""
    from edl_tpu.obs import trace as obs_trace

    S = importlib.import_module("edl_tpu.ops.sparse_attention")
    h, h_kv, t, d, j, di, topk = SPARSE

    def sds(dims, dtype=jnp.bfloat16, **kw):
        return jax.ShapeDtypeStruct(dims, dtype, **kw)

    obs_trace.get_tracer().reset_notes()
    ring = obs_trace.get_tracer()
    before = len([e for e in ring.to_events() if e["name"] == "dsa_shape"])
    jax.eval_shape(
        lambda *a: S.sparse_attention(*a, topk, interpret=True),
        sds((1, h, t, d)), sds((1, h_kv, t, d)), sds((1, h_kv, t, d)),
        sds((1, j, t, di)), sds((1, t, di)), sds((1, t, j), jnp.float32),
    )
    (event,) = [e["args"] for e in ring.to_events() if e["name"] == "dsa_shape"][before:]
    assert event["target_blocks"] == [512, 512] and event["target_heads_step"] == h
    assert event["target_strip"] == [256, 512]
    lowered = jax.jit(
        lambda q, k, l, m, s, li: S._target_kernels(
            q, k, l, m, s, li, d ** -0.5, 512, 512, False, jnp.bfloat16)
    ).lower(
        sds((h, t, d), sharding=one_chip), sds((h_kv, t, d), sharding=one_chip),
        sds((h, t), jnp.float32, sharding=one_chip), sds((t, t), jnp.int8, sharding=one_chip),
        sds((t, t), jnp.float32, sharding=one_chip), sds((t,), jnp.float32, sharding=one_chip),
    )
    (limit,) = re.findall(r'memory_space\\22: ?1, \\22offset\\22: 0, \\22size\\22: (\d+)', lowered.as_text())
    assert int(limit) == S._target_vmem(h, h_kv, d, 2, 512, 512, 256, 2)
    assert 16 << 20 < int(limit) < A._vmem_capacity() // 2   # q alone is two buffers of 4 MB


def _sparse_cell():
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "keye_vl_2_0_30b_a3b.json")) as f:
        return root, json.load(f)


def test_the_sparse_cells_depth_is_the_one_its_plan_chose():
    """The rule, on the numbers the file records: the deepest of 6, 5, 4 layers
    whose compiled step leaves at least 1 GB of the chip's 15.75 (the slow case
    below compiles them again)."""
    _, config = _sparse_cell()
    plan = config["plan"]
    fits = [
        tried["num_hidden_layers"] for tried in plan["tried"]
        if plan["chip_gb"] - tried["total_gb"] >= plan["least_left_gb"]
    ]
    assert plan["chosen"] == max(fits) == config["num_hidden_layers"]
    assert config["published"]["num_hidden_layers"] == 48
    assert config["train"]["seq_len"] == 16384
    for tried in plan["tried"]:
        assert tried["left_gb"] == pytest.approx(plan["chip_gb"] - tried["total_gb"], abs=2e-3)
        if tried["num_hidden_layers"] > plan["chosen"]:
            assert tried["left_gb"] < plan["least_left_gb"]


@pytest.mark.slow
@pytest.mark.parametrize("depth", [6, 5])
def test_the_sparse_cells_whole_step_compiles_for_v5e_and_its_plan_is_as_recorded(depth):
    """``benchmark/tools/compile_for_v5e.py`` on the cell at the depth the
    issue asked for first and at the one the rule chose (3 to 4 minutes each):
    the step compiles with its 29 custom calls a layer, and the plan's total is
    what the configuration's file records, to 0.1 GB (stale since before PR
    66: the file's totals are PR 41's, ROADMAP S11)."""
    import json
    import subprocess
    import sys

    root, config = _sparse_cell()
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tools", "compile_for_v5e.py"),
         "keye_vl_2_0_30b_a3b.steady", "num_hidden_layers=%d" % depth],
        capture_output=True, text=True, timeout=1500, cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    recorded = next(
        t for t in config["plan"]["tried"] if t["num_hidden_layers"] == depth
    )
    assert doc["parameters"] == recorded["parameters"]
    assert doc["total_gb"] == pytest.approx(recorded["total_gb"], abs=0.1)
    assert doc["tpu_custom_calls"] == 29 * depth


# -- ling_3_0_flash_vl.steady: two-width flash2, the per-channel delta rule --

MLA = (1, 16, 8192, 192, 128)  # the latent layer as it trains: b, h, t, d_qk, d_v


@pytest.mark.parametrize("heads", [16, 32], ids=["held", "published"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash2_compiles_for_v5e_at_keys_of_192_and_values_of_128(one_chip, direction, heads):
    """The grid-pipelined forward and the fused backward with q and k of 192
    and v, dO, o and dv of 128 (multi-head latent attention as it trains), at
    the blocks ``_auto`` gives the call: the kernels themselves lower, and the
    chip's compiler takes their blocks and the fused backward's VMEM."""
    b, _, t, d_qk, d_v = MLA
    scale = d_qk ** -0.5

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    q, v = sds((b, heads, t, d_qk)), sds((b, heads, t, d_v))
    if direction == "fwd":
        bq, bk = A._flash2_blocks("fwd", t, t, None)
        fn = lambda q, k, v: A._flash2_forward(q, k, v, True, scale, bq, bk, False)
        args, want = (q, q, v), ["_flash2_kernel"]
    else:
        bq, bk = A._flash2_blocks("bwd", t, t, None)
        fn = lambda q, k, v, g, lse, delta: A._flash2_backward_kernels(
            q, k, v, g, lse, delta, True, scale, bq, bk, False
        )
        row = sds((b * heads, t), jnp.float32)
        args, want = (q, q, v, v, row, row), ["_flash2_bwd_kernel"]
    lowered = jax.jit(fn).lower(*args)
    assert _kernel_names(lowered.as_text()) == want
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    shapes = [o.shape for o in jax.tree.leaves(jax.eval_shape(fn, *args))]
    if direction == "fwd":
        assert shapes[0] == (b, heads, t, d_v)
    else:
        assert shapes == [(b, heads, t, d_qk), (b, heads, t, d_qk), (b, heads, t, d_v)]


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_kda_rule_compiles_for_v5e_at_the_cells_widths(one_chip, path):
    """The per-channel rule with its backward at one sequence of 8192, the 16
    heads of 128 / 128 the cell holds, chunks of 64 in sub-blocks of 16. Plain
    XLA (what a CPU backend and a shape the kernels refuse take), what the
    chip's compiler can refuse is the memory: the decayed columns are four
    float32 copies of the keys (268 MB at 16 heads) and their cotangent as many
    again; value and gradients together plan 2.23 GB, under 3 of the 6 the step
    has beside its state. As the chip lowers it (``jax.default_backend`` steered
    here, in the test) the chunk-local stage is the three kernels, each once,
    and no copy of a chunk's decayed keys reaches HBM: 0.71 GB."""
    from unittest import mock

    from edl_tpu.ops import kda_rule

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    t, h, d = 8192, 16, 128
    args = (sds((1, t, h, d)), sds((1, t, h, d)), sds((1, t, h, d)),
            sds((1, t, h, d), jnp.float32), sds((1, t, h), jnp.float32))

    def value_and_grads(w, *a):
        out, vjp = jax.vjp(lambda *a: kda_rule(*a, chunk=64), *a)
        return (out, *vjp(w))

    with mock.patch.object(jax, "default_backend", lambda: "tpu" if path == "kernels" else "cpu"):
        lowered = jax.jit(value_and_grads).lower(sds((1, t, h, d)), *args)
    kernels = sorted(_kernel_names(lowered.as_text()))
    compiled = lowered.compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == len(kernels)
    if path == "kernels":
        assert kernels == [
            "delta_carry", "delta_carry_back", "kda_backward", "kda_inverse", "kda_operands"
        ] and temp < 1e9
    else:
        assert kernels == [] and 1e9 < temp < 3e9


@pytest.mark.parametrize("heads", [16, 32, 8], ids=["held", "published", "solar"])
@pytest.mark.parametrize("kernel", ["kda_inverse", "kda_operands", "kda_backward"])
def test_kda_chunk_local_kernels_compile_for_v5e_at_the_cells_shape(one_chip, kernel, heads):
    """The three kernels of the rule's chunk-local stage at one sequence of
    8192 and heads of 128 / 128 (any ``g <= 0``: a level's rolls down the
    sublanes, six masked products a tile), at Ling's 16 held and 32 published
    heads and at Solar's 8 held, a chunk of every head a grid step (blocks of
    ``[64, heads * 128]`` rows of the mixer's own arrays, a head's lanes taken
    by a dynamic slice inside the body's loop, ``T`` a pair of heads a
    ``[64, 128]`` row, ``[heads, 64, 64]`` tiles of the scores): the tiling, the slices and the VMEM limit set from
    the shapes are what the chip's compiler can refuse."""
    G = importlib.import_module("edl_tpu.ops.gated_delta")

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    t, d, f32 = 8192, 128, jnp.float32
    nc = t // 64
    rows, decays, beta = sds((1, t, heads * d)), sds((1, t, heads * d), f32), sds((1, t, heads), f32)
    inverse = sds((1, nc, heads // 2, 64, 128), f32)
    operands = (sds((nc, 1, heads, 64, d)), sds((nc, 1, heads, 64, d), f32),
                sds((nc, 1, 64, heads * d)), sds((nc, 1, heads, d), f32), rows,
                sds((1, nc, heads, 64, 64)))
    call, args = {
        "kda_inverse": (lambda *a: G._inverse_call(*a, False), (rows, decays, beta)),
        "kda_operands": (lambda *a: G._operands_call(*a, False),
                         (rows, rows, rows, decays, beta, inverse)),
        "kda_backward": (lambda *a: G._backward_call(*a, False),
                         (rows, rows, rows, decays, beta, inverse, *operands)),
    }[kernel]
    compiled = jax.jit(call).lower(*args).compile()
    assert kernel in compiled.as_text()
    out = jax.eval_shape(call, *args)
    if kernel == "kda_operands":
        assert [(a.shape, a.dtype) for a in out] == [(a.shape, a.dtype) for a in operands]
    elif kernel == "kda_backward":
        assert [(a.shape, a.dtype) for a in out] == [
            (a.shape, a.dtype) for a in (rows, rows, rows, decays, beta)
        ]


CARRY_SHAPES = {"ling": (16, 128, 128, False), "ling_published": (32, 128, 128, False),
                "solar": (8, 128, 128, False),
                "olmo_hybrid": (15, 96, 192, True)}


@pytest.mark.parametrize("cell", list(CARRY_SHAPES))
@pytest.mark.parametrize("kernel", ["delta_carry", "delta_carry_back"])
def test_the_delta_rules_walk_compiles_for_v5e_at_the_cells_shape(one_chip, kernel, cell):
    """The carry and the output stage as one walk over 128 chunks of 64, the
    state of every head in VMEM (Ling 16 heads of 128 / 128: 1 MB; Solar 8;
    OLMo-hybrid 15 of 96 / 192, a lane tile and a half of state a row): the
    operands where the chunk-local kernels write them (a row's heads side by
    side; OLMo-hybrid's a tile a head), the chunks ``"arbitrary"``, the scratch
    and a VMEM limit set from the blocks are what the chip's compiler can
    refuse."""
    G = importlib.import_module("edl_tpu.ops.gated_delta")

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    h, d_k, d_v, tiles = CARRY_SHAPES[cell]
    nc, size, f32 = 128, 64, jnp.float32
    by_head = lambda d, dtype=jnp.bfloat16: sds((nc, 1, h, size, d), dtype)  # noqa: E731
    if tiles:
        k_out, q_in, o = by_head(d_k), sds((1, nc, h, size, d_k)), sds((1, nc, h, size, d_v))
    else:
        k_out, q_in, o = (sds((nc, 1, size, h * d_k)), sds((1, nc * size, h * d_k)),
                          sds((1, nc * size, h * d_v)))
    w, u, new, whole = by_head(d_k), by_head(d_v, f32), by_head(d_v), sds((nc, 1, h, d_k), f32)
    scores, state = sds((1, nc, h, size, size)), sds((1, h, d_v, d_k), f32)
    entering = sds((nc, 1, h, d_v, d_k), f32)
    call, args, want = {
        "delta_carry": (lambda *a: G._carry_call(*a, False),
                        (w, u, k_out, whole, q_in, scores, state), (o, new, entering, state)),
        "delta_carry_back": (lambda *a: G._carry_back_call(*a, False),
                             (w, k_out, whole, q_in, scores, entering, new, o, state),
                             (w, u, k_out, whole, q_in, scores, state)),
    }[kernel]
    assert kernel in jax.jit(call).lower(*args).compile().as_text()
    out = jax.eval_shape(call, *args)
    assert [(a.shape, a.dtype) for a in out] == [(a.shape, a.dtype) for a in want]


def test_a_chunk_call_that_is_no_walk_says_of_its_grid_what_it_said_before(one_chip):
    """The delta rules' chunk-local calls go through ``_chunk_call`` without
    ``walk``: both grid dimensions parallel, as before the walk was there,
    whose own chunks are ``"arbitrary"``, as are ``ops/ssd.py``'s two since
    they carry the state themselves (PR 64) (the semantics lie in a call's
    Mosaic bytecode, so they are read where they are handed over;
    ``benchmark/tools/lowered_step.py`` hashes whole steps against the
    parent's: PERF.md, PR 62)."""
    from unittest import mock

    from jax.experimental.pallas import tpu as pltpu

    S = importlib.import_module("edl_tpu.ops.ssd")
    G = importlib.import_module("edl_tpu.ops.gated_delta")

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    # shapes no other test traces: the calls are jitted, a shape lowers once
    f32, h, p, n, t = jnp.float32, 8, 64, 128, 768
    xbc, dt, head = sds((1, h * p + 2 * n, t)), sds((1, h, t), f32), sds((h, 1), f32)
    rows, decays, beta = sds((1, t, 256)), sds((1, t, 256), f32), sds((1, t, 2), f32)
    by_head = lambda d, dtype=jnp.bfloat16: sds((t // 64, 1, 2, 64, d), dtype)  # noqa: E731
    calls = {
        "ssd_forward": (lambda *a: S._forward_call(*a, 256, p, n, False),
                        (xbc, dt, head, head, sds((1, h, p, n), f32))),
        "kda_inverse": (lambda *a: G._inverse_call(*a, False), (rows, decays, beta)),
        "gdn_inverse": (lambda *a: G._scalar_inverse_call(*a, False),
                        (sds((1, 256, t)), sds((1, 2, t), f32), sds((1, 2, t), f32))),
        "delta_carry": (lambda *a: G._carry_call(*a, False), (
            by_head(128), by_head(128, f32), sds((t // 64, 1, 64, 256)),
            sds((t // 64, 1, 2, 128), f32), rows, sds((1, t // 64, 2, 64, 64)),
            sds((1, 2, 128, 128), f32),
        )),
    }
    said = {}
    for name, (call, args) in calls.items():
        with mock.patch.object(pltpu, "CompilerParams", wraps=pltpu.CompilerParams) as params:
            jax.jit(call).lower(*args)
        (made,) = params.call_args_list
        said[name] = made.kwargs["dimension_semantics"]
    walks = {said.pop("delta_carry"), said.pop("ssd_forward")}
    assert walks == {("parallel", "arbitrary")}
    assert set(said.values()) == {("parallel", "parallel")}


def _kda_cell():
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "ling_3_0_flash_vl.json")) as f:
        return root, json.load(f)


def test_the_kda_cells_cut_is_the_one_its_plan_chose():
    """The rule, on the numbers the file records: the deeper of 7 and 6 layers
    at all 32 heads whose compiled step leaves at least 1 GB of the chip's
    15.75; neither does, so depth 6 as one of two chips' share of the heads
    (16 of 32), whose plan has to leave that much (the slow case below compiles
    them again). The tool's total counts more than the chip reserves, so the
    file also records what the chip itself left at depth 6 (``on_chip``): with
    all the heads the step runs, but with less than 1 GB left and not beside a
    ballast of 1 GiB; with 16 it leaves the room the rule asks for."""
    _, config = _kda_cell()
    plan = config["plan"]
    left = lambda tried: plan["chip_gb"] - tried["total_gb"]  # noqa: E731
    whole = [t for t in plan["tried"] if t["num_attention_heads"] == 32]
    assert sorted(t["num_hidden_layers"] for t in whole) == [6, 7]
    assert all(left(t) < plan["least_left_gb"] for t in whole)
    chosen = next(t for t in plan["tried"] if t["num_attention_heads"] == 16)
    assert left(chosen) >= plan["least_left_gb"]
    whole_on_chip = next(t for t in whole if t["num_hidden_layers"] == 6)["on_chip"]
    assert whole_on_chip["ran"] and whole_on_chip["left_gb"] < plan["least_left_gb"]
    assert "RESOURCE_EXHAUSTED" in whole_on_chip["beside_a_ballast_of_1_gib"]
    for on_chip in (whole_on_chip, chosen["on_chip"]):      # the chip's own arithmetic
        reserved = on_chip["state_gb"] + on_chip["program_reserve_gib"] * 2 ** 30 / 1e9
        assert on_chip["left_gb"] == pytest.approx(on_chip["bytes_limit"] / 1e9 - reserved, abs=0.01)
    assert chosen["on_chip"]["left_gb"] >= plan["least_left_gb"]
    assert plan["chosen"] == {"num_hidden_layers": 6, "num_attention_heads": 16}
    assert (config["num_hidden_layers"], config["num_attention_heads"]) == (6, 16)
    assert config["published"]["num_attention_heads"] == 32
    assert config["published"]["num_hidden_layers"] == 42
    for tried in plan["tried"]:
        assert tried["left_gb"] == pytest.approx(left(tried), abs=2e-3)
    # a whole period at the published five to one, as layers 1 to 6 lie
    kinds = config["layer_types"]
    assert kinds == config["published"]["layer_types"][1:7]
    assert kinds.count("full_attention") == 1 and len(kinds) == config["layer_group_size"]


@pytest.mark.slow
@pytest.mark.parametrize("heads", [32, 16])
def test_the_kda_cells_whole_step_compiles_for_v5e_and_its_plan_is_as_recorded(heads):
    """``benchmark/tools/compile_for_v5e.py`` on the cell at depth 6 with all
    the heads and with the share the rule chose (3 to 5 minutes each). At the
    16 heads the cell runs the step compiles and the plan's total is 13.65 GB
    to 0.1: the configuration's file keeps PR 45's 12.94 (a ``benchmark`` PR's
    to rewrite, ROADMAP S11), and since the mixers' in projections are kept by
    name (PR 54: 201 MB a layer, five layers) the tool reads 13.653. With all
    32 (which the rule refused: the file keeps PR 45's 17.52, and PR 46's
    kernels brought the plan to 16.53) the kept projections are 403 MB a layer
    and the compiler gives up: 15.85 G of the chip's 15.75."""
    import json
    import subprocess
    import sys

    root, config = _kda_cell()
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tools", "compile_for_v5e.py"),
         "ling_3_0_flash_vl.steady", "num_attention_heads=%d" % heads,
         "num_key_value_heads=%d" % heads],
        capture_output=True, text=True, timeout=1500, cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    recorded = next(
        t for t in config["plan"]["tried"]
        if (t["num_hidden_layers"], t["num_attention_heads"]) == (6, heads)
    )
    if heads == 32:
        assert recorded["left_gb"] < config["plan"]["least_left_gb"]
        assert out.returncode != 0 and "RESOURCE_EXHAUSTED" in out.stderr
        assert re.search(r"Used 1[56]\.\d+G of 15\.75G hbm", out.stderr)
        return
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    assert doc["parameters"] == recorded["parameters"]
    assert recorded["total_gb"] == pytest.approx(12.94, abs=0.01)
    assert doc["total_gb"] == pytest.approx(13.65, abs=0.1)


# -- nemotron_3_super_120b_a12b.steady: experts in a latent, Mamba-2 in groups --

@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_ssd_scan_compiles_for_v5e_in_four_groups_at_a_chunk_of_128(one_chip, path):
    """The chunked scan at one sequence of 8192, the 64 heads of 64 the cell
    holds in 4 groups of 16 over a state of 128, chunk 128 (``r = h // g`` =
    16: the first cell with more than one group). Plain XLA, what the chip's
    compiler can refuse is the memory: at half Granite's chunk the float32
    decay matrices of one pass are 268 MB. As the chip lowers it, the two
    kernels, each once, and outside them no ``[128, 128]`` array, no ``while``
    and no float32 array of ``y``'s size."""
    kernels, compiled = _ssd_value_and_grads(one_chip, *SSD_CELLS["nemotron"], path)
    temp = compiled.memory_analysis().temp_size_in_bytes
    loops, wide = _between_the_scans_kernels(compiled)
    if path == "kernels":
        assert kernels == ["ssd_backward", "ssd_forward"] and temp < 1.5e9
        assert _square_tiles(compiled, 128) == []
        assert (loops, wide) == (0, [])
    else:
        assert kernels == [] and temp < 3e9
        assert loops == 2


@pytest.mark.parametrize("kernel", ["ssd_forward", "ssd_backward"])
@pytest.mark.parametrize("cell", list(SSD_CELLS))
def test_ssd_chunk_local_kernels_compile_for_v5e_at_the_cells_shape(one_chip, cell, kernel):
    """The scan's two kernels at one sequence of 8192 and 64 heads of 64 over a
    state of 128, a chunk of every head a grid step, the chunks in order (last
    to first in the backward) with every head's float32 state in a VMEM scratch
    from one to the next, time along the lanes (blocks of ``[64 * 64 + 2 *
    groups * 128, chunk]`` columns of the transposed ``xBC``, a head's 64
    sublanes and a group's 128 taken by dynamic slices inside the body's
    loops, a head's decay a ``[1, 128]`` row of a scratch a block of lanes
    apart, the own states of a slab of heads and what they read of the states
    they inherit by one product each, ``y`` and ``dy`` bfloat16 blocks of ``[B,
    H P, T]``): the tiling, the slices, the transposed products and the VMEM
    limit set from the shapes are what the chip's compiler can refuse."""
    S = importlib.import_module("edl_tpu.ops.ssd")

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    chunk, groups = SSD_CELLS[cell]
    t, h, p, n, f32 = 8192, 64, 64, 128, jnp.float32
    nc = t // chunk
    state = sds((1, h, p, n), f32)
    inputs = (sds((1, h * p + 2 * groups * n, t)), sds((1, h, t), f32), sds((h, 1), f32),
              sds((h, 1), f32))
    y, entering, sums = sds((1, h * p, t)), sds((nc, 1, h, p, n), f32), sds((nc, 1, h, 1), f32)
    call, args, want = {
        "ssd_forward": (lambda *a: S._forward_call(*a, chunk, p, n, False), (*inputs, state),
                        (y, entering, state)),
        "ssd_backward": (lambda *a: S._backward_call(*a, chunk, p, n, False),
                         (*inputs, y, entering, state),
                         (inputs[0], inputs[1], sums, sums, state)),
    }[kernel]
    compiled = jax.jit(call).lower(*args).compile()
    assert kernel in compiled.as_text()
    out = jax.eval_shape(call, *args)
    assert [(a.shape, a.dtype) for a in out] == [(a.shape, a.dtype) for a in want]


def _large_under(text, scope, elements):
    """``(opcode or the fusion's name, dtype)`` of the compiled program's entry
    instructions named under ``scope`` that write ``elements`` values or more
    themselves: kernels, loops, a loop's empty output buffers and the
    bookkeeping around them (``get-tuple-element``, ``bitcast``, ``tuple``)
    left out."""
    found = []
    for line in text[text.index("ENTRY"):].splitlines():
        named = re.search(r'op_name="([^"]*)"', line)
        if " = " not in line or not named or "/%s/" % scope not in named.group(1) + "/":
            continue
        name, rest = line.strip().split(" = ", 1)
        written = re.match(r"\(?(\w+)\[([0-9,]*)\]", rest)
        opcode = re.search(r"[\])}] ([a-z\-]+)\(", rest)
        if not written or not opcode or opcode.group(1) in (
                "custom-call", "while", "broadcast", "get-tuple-element", "bitcast", "tuple"):
            continue
        if math.prod(int(d) for d in written.group(2).split(",") if d) >= elements:
            kind = opcode.group(1)
            # a fusion by its name, which XLA makes from what it holds
            found.append((name.lstrip("%").split(".")[0] if kind == "fusion" else kind,
                          written.group(1)))
    return found


@pytest.mark.parametrize("cell", list(SSD_CELLS))
def test_a_mixer_at_the_cells_widths_passes_xbc_whole_and_moves_y_not_at_all(
        one_chip, cell):
    """One ``Mamba2Mixer`` at a cell's widths (8192 steps, 64 heads of 64 over a
    state of 128), value and gradients under ``jax.checkpoint`` as a block's
    recomputation runs it, lowered as the chip lowers it. What XLA does around
    the scan's kernels is worth as much as the kernels (PERF.md section 6, PR
    50), and an upgrade of XLA could take it back silently: **between the
    convolution and the scan** the mixer's three slices of ``xBC`` and
    ``ssd_scan``'s laying them side by side cancel, so under ``ssm_scan`` no
    array of ``x``'s size is sliced, fused or concatenated, and the backward
    kernel writes one gradient of ``xBC``'s whole shape; **between the scan and
    the gate** nothing moves at all since PR 64: ``ssd_forward`` writes ``y``
    in bfloat16 with time along the lanes, which is how the gate reads it, and
    the gate's backward writes ``dy`` so for ``ssd_backward`` (before, one
    bfloat16 copy a forward pass behind an ``optimization_barrier`` and a
    float32 ``dy`` for the reverse loop), and the gate makes no copy of its
    own."""
    from unittest import mock

    from edl_tpu.models import Mamba2Mixer, MambaSpec

    (chunk, groups), d_model = SSD_CELLS[cell], {"granite": 2048, "nemotron": 4096}[cell]
    t, h, p, n = 8192, 64, 64, 128
    mixer = Mamba2Mixer(
        MambaSpec(num_heads=h, head_dim=p, d_state=n, n_groups=groups, chunk=chunk),
        jnp.bfloat16, 1e-5,
    )
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    x = jax.ShapeDtypeStruct((1, t, d_model), jnp.bfloat16)
    params = jax.eval_shape(
        lambda: mixer.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype))["params"]
    )

    def loss(params, x):
        out = jax.checkpoint(lambda params, x: mixer.apply({"params": params}, x))(params, x)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(described(params), described(x))
    text = lowered.compile().as_text()
    assert " concatenate(" not in text
    assert re.search(r"\(bf16\[1,%d,%d\]\S* [^=]*custom-call\(" % (h * p + 2 * groups * n, t), text)
    # under ``ssm_scan`` at most the gate's own last step backward (``dy`` rounded as it
    # is laid out, where XLA names the fusion by its root's ``transpose``)
    moved = _large_under(text, "ssm_scan", t * h * p)
    assert not [(kind, dtype) for kind, dtype in moved if "copy" in kind or dtype != "bf16"]
    assert len(moved) <= 1
    at_the_gate = _large_under(text, "ssm_gate", t * h * p)
    assert at_the_gate and not [
        kind for kind, _ in at_the_gate if "copy" in kind or "transpose" in kind
    ]


def test_a_one_branch_step_on_the_tpu_path_leaves_no_matmul_unplaced(one_chip):
    """A toy of Nemotron-H's blocks (a Mamba-2 mixer in two groups, the latent
    expert layer alone, attention alone, a dense feed-forward alone), lowered as
    the chip lowers it (``jax.default_backend`` steered to ``tpu`` here, for this
    compile only): ``STEP_PARTS`` places every matmul of the compiled step, the
    latent's two projections under ``moe_latent``, and an ungated expert's two
    banks make two ``gmm`` calls a pass where a gated expert's make three."""
    from unittest import mock

    import numpy as np
    import optax

    from edl_tpu.models import ArchSpec, MambaSpec, MoESpec, TransformerLM
    from edl_tpu.obs import profile as obs_profile
    from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

    layers = ("mamba", "moe", "attention", "mlp")
    lm = TransformerLM(
        vocab_size=256, d_model=128, num_heads=4, num_kv_heads=1, num_layers=len(layers),
        d_ff=256, dtype=jnp.bfloat16, remat=True, norm_eps=1e-5,
        moe=MoESpec(
            num_experts=16, top_k=4, d_ff=256, norm_topk_prob=True, aux_weight=0.0,
            z_weight=0.0, score_func="sigmoid", route_scale=5.0, bias_rate=1e-3,
            shared_d_ff=256, held=(0, 4), gated=False, activation="relu2", latent=128,
        ),
        arch=ArchSpec(
            layer_types=layers, head_dim=32, rope=False, one_branch=True,
            mamba=MambaSpec(num_heads=8, head_dim=16, d_state=32, n_groups=2, chunk=32),
        ),
    )
    tokens = np.zeros((1, 256), np.int32)
    state = jax.eval_shape(
        lambda: create_state(lm, jax.random.PRNGKey(0), tokens, optax.adamw(1e-3))
    )
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    loss = lambda logits, y: cross_entropy_loss(  # noqa: E731
        logits.reshape(-1, logits.shape[-1]), y.reshape(-1)
    )
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = make_train_step(loss, numerics=False).lower(
            described(state), described((tokens, tokens))
        )
    program = obs_profile.HloProgram(lowered.compile().as_text())
    census = program.census()
    assert census["totals"]["matmuls"] > 0 and census["totals"]["unplaced_matmuls"] == 0
    parts = {key.split("/")[0] for key in census["parts"]}
    assert {"moe_latent", "moe_shared", "moe_experts", "ssm_proj", "mlp", "attn", "head"} <= parts
    # two banks: in each branch of the layer's cond, forward 2 gmm; backward
    # the recomputed up's 1, 2 gmm_dlhs and 2 tgmm (3 / 3 / 3 where gated)
    assert census["kernels"]["gmm/forward"] == 2 * 2
    # and, since PR 60, the buffer branch's sum by token: the combine forward
    # and recomputed (nothing is kept of this block), and the gather's gradient
    assert census["kernels"]["tgmm/forward"] == 1
    assert census["kernels"]["tgmm/backward"] == 2 * 2 + 2


def _latent_cell():
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
        root, "benchmark", "configs", "nemotron_3_super_120b_a12b.json"
    )) as f:
        return root, json.load(f)


def test_the_latent_cells_share_of_the_heads_is_the_one_its_plan_chose():
    """The rule, on the numbers the file records: the first whole period (depth
    9, ``MEMEMEM*E``) with the heads as one of TWO chips' share, if its compiled
    step (the tool) and the chip itself (what ``bytes_limit`` leaves after the
    state and the block the step reserves, and a run beside a ballast of 1 GiB)
    leave at least 1 GB of the chip's 15.75; else one of four chips' share. With
    every head held the period is 1002.7 M parameters, 16.04 GB with its
    gradients: no chip holds it, and the file records the tool's refusal."""
    _, config = _latent_cell()
    plan = config["plan"]
    left = lambda tried: plan["chip_gb"] - tried["total_gb"]  # noqa: E731
    by_share = {t["chips_a_heads"]: t for t in plan["tried"]}
    assert sorted(by_share) == [1, 2]
    assert left(by_share[1]) < 0 and by_share[1]["parameters"] == 1002718720
    chosen = by_share[plan["chosen"]["chips_a_heads"]]
    assert plan["chosen"] == {"num_hidden_layers": 9, "chips_a_heads": 2}
    assert left(chosen) >= plan["least_left_gb"] and chosen["parameters"] == 765620992
    on_chip = chosen["on_chip"]
    assert on_chip["ran"] and on_chip["correct"]
    reserved = on_chip["state_gb"] + on_chip["program_reserve_gib"] * 2 ** 30 / 1e9
    assert on_chip["left_gb"] == pytest.approx(on_chip["bytes_limit"] / 1e9 - reserved, abs=0.01)
    assert on_chip["left_gb"] >= plan["least_left_gb"]
    assert on_chip["beside_a_ballast_of_1_gib"].startswith("ran, correct")
    for tried in plan["tried"]:
        assert tried["left_gb"] == pytest.approx(left(tried), abs=2e-3)
    share = config["share"]
    assert share["chips_a_heads"] == 2 and share["chips_a_layer"] == 64
    published = config["published"]
    assert (config["mamba_num_heads"], config["n_groups"]) == (
        published["mamba_num_heads"] // 2, published["n_groups"] // 2)
    assert (config["num_attention_heads"], config["num_key_value_heads"]) == (
        published["num_attention_heads"] // 2, published["num_key_value_heads"] // 2)
    # each group's heads whole, and the published 16 queries a KV head
    assert config["mamba_num_heads"] // config["n_groups"] == 16
    assert config["num_attention_heads"] // config["num_key_value_heads"] == 16
    # the pattern's first whole period, as blocks 0 to 8 lie
    assert config["hybrid_override_pattern"] == published["hybrid_override_pattern"][:9]
    assert config["num_hidden_layers"] == 9 and published["num_hidden_layers"] == 88


@pytest.mark.slow
def test_the_latent_cells_whole_step_compiles_for_v5e_and_its_plan_is_as_recorded():
    """``benchmark/tools/compile_for_v5e.py`` on the cell as it runs (3 to 5
    minutes): the step compiles, and the plan's total is within 0.1 GB of
    14.73. The configuration's file keeps PR 49's 14.094 (a ``benchmark`` PR's
    to rewrite: a ``perf_opt`` PR edits nothing under ``benchmark/``); since
    the scan's chunk-local stage is kernels (PR 50) the plan read 14.203
    (temporaries 4.65 GB, code 0.37): the float32 ``Y_diag`` a layer's kernel
    hands its carry loop (134 MB) is alive beside the loop's stacked outputs,
    where XLA fused the plain form's into the product's pass; since the four
    mixers' in projections are kept by name (PR 54: 152 MB a layer) it reads
    14.725 (temporaries 5.21). What the chip itself reserves and leaves is
    in PERF.md, section 6, PR 54."""
    import json
    import subprocess
    import sys

    root, config = _latent_cell()
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tools", "compile_for_v5e.py"),
         "nemotron_3_super_120b_a12b.steady"],
        capture_output=True, text=True, timeout=1500, cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    recorded = next(t for t in config["plan"]["tried"] if t["chips_a_heads"] == 2)
    assert doc["parameters"] == recorded["parameters"]
    assert recorded["total_gb"] == pytest.approx(14.09, abs=0.01)
    assert doc["total_gb"] == pytest.approx(14.73, abs=0.1)


# -- Solar-Open2: the rule under Kimi Linear's own gate in a whole step ------------------------------

def _solar_cell():
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "solar_open2_250b.json")) as f:
        return root, json.load(f)


def test_a_solar_step_on_the_tpu_path_runs_the_rule_as_kernels_under_kda_scan(one_chip):
    """A toy of Solar-Open2's first period (a gated attention layer without a
    position term, three Kimi-delta-attention layers with the softplus gate,
    beta to 2 and low-rank pairs, an expert layer in every block), at heads of
    128 so that the kernels take it, lowered as the chip lowers it
    (``jax.default_backend`` steered to ``tpu`` here, for this compile only):
    the rule notes ``path="kernel"`` and its caller's gate, the three ``kda_*``
    custom calls lie under ``kda_scan``, and ``STEP_PARTS`` places every
    matmul of the compiled step."""
    from unittest import mock

    import numpy as np
    import optax

    from edl_tpu.models import ArchSpec, KimiDeltaSpec, MoESpec, TransformerLM
    from edl_tpu.obs import profile as obs_profile
    from edl_tpu.obs import trace as obs_trace
    from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

    layers = ("attention", "kda", "kda", "kda")
    lm = TransformerLM(
        vocab_size=256, d_model=128, num_heads=2, num_kv_heads=1, num_layers=len(layers),
        d_ff=256, dtype=jnp.bfloat16, remat=True, remat_policy="save_flash", norm_eps=1e-5,
        moe=MoESpec(
            num_experts=20, top_k=4, d_ff=128, norm_topk_prob=True, aux_weight=0.0,
            z_weight=0.0, score_func="sigmoid", route_scale=1.0, bias_rate=1e-3,
            shared_d_ff=128, held=(0, 4),
        ),
        arch=ArchSpec(
            layer_types=layers, head_dim=128, rope=False, attn_gate=True,
            kda=KimiDeltaSpec(num_heads=2, key_dim=128, value_dim=128, lower_bound=None,
                              neg_eigval=True, gate_rank=128),
        ),
    )
    tokens = np.zeros((1, 256), np.int32)
    state = jax.eval_shape(
        lambda: create_state(lm, jax.random.PRNGKey(0), tokens, optax.adamw(1e-3))
    )
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    loss = lambda logits, y: cross_entropy_loss(  # noqa: E731
        logits.reshape(-1, logits.shape[-1]), y.reshape(-1)
    )
    tracer = obs_trace.get_tracer()
    tracer.reset_notes()
    before = len([e for e in tracer.to_events() if e["name"] == "kda_chunks"])
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = make_train_step(loss, numerics=False).lower(
            described(state), described((tokens, tokens))
        )
    noted = [e["args"] for e in tracer.to_events() if e["name"] == "kda_chunks"][before:]
    assert noted and all(
        (a["path"], a["pairs"], a["gate"], a["bound"], a["beta_max"], a["rank"])
        == ("kernel", "halving", "softplus", None, 2.0, 128) for a in noted
    )
    text = lowered.compile().as_text()
    program = obs_profile.HloProgram(text)
    census = program.census()
    assert census["totals"]["matmuls"] > 0 and census["totals"]["unplaced_matmuls"] == 0
    parts = {key.split("/")[0] for key in census["parts"]}
    assert {"kda_proj", "kda_conv", "kda_scan", "kda_gate", "attn_gate", "moe_shared",
            "moe_experts", "moe_route", "head"} <= parts
    kernels = {key.split("/")[0] for key in census["kernels"]}
    assert {"kda_inverse", "kda_operands", "kda_backward"} <= kernels
    scopes = obs_profile.scopes_of_hlo(text, ("kda_proj", "kda_conv", "kda_scan", "kda_gate"))
    calls = {name: scope for name, scope in scopes.items() if name.startswith("kda_")}
    assert calls and set(calls.values()) == {"kda_scan"}


def test_the_solar_cells_sequence_is_the_one_its_plan_chose():
    """The rule, on the numbers the file records: one sequence of 8192 if the
    chip itself leaves at least 1 GB of ``bytes_limit`` after the state and the
    block the step reserves and the cell runs ``correct`` beside a ballast of
    1 GiB; the tool's total is recorded beside it (it counts more than the chip
    reserves)."""
    _, config = _solar_cell()
    plan = config["plan"]
    tried = {t["seq_len"]: t for t in plan["tried"]}
    chosen = tried[plan["chosen"]["seq_len"]]
    assert chosen["parameters"] == 840874392
    assert chosen["left_gb"] == pytest.approx(plan["chip_gb"] - chosen["total_gb"], abs=2e-3)
    on_chip = chosen["on_chip"]
    assert on_chip["ran"] and on_chip["correct"]
    reserved = on_chip["state_gb"] + on_chip["program_reserve_gib"] * 2 ** 30 / 1e9
    assert on_chip["left_gb"] == pytest.approx(on_chip["bytes_limit"] / 1e9 - reserved, abs=0.01)
    if plan["chosen"]["seq_len"] == 8192:
        assert on_chip["left_gb"] >= plan["least_left_gb"]
        assert on_chip["beside_a_ballast_of_1_gib"].startswith("ran, correct")
    assert config["train"]["seq_len"] == plan["chosen"]["seq_len"]
    share, published = config["share"], config["published"]
    assert share["chips_a_layer"] == 40 and share["chips_a_heads"] == 8
    assert config["n_routed_experts"] * share["chips_a_layer"] == published["n_routed_experts"]
    assert config["vocab_size"] * share["chips_a_vocabulary"] == published["vocab_size"]
    assert config["linear_attn_config"]["num_heads"] * 8 == published["linear_attn_config.num_heads"]
    assert config["num_attention_heads"] * 8 == published["num_attention_heads"]
    assert config["num_key_value_heads"] * 8 == published["num_key_value_heads"]
    assert config["gqa_layers"] == [0] and config["num_hidden_layers"] == 4


@pytest.mark.slow
def test_the_solar_cells_whole_step_compiles_for_v5e_and_its_plan_is_as_recorded():
    """``benchmark/tools/compile_for_v5e.py`` on the cell as it runs (about
    five minutes): the step compiles, and the plan's total is within 0.1 GB of
    15.93. The configuration's file keeps PR 51's 15.554 (a ``benchmark`` PR's
    to rewrite); since the three KDA layers' in projections are kept by name
    (PR 54: 101 MB a layer) the tool reads 15.929, of which the chip reserves
    less (PERF.md, section 6, PR 54)."""
    import json
    import subprocess
    import sys

    root, config = _solar_cell()
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tools", "compile_for_v5e.py"),
         "solar_open2_250b.steady"],
        capture_output=True, text=True, timeout=1800, cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    recorded = next(
        t for t in config["plan"]["tried"] if t["seq_len"] == config["train"]["seq_len"]
    )
    assert doc["parameters"] == recorded["parameters"]
    assert recorded["total_gb"] == pytest.approx(15.55, abs=0.01)
    assert doc["total_gb"] == pytest.approx(15.93, abs=0.1)


# -- what a block's recomputation runs again of an expert layer (PR 52) --------

_WHERE = (  # first match wins: where an instruction of an expert layer runs
    ("large_again", re.compile(r"branch_0_fun/checkpoint/rematted_computation/")),
    ("recomputed", re.compile(r"/rematted_computation/")),
    ("backward", re.compile(r"transpose\(")),
    ("forward", re.compile(r"")),
)


def _pick_computations(text):
    """``{a fused computation's name: "forward" | "backward"}`` for those that
    hold a reduce of ``models/moe.py:_picked`` (its scope ``picked`` under
    ``moe_route``): the chosen scores read off ``[N, E]`` by comparison, or
    their gradient sent back. XLA names a fusion after its root, which may be
    a neighbour's (the weights' sum), so the fusion is known by what it holds."""
    found, name = {}, None
    for line in text.splitlines():
        header = re.match(r"%([\w.\-]+) \(.*\) -> .* \{$", line)
        if header:
            name = header.group(1)
        elif line.startswith("ENTRY"):
            name = None
        elif name and " reduce(" in line and "/moe_route/picked/" in line:
            found[name] = "backward" if "transpose(" in line.split('op_name="')[-1] else "forward"
    return found


def _called(line):
    """The computation a ``fusion`` instruction calls."""
    return line.split(" calls=%")[-1].split(",")[0]


def _expert_layer_census(text):
    """``{(what, where): count}`` over a compiled step's text: ``what`` the
    router's matmul, a ``top_k``, a sort of the route's, a fusion that holds
    the picks of the chosen scores (``_pick_computations``), a sort that carries the
    weights, the three Megablox kernels by the ``cond``'s branch (``buffer`` /
    ``large``), the sort and the ``tgmm`` of the buffer's sum by token
    (``segment_sort``, ``segment_sum``); ``where`` from the instruction's
    ``op_name``: ``forward``,
    ``recomputed`` (the block's recomputation), ``large_again`` (the large
    branch's own, inside its backward), ``backward``."""
    import collections

    census = collections.Counter()
    picks = set(_pick_computations(text))
    for line in text.splitlines():
        op_name = re.search(r'op_name="([^"]*)"', line)
        opcode = re.search(r" (dot|convolution|sort|custom-call|fusion)\(", line[:4096])
        if not op_name or not opcode or "/moe/" not in op_name.group(1):
            continue
        op_name, opcode = op_name.group(1), opcode.group(1)
        if opcode == "fusion":  # the chosen experts' scores, read off [N, E] by comparison
            if _called(line) not in picks:
                continue
            what = "scores_picked"
        elif opcode == "custom-call":
            kernel = re.search(r"moe_experts/jit\((t?gmm)\)/|/(segment_sum)/jit\(tgmm\)/", op_name)
            if "tpu_custom_call" not in line or not kernel:
                continue
            branch = "large" if "branch_0_fun" in op_name else "buffer"
            what = "%s_%s" % (kernel.group(1) or kernel.group(2), branch)
        elif opcode == "sort":
            what = (
                "top_k" if op_name.endswith("/top_k")
                else "route_sort" if "/moe_route/" in op_name
                else "segment_sort" if "/segment_sum/" in op_name else "weights_sort"
            )
        elif "/moe_route/router/" in op_name:
            what = "router"
        else:
            continue
        where = next(name for name, pattern in _WHERE if pattern.search(op_name))
        census[what, where] += 1
    return census


@pytest.mark.parametrize("policy", ["save_flash", None], ids=["save_flash", "full"])
@pytest.mark.parametrize("choice", ["plain", "grouped"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_a_held_share_step_on_the_tpu_path_decides_and_multiplies_once(
    one_chip, gated, choice, policy
):
    """A toy of one block that holds 8 of 32 experts (a buffer of 2048 of the
    4096 pairs), lowered as the chip lowers it. Under ``save_flash`` the
    policy keeps the layer's ``REMAT_NAMES`` and a block's recomputation runs
    no router matmul, no ``top_k``, no ``argsort``, no pick of the chosen
    scores and no Megablox call in the buffer branch: a layer's route runs once, and the buffer branch launches
    ``gmm`` three times forward (two ungated) and three times for ``d lhs``.
    The large branch keeps nothing and runs ``gate`` / ``up`` again inside its
    own backward, as it did. With ``remat_policy=None`` nothing is kept and the
    recomputation runs all of it, the counts before the names.

    Since PR 60 the buffer branch sums its rows by token (one sort of the
    buffer's 2048 keys and one ``tgmm`` over eight tiles of 128 tokens, forward
    under ``moe_combine`` and once more as ``_rows_sorted``'s gradient): under
    ``/moe/`` and outside the large branch no instruction, forward or backward,
    has an operand or a result of the 4096 pairs' rows at the layer's width;
    the large branch gathers them as it did."""
    from unittest import mock

    import numpy as np
    import optax

    from edl_tpu.models import ArchSpec, MoESpec, TransformerLM
    from edl_tpu.obs import trace as obs_trace
    from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

    layers, banks = 1, 3 if gated else 2
    lm = TransformerLM(
        vocab_size=256, d_model=256, num_heads=2, num_kv_heads=1, num_layers=layers,
        d_ff=256, dtype=jnp.bfloat16, remat=True, remat_policy=policy, norm_eps=1e-5,
        moe=MoESpec(
            num_experts=32, top_k=4, d_ff=256, norm_topk_prob=True, aux_weight=0.0,
            z_weight=0.0, score_func="sigmoid", route_scale=2.5, bias_rate=1e-3,
            held=(0, 8), gated=gated, activation="silu" if gated else "relu2",
            **(dict(n_group=4, topk_group=2) if choice == "grouped" else {}),
        ),
        arch=ArchSpec(head_dim=128),
    )
    tokens = np.zeros((1, 1024), np.int32)
    state = jax.eval_shape(
        lambda: create_state(lm, jax.random.PRNGKey(0), tokens, optax.adamw(1e-3))
    )
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    loss = lambda logits, y: cross_entropy_loss(  # noqa: E731
        logits.reshape(-1, logits.shape[-1]), y.reshape(-1)
    )
    obs_trace.get_tracer().reset_notes()
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = make_train_step(loss, numerics=False).lower(
            described(state), described((tokens, tokens))
        )
    text = lowered.compile().as_text()  # under the default scoped VMEM, on the rule's tiles
    tiles = [a for name, a in obs_trace.get_tracer().notes() if name == "gmm_tiles"]
    assert {a["kernel"] for a in tiles} == {"gmm", "gmm_dlhs", "tgmm", "segment_sum"}
    (by_token,) = [a for a in tiles if a["kernel"] == "segment_sum"]
    assert (by_token["rows"], by_token["groups"], by_token["contracting"]) == (2048, 8, 128)
    assert by_token["tiling"] == [128, 128, 256] and by_token["path"] == "kernel"
    tiles = [a for a in tiles if a is not by_token]
    for a in tiles:  # 8 held groups in a buffer of 2048 or the whole 4096
        assert a["groups"] == 8 and a["rows_a_group"] == a["rows"] // 8 in (256, 512)
        assert a["tiling"][0] == (128 if a["kernel"] == "tgmm" else 256)
        assert a["visits_bound"] == a["rows"] // a["tiling"][0] + 7
    # no cond hands on a [N k, F] array: the large branch keeps nothing, and
    # the join of the two branches' residuals would allocate it on every step
    conds = [line.split(" conditional(")[0] for line in text.splitlines() if " conditional(" in line]
    assert len(conds) == 3 * layers and not [c for c in conds if "[4096,256]" in c]
    census = _expert_layer_census(text)
    # a pick forward and its gradient's way back; the recomputation is handed
    # the weights under a policy and picks again without one
    picked = {key: n for key, n in census.items() if key[0] == "scores_picked"}
    assert picked == dict.fromkeys(
        [("scores_picked", where)
         for where in ("forward", "backward") + (() if policy else ("recomputed",))], layers,
    )
    assert not [
        line for line in text.splitlines()
        if re.search(r" (gather|scatter)\(", line[:4096]) and "/moe_route/" in line
    ]
    census = {key: n for key, n in census.items() if key[0] != "scores_picked"}
    top_ks = 3 if choice == "grouped" else 1
    again = 0 if policy == "save_flash" else 1  # what the recomputation repeats
    want = {
        ("router", "forward"): 1, ("router", "recomputed"): again, ("router", "backward"): 2,
        ("top_k", "forward"): top_ks, ("top_k", "recomputed"): top_ks * again,
        ("route_sort", "forward"): 2, ("route_sort", "recomputed"): 2 * again,
        # the weights ride a sort into expert order in each branch, and their
        # gradient one back: elementwise work's company, made again
        ("weights_sort", "forward"): 2, ("weights_sort", "recomputed"): 1,
        ("weights_sort", "large_again"): 1, ("weights_sort", "backward"): 2,
        ("gmm_buffer", "forward"): banks, ("gmm_buffer", "recomputed"): (banks - 1) * again,
        ("gmm_buffer", "backward"): banks, ("tgmm_buffer", "backward"): banks,
        ("gmm_large", "forward"): banks, ("gmm_large", "large_again"): banks - 1,
        ("gmm_large", "backward"): banks, ("tgmm_large", "backward"): banks,
        # the buffer's rows summed by token: the combine (whose value no
        # recomputation needs) and ``_rows_sorted``'s gradient
        ("segment_sort", "forward"): 1, ("segment_sort", "backward"): 1,
        ("segment_sum_buffer", "forward"): 1, ("segment_sum_buffer", "backward"): 1,
    }
    assert dict(census) == {key: layers * n for key, n in want.items() if n}
    pairs_rows = re.compile(r"\[4096,256\]|\[1024,4,256\]")  # every pair's row, flat or by token
    under_moe = [
        (re.search(r'op_name="([^"]*)"', line).group(1), line.split(", metadata=")[0])
        for line in text.splitlines() if "/moe/" in line and 'op_name="' in line
    ]
    assert not [
        head for op_name, head in under_moe
        if "branch_0_fun" not in op_name and "/moe/" in op_name and pairs_rows.search(head)
    ]
    every_pairs = {
        (scope, "backward" if "transpose(" in op_name else "forward")
        for op_name, head in under_moe for scope in ("moe_combine", "moe_experts")
        if "branch_0_fun" in op_name and op_name.endswith("/%s/gather" % scope)
        and " gather(" in head and "bf16[4096,256]" in head
    }
    assert every_pairs >= {("moe_combine", "forward"), ("moe_experts", "backward")}


# -- a token's chosen scores, read by comparison (PR 65) -----------------------

@pytest.mark.parametrize("k,groups", [
    pytest.param(22, {}, id="nemotron"),
    pytest.param(8, dict(n_group=8, topk_group=4), id="ling"),
])
def test_the_route_picks_its_scores_by_comparison_in_one_fusion_a_direction(one_chip, k, groups):
    """One ``DroplessMoE`` (value and gradients) that routes 8192 tokens over 512
    experts as Nemotron's and Ling's cells do (sigmoid scores, a bias, top-22,
    or top-8 inside the best four of eight groups; 8 experts held), compiled
    for the described v5e: no ``gather`` and no ``scatter`` is left under
    ``moe_route``, the picks are one fusion forward and one backward, and no
    instruction outside a fusion writes an array of ``N k E`` elements (the
    broadcast compare lives inside the fusions: 369 MB at top-22 otherwise)."""
    from edl_tpu.models.moe import DroplessMoE

    n, e = 8192, 512
    layer = DroplessMoE(
        num_experts=e, top_k=k, d_ff=128, score_func="sigmoid", bias_rate=1e-3,
        norm_topk_prob=True, route_scale=2.5, aux_weight=0.0, z_weight=0.0, held=(0, 8),
        **groups,
    )
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    x = jax.ShapeDtypeStruct((1, n, 256), jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype))
    )

    def value_and_gradients(variables, x):
        def loss(params, x):
            y, _ = layer.apply(
                {**variables, "params": params}, x,
                mutable=["losses", "metrics", "batch_stats"],
            )
            return jnp.sum(jnp.square(y.astype(jnp.float32)))

        return jax.value_and_grad(loss, argnums=(0, 1))(variables["params"], x)

    # the experts' matmuls in their plain form (the backend is the CPU's): the
    # route asks no backend, and no Megablox call is compiled for its sake
    text = jax.jit(value_and_gradients).lower(
        described(variables), described(x)
    ).compile().as_text()
    under_route = [line for line in text.splitlines() if "/moe_route/" in line]
    assert under_route and not [
        line for line in under_route if re.search(r" (gather|scatter)\(", line[:4096])
    ]
    picks = _pick_computations(text)
    entry = text[text.index("ENTRY"):].splitlines()
    calls = [
        picks[_called(line)] for line in entry
        if " fusion(" in line[:4096] and _called(line) in picks
    ]
    assert sorted(calls) == ["backward", "forward"] == sorted(picks.values())
    wide, fused = [], False
    for line in text.splitlines():  # what a fusion holds inside is no array
        if re.match(r"%fused_computation[\w.\-]* \(", line):
            fused = True
        elif line.startswith(("%", "ENTRY")):
            fused = False
        shape = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \(?\w+\[([0-9,]*)\]", line)
        if shape and not fused and math.prod(
                int(d) for d in shape.group(1).split(",") if d) >= n * k * e:
            wide.append(line.strip()[:120])
    assert not wide


# -- what a block's recomputation runs again of a mixer's projections (PR 54) --

@pytest.mark.parametrize("named", [True, False], ids=["named", "unnamed"])
@pytest.mark.parametrize("mixer", ["mamba2", "kda"])
def test_a_mixers_step_on_the_tpu_path_projects_once(one_chip, monkeypatch, mixer, named):
    """A toy of one block that is a Mamba-2 mixer, or Kimi delta attention with
    its low-rank pairs, alone (``one_branch``: no feed-forward reads what the
    out projection adds to), under ``save_flash``, lowered as the chip lowers
    it. With ``mixer_in`` in the policy a block's recomputation holds no matmul
    under the mixer's ``*_proj`` scope but a pair's first matrix; with the name
    taken out of the policy each in projection is multiplied again, the counts
    before the name."""
    import collections
    from unittest import mock

    import numpy as np
    import optax

    from edl_tpu.models import ArchSpec, KimiDeltaSpec, MambaSpec, TransformerLM, transformer
    from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

    # its ArchSpec fields, its layer type, the scope of its projections and
    # the leaves of the in projections that bear the name
    fields, layer, scope, leaves = {
        "mamba2": (
            dict(mamba=MambaSpec(num_heads=8, head_dim=16, d_state=128, n_groups=1, chunk=128)),
            "mamba", "ssm_proj", ("in_proj",),
        ),
        "kda": (
            dict(kda=KimiDeltaSpec(num_heads=2, key_dim=128, value_dim=128, lower_bound=None,
                                   neg_eigval=True, gate_rank=128)),
            "kda", "kda_proj", ("q_proj", "k_proj", "v_proj", "f_up", "g_up", "b_proj"),
        ),
    }[mixer]
    if not named:
        monkeypatch.setattr(transformer, "MIXER_NAMES", ())
    lm = TransformerLM(
        vocab_size=256, d_model=128, num_heads=2, num_kv_heads=1, num_layers=1, d_ff=256,
        dtype=jnp.bfloat16, remat=True, remat_policy="save_flash", norm_eps=1e-5,
        arch=ArchSpec(layer_types=(layer,), head_dim=64, rope=False, one_branch=True,
                      **fields),
    )
    tokens = np.zeros((1, 256), np.int32)
    state = jax.eval_shape(
        lambda: create_state(lm, jax.random.PRNGKey(0), tokens, optax.adamw(1e-3))
    )
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    loss = lambda logits, y: cross_entropy_loss(  # noqa: E731
        logits.reshape(-1, logits.shape[-1]), y.reshape(-1)
    )
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = make_train_step(loss, numerics=False).lower(
            described(state), described((tokens, tokens))
        )
    again, all_passes = collections.Counter(), collections.Counter()
    for line in lowered.compile().as_text().splitlines():
        op_name = re.search(r'op_name="([^"]*)"', line)
        if not op_name or not re.search(r" (dot|convolution)\(", line[:4096]):
            continue
        leaf = re.search(r"/%s/(\w+)/dot_general" % scope, op_name.group(1))
        if leaf:
            all_passes[leaf.group(1)] += 1
            if "/rematted_computation/" in op_name.group(1):
                again[leaf.group(1)] += 1
    pairs_first = {"f_down": 1, "g_down": 1} if mixer == "kda" else {}
    want = dict(pairs_first, **(dict.fromkeys(leaves, 1) if not named else {}))
    assert dict(again) == want
    # forward once and the backward's two, whatever the policy keeps
    assert {leaf: all_passes[leaf] - again[leaf] for leaf in leaves} == dict.fromkeys(leaves, 3)


# -- GLM-4.7-Flash: latent attention at 20 heads of 256 / 256, a multi-token module (PR 55) ------

MLA_256 = (1, 20, 8192, 256, 256)  # glm_4_7_flash.steady's six calls: b, h, t, d_qk, d_v


def _glm_cell():
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "glm_4_7_flash.json")) as f:
        return root, json.load(f)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash2_compiles_for_v5e_at_twenty_heads_of_256(one_chip, direction):
    """The grid-pipelined forward and the FUSED backward with q, k, v, dO, o
    and every gradient 256 wide at T = 8192 (a head's float32 dq accumulator is
    8.4 MB): the chip's compiler takes the blocks ``_flash2_blocks`` gives and
    the VMEM limit ``_fused_bwd_vmem`` sets from the shapes."""
    b, heads, t, d_qk, d_v = MLA_256
    scale = d_qk ** -0.5

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    q, v = sds((b, heads, t, d_qk)), sds((b, heads, t, d_v))
    if direction == "fwd":
        bq, bk = A._flash2_blocks("fwd", t, t, None)
        fn = lambda q, k, v: A._flash2_forward(q, k, v, True, scale, bq, bk, False)
        args, want = (q, q, v), ["_flash2_kernel"]
    else:
        bq, bk = A._flash2_blocks("bwd", t, t, None)
        fn = lambda q, k, v, g, lse, delta: A._flash2_backward_kernels(
            q, k, v, g, lse, delta, True, scale, bq, bk, False
        )
        row = sds((b * heads, t), jnp.float32)
        args, want = (q, q, v, v, row, row), ["_flash2_bwd_kernel"]
    lowered = jax.jit(fn).lower(*args)
    assert _kernel_names(lowered.as_text()) == want
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    shapes = [o.shape for o in jax.tree.leaves(jax.eval_shape(fn, *args))]
    if direction == "fwd":
        assert shapes[0] == (b, heads, t, d_v)
    else:
        assert shapes == [(b, heads, t, d_qk), (b, heads, t, d_qk), (b, heads, t, d_v)]


def test_the_glm_cells_plan_fits_the_chip_at_the_guides_floor():
    """The numbers the file records: the tool's plan of the whole step (a
    described v5e) under the chip's 15.75 GB, the cut at the guide's floor (a
    leading dense layer and four that follow, 8 experts a layer, an eighth of
    the vocabulary), and what the chip itself read."""
    _, config = _glm_cell()
    plan = config["plan"]
    (tried,) = plan["tried"]
    assert tried["parameters"] == 706518528
    assert tried["total_gb"] == pytest.approx(13.32, abs=0.01) and tried["total_gb"] < plan["chip_gb"]
    assert tried["left_gb"] == pytest.approx(plan["chip_gb"] - tried["total_gb"], abs=2e-3)
    assert tried["on_chip"]["ran"] and tried["on_chip"]["correct"]
    assert config["train"]["seq_len"] == plan["chosen"]["seq_len"] == tried["seq_len"] == 8192
    share, published = config["share"], config["published"]
    assert config["n_routed_experts"] * share["chips_a_layer"] == published["n_routed_experts"]
    assert config["vocab_size"] * share["chips_a_vocabulary"] == published["vocab_size"]
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] == 4
    assert config["num_nextn_predict_layers"] == 1


@pytest.mark.slow
def test_the_glm_cells_whole_step_compiles_for_v5e_and_its_plan_is_as_recorded():
    """``benchmark/tools/compile_for_v5e.py`` on the cell as it runs (about
    three minutes): the step compiles with its six attention calls' kernels,
    and the plan's total is within 0.1 GB of the recorded one."""
    import json
    import subprocess
    import sys

    root, config = _glm_cell()
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tools", "compile_for_v5e.py"),
         "glm_4_7_flash.steady"],
        capture_output=True, text=True, timeout=1500, cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    (recorded,) = config["plan"]["tried"]
    assert doc["parameters"] == recorded["parameters"]
    assert doc["total_gb"] == pytest.approx(recorded["total_gb"], abs=0.1)
    assert doc["tpu_custom_calls"] == recorded["tpu_custom_calls"]


def test_a_multi_token_step_on_the_tpu_path_places_the_modules_matmuls(one_chip):
    """A toy of the cell's model (latent attention with a query rank, a dense
    layer, expert layers, the multi-token module), lowered as the chip lowers it:
    ``STEP_PARTS`` places every matmul of the compiled step, the module's joined
    projection under ``mtp_join`` and the head's second use under ``mtp_head``
    (not ``head``: the main head's alone), and asked for the module's scopes
    alone ``mtp`` takes its block's attention kernels with everything else the
    module runs, so a trace tells them from the trunk's."""
    from unittest import mock

    import numpy as np
    import optax

    from edl_tpu.models import ArchSpec, LatentAttentionSpec, MoESpec, MTPSpec, TransformerLM
    from edl_tpu.obs import profile as obs_profile
    from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

    lm = TransformerLM(
        vocab_size=256, d_model=128, num_heads=4, num_layers=2, d_ff=256,
        dtype=jnp.bfloat16, remat=True, norm_eps=1e-5,
        moe=MoESpec(
            num_experts=16, top_k=4, d_ff=128, norm_topk_prob=True, aux_weight=0.0,
            z_weight=0.0, score_func="sigmoid", route_scale=1.8, bias_rate=1e-3,
            shared_d_ff=128, held=(0, 4),
        ),
        arch=ArchSpec(
            layer_types=("latent_attention",) * 2, dense_layers=1, rope_theta=1e6,
            latent_attention=LatentAttentionSpec(
                kv_lora_rank=64, qk_nope_head_dim=96, qk_rope_head_dim=32, v_head_dim=128,
                q_lora_rank=96,
            ),
            mtp=MTPSpec(),
        ),
    )
    tokens = np.zeros((1, 512), np.int32)
    state = jax.eval_shape(
        lambda: create_state(lm, jax.random.PRNGKey(0), tokens, optax.adamw(1e-3))
    )
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
    )
    loss = lambda logits, y: cross_entropy_loss(  # noqa: E731
        logits.reshape(-1, logits.shape[-1]), y.reshape(-1)
    )
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = make_train_step(loss, numerics=False).lower(
            described(state), described((tokens, tokens))
        )
    text = lowered.compile().as_text()
    program = obs_profile.HloProgram(text)
    census = program.census()
    assert census["totals"]["matmuls"] > 0 and census["totals"]["unplaced_matmuls"] == 0
    parts = {key.split("/")[0] for key in census["parts"]}
    assert {"mtp_join", "mtp_head", "head", "mla_proj", "moe_shared", "moe_experts", "mlp"} <= parts
    # three attention calls (two layers and the module's block): a forward and a
    # fused backward each, the module's under ``mtp`` when its scopes are asked alone
    calls = {name: scope for name, scope in program.scopes(("attn_mla",)).items()
             if name in program.kernels}
    assert len(calls) == 3 * 2
    under_module = {name for name, scope in program.scopes(("mtp", "mtp_join", "mtp_head")).items()
                    if name in program.kernels and name in calls}
    assert len(under_module) == 2


# -- SmallThinker: 16,384 positions, a group of seven query heads ---------------

SMALLTHINKER = (1, 28, 4, 16384, 128)  # smallthinker_21b_a3b.steady's attention layers


def _smallthinker_cell():
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "smallthinker_21b_a3b.json")) as f:
        return root, json.load(f)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("window", [None, 4096], ids=["full", "w4096"])
def test_flash2_compiles_for_v5e_at_sixteen_thousand_positions_and_a_group_of_seven(
    one_chip, direction, window
):
    """The grid-pipelined forward and the FUSED backward at T = 16,384, 28 query
    heads over 4 key heads of 128 (``i // 7`` in the kernels' index maps, dk and
    dv folded from seven heads), over the whole sequence and under the
    published window of 4096, with the blocks the dispatch gives: twice the
    longest unmasked sequence any other cell has, and a head's float32 dq
    accumulator at 8.4 MB under the limit ``_fused_bwd_vmem`` sets."""
    b, h, h_kv, t, d = SMALLTHINKER

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    q, kv = sds((b, h, t, d)), sds((b, h_kv, t, d))
    fwd, dq, dkv = (A._flash2_blocks(kind, t, t, window) for kind in ("fwd", "dq", "bwd"))
    if direction == "fwd":
        fn = lambda q, k, v: A._flash2_forward(
            q, k, v, True, d ** -0.5, *fwd, False, window
        )
        args, want = (q, kv, kv), [FWD_NAME]
    else:
        fn = lambda q, k, v, g, lse, delta: A._flash2_backward_kernels(
            q, k, v, g, lse, delta, True, d ** -0.5, *dq, False, window, dkv
        )
        row = sds((b * h, t), jnp.float32)
        args, want = (q, kv, kv, q, row, row), list(BWD_NAMES)
    lowered = jax.jit(fn).lower(*args)
    assert _kernel_names(lowered.as_text()) == want
    assert lowered.compile().as_text().count("tpu_custom_call") == len(want)
    shapes = [o.shape for o in jax.tree.leaves(jax.eval_shape(fn, *args))]
    if direction == "bwd":  # dq at the query heads' width, dk and dv folded to the key heads'
        assert shapes == [(b, h, t, d), (b, h_kv, t, d), (b, h_kv, t, d)]


def test_the_smallthinker_cells_rung_is_the_first_its_ladder_leaves_room_for():
    """The numbers the file records: the ladder's rungs in order (depth 8 with
    one sequence, depth 4 with two, depth 4 with one), each with the tool's plan
    of the whole step (a described v5e), and the chosen one the first that
    leaves at least 1 GB of the chip's 15.75 and ran on the chip."""
    _, config = _smallthinker_cell()
    plan = config["plan"]
    rungs = [(t["num_hidden_layers"], t["batch_per_chip"]) for t in plan["tried"]]
    assert rungs == [(8, 1), (4, 2), (4, 1)]
    fits = [t for t in plan["tried"] if t["left_gb"] >= 1.0 and t["on_chip"]["ran"]]
    chosen = plan["chosen"]
    assert (fits[0]["num_hidden_layers"], fits[0]["batch_per_chip"]) == (
        chosen["num_hidden_layers"], chosen["batch_per_chip"]
    ) == (config["num_hidden_layers"], config["train"]["batch_per_chip"])
    for tried in plan["tried"]:
        assert tried["seq_len"] == config["train"]["seq_len"] == 16384
        assert tried["left_gb"] == pytest.approx(plan["chip_gb"] - tried["total_gb"], abs=2e-3)
    assert fits[0]["parameters"] == chosen["parameters"]
    assert fits[0]["on_chip"]["correct"] and 4.0 <= fits[0]["on_chip"]["hbm_peak_gb"] < 15.75
    share, published = config["share"], config["published"]
    assert config["moe_num_primary_experts"] * share["chips_a_layer"] == 64
    assert config["vocab_size"] * share["chips_a_vocabulary"] == published["vocab_size"]


@pytest.mark.slow
def test_the_smallthinker_cells_whole_step_compiles_for_v5e_and_its_plan_is_as_recorded():
    """``benchmark/tools/compile_for_v5e.py`` on the cell as it runs (several
    minutes): the step compiles with its attention layers' kernels and its
    expert layers', and the plan's total is within 0.1 GB of the recorded one."""
    import json
    import subprocess
    import sys

    root, config = _smallthinker_cell()
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tools", "compile_for_v5e.py"),
         "smallthinker_21b_a3b.steady"],
        capture_output=True, text=True, timeout=1500, cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    chosen = config["plan"]["chosen"]
    recorded = next(
        t for t in config["plan"]["tried"]
        if (t["num_hidden_layers"], t["batch_per_chip"])
        == (chosen["num_hidden_layers"], chosen["batch_per_chip"])
    )
    assert doc["parameters"] == recorded["parameters"]
    assert doc["total_gb"] == pytest.approx(recorded["total_gb"], abs=0.1)
    assert doc["tpu_custom_calls"] == recorded["tpu_custom_calls"]


# sdar_30b_a3b.steady's attention layers: a clean and a noised copy of 8192, GQA 32:4 x 128
SDAR = (1, 32, 4, 8192, 128, 4)


@pytest.mark.parametrize("kernels", ["fwd", "bwd", "dq_dkv"])
def test_flash2_compiles_for_v5e_under_the_block_diffusion_mask(one_chip, monkeypatch, kernels):
    """The grid-pipelined kernels under ``block_diffusion=(8192, 4)`` over
    16,384 positions, with the blocks ``_flash2_blocks`` gives the kind: a block
    index that jumps from the clean keys to a noised block's own (and, a kv
    block, from the clean rows to the later noised ones), the mask from a
    tile's corner, the fused backward's dq accumulator of a 16,384-row head
    (8 MiB) under the limit ``_fused_bwd_vmem`` sets; and the dq / dkv pair a
    chip of less VMEM would take."""
    b, h, h_kv, length, d, block = SDAR
    t, bd, scale = 2 * length, (length, block), d ** -0.5

    def sds(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    q, kv = sds((b, h, t, d)), sds((b, h_kv, t, d))
    if kernels == "fwd":
        bq, bk = A._flash2_blocks("fwd", t, t, None, None, bd)
        fn = lambda q, k, v: A._flash2_forward(q, k, v, True, scale, bq, bk, False, None, bd)
        args, want = (q, kv, kv), ["_flash2_kernel"]
    else:
        if kernels == "dq_dkv":
            monkeypatch.setattr(A, "_vmem_capacity", lambda: 0)
        dq, dkv = (A._flash2_blocks(kind, t, t, None, None, bd) for kind in ("dq", "bwd"))
        fn = lambda q, k, v, g, lse, delta: A._flash2_backward_kernels(
            q, k, v, g, lse, delta, True, scale, *dq, False, None, dkv, bd
        )
        row = sds((b * h, t), jnp.float32)
        args = (q, kv, kv, q, row, row)
        want = (
            ["_flash2_bwd_kernel"] if kernels == "bwd"
            else ["_flash2_bwd_dq_kernel", "_flash2_bwd_dkv_kernel"]
        )
    for kind, side in (("fwd", "kv"), ("dq", "kv"), ("bwd", "q")):
        assert A._spans_fit(*A._flash2_blocks(kind, t, t, None, None, bd), t, t, None, side, bd)
    lowered = jax.jit(fn).lower(*args)
    assert _kernel_names(lowered.as_text()) == want
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") == len(want)
