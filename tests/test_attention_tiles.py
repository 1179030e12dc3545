"""What the mask makes of a tile: the grid-pipelined flash kernels' walk.

A tile of the ``flash2`` kernels is dead, interior or an edge
(``_tile_class``). Dead tiles are neither copied nor computed: a masked
call's innermost grid steps are spans that start where the block's first
visible key (or row) lies, and a step the mask leaves nothing for holds the
nearest live span again. Here: the kernels against the dense reference in
interpret mode over the geometries that exercise that (full-causal, a window
of whole blocks and one more, fewer queries than keys, spans no block
divides); the span maps against the mask itself; the static census against a
count over the mask. (``tests/test_attention.py`` is marked ``slow`` as a
module, so tier-1 never runs it: these live here.)
"""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

A = importlib.import_module("edl_tpu.ops.attention")
from edl_tpu.obs import trace as obs_trace  # noqa: E402

NAMES = ("o", "lse", "dq", "dk", "dv")
# the backward a call takes: one fused kernel where a head's dq accumulator
# fits the chip's VMEM, the dq and dk/dv kernels where it does not
BACKWARDS = ("fused", "pair")


@pytest.fixture
def backward(request, monkeypatch):
    """``"pair"``: a chip with no VMEM to spare, so that no accumulator fits."""
    if request.param == "pair":
        monkeypatch.setattr(A, "_vmem_capacity", lambda: 0)
    return request.param


def _bwd_kernels_traced(fn):
    """The ``kernel_trace`` names of the backward kernels ``fn`` enters."""
    ring = obs_trace.get_tracer()
    ring.clear()
    out = fn()
    names = [
        e["args"]["kernel"] for e in ring.to_events() if e["name"] == "kernel_trace"
    ]
    return out, [n for n in names if n != "flash2_fwd"]


TRACED = {"fused": ["flash2_bwd"], "pair": ["flash2_dq", "flash2_dkv"]}


def _inputs(h, h_kv, tq, tk, d, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda heads, t: jnp.asarray(rng.randn(1, heads, t, d), jnp.float32)  # noqa: E731
    return mk(h, tq), mk(h_kv, tk), mk(h_kv, tk), mk(h, tq)


def _kernels(q, k, v, g, fwd, dq, dkv, window):
    """(o, lse, dq, dk, dv) of the flash2 kernels, interpret mode (``dkv``:
    the blocks of the fused backward, and of dk/dv in the pair)."""
    b, h, tq, d = q.shape
    scale = d ** -0.5
    o, lse = A._flash2_forward(q, k, v, True, scale, *fwd, True, window)
    grads = A._flash2_backward_kernels(
        q, k, v, g, lse, A._bwd_delta(g, o, b, h, tq, d), True, scale, *dq,
        True, window, dkv,
    )
    return tuple(np.asarray(x) for x in (o, lse, *grads))


def _reference(q, k, v, g, window):
    scale = q.shape[-1] ** -0.5

    @jax.jit
    def dense(q, k, v, g):  # one program, not a compile an operation
        o, lse = A.attention_reference_with_lse(
            q, k, v, causal=True, scale=scale, window=window
        )
        _, vjp = jax.vjp(
            lambda q, k, v: A.attention_reference(
                q, k, v, causal=True, scale=scale, window=window
            ), q, k, v,
        )
        return (o, lse.reshape(-1, q.shape[2]), *vjp(g))

    return tuple(np.asarray(x) for x in dense(q, k, v, g))


@functools.lru_cache(maxsize=None)
def _walk_reference(h, h_kv, tq, tk, d, window):
    """The dense side of a walk, once for both of its backwards."""
    return _reference(*_inputs(h, h_kv, tq, tk, d), window)


def _sees(tq, tk, window):
    return np.asarray(A._sees(
        np.arange(tq)[:, None] + (tk - tq), np.arange(tk)[None, :], window
    ))


# Blocks an eighth of the cells' (spans aligned to 16 where the chip aligns
# them to 128), so that the tile counts are the cells': full-causal 4 q
# blocks a kv block forward (256 x 1024 there) and 2 backward (512 x 1024);
# a windowed call one forward update of 320 keys a 64 rows (2560 a 512), dq
# one of 288 a 32 rows, dk/dv two of 160 rows a 64 keys.
ALIGN = 16
FULL = ((32, 128), (64, 128), (64, 128))
WINDOWED = ((64, 320), (32, 288), (160, 64))
#        h, h_kv, tq,  tk,  d,   window, blocks
WALKS = [
    pytest.param(8, 1, 512, 512, 128, None, FULL, id="full-gqa8-d128"),
    pytest.param(8, 2, 512, 512, 64, None, FULL, id="full-gqa4-d64"),
    pytest.param(32, 4, 256, 256, 128, None, FULL, id="full-32:4-d128"),
    pytest.param(32, 8, 256, 256, 64, None, FULL, id="full-32:8-d64"),
    pytest.param(8, 1, 512, 512, 128, 256, WINDOWED, id="window-2-blocks"),
    pytest.param(8, 1, 512, 512, 128, 257, WINDOWED, id="window-2-blocks+1"),
    pytest.param(8, 2, 512, 512, 64, 256, FULL, id="window-2-blocks-whole-blocks"),
    pytest.param(8, 2, 512, 512, 64, 257, FULL, id="window-2-blocks+1-whole-blocks"),
    pytest.param(4, 2, 512, 512, 64, 100, ((32, 48), (64, 80), (96, 128)), id="window100-odd-spans"),
    pytest.param(4, 2, 384, 512, 64, None, FULL, id="offset128"),
    pytest.param(4, 2, 128, 512, 64, None, FULL, id="offset384"),
    pytest.param(4, 2, 384, 512, 64, 100, WINDOWED, id="offset128-window100"),
    pytest.param(4, 2, 128, 512, 64, 100, FULL, id="offset384-window100"),
    pytest.param(4, 2, 256, 256, 64, 1000, FULL, id="window-past-the-sequence"),
    # every head its own keys (group 1), at both head widths
    pytest.param(2, 2, 256, 256, 128, None, FULL, id="full-mha-d128"),
    pytest.param(2, 2, 512, 512, 64, 256, WINDOWED, id="window-mha-d64"),
    pytest.param(8, 2, 384, 512, 128, 256, WINDOWED, id="offset128-window-gqa4-d128"),
]


@pytest.mark.parametrize("backward", BACKWARDS, indirect=True)
@pytest.mark.parametrize("h,h_kv,tq,tk,d,window,blocks", WALKS)
def test_flash2_walk_agrees_with_the_dense_reference(
    monkeypatch, backward, h, h_kv, tq, tk, d, window, blocks
):
    """Forward and backward (the fused kernel; dq and dk/dv where a head's
    dq does not fit) over dead steps, clamped spans and spans that start
    between blocks: a class or a span off by one is a whole wrong tile, far
    past these tolerances (the file's own)."""
    monkeypatch.setattr(A, "_SPAN_ALIGN", ALIGN)
    q, k, v, g = _inputs(h, h_kv, tq, tk, d)
    fitted = tuple(
        A._fit_blocks(*pair, tq, tk, window, side)
        for pair, side in zip(blocks, ("kv", "kv", "q"))
    )
    for pair, side in zip(fitted, ("kv", "kv", "q")):
        assert A._spans_fit(*pair, tq, tk, window, side)
    got, traced = _bwd_kernels_traced(
        lambda: _kernels(q, k, v, g, *fitted, window)
    )
    assert traced == TRACED[backward]
    want = _walk_reference(h, h_kv, tq, tk, d, window)
    for name, a, b in zip(NAMES, got, want):
        tol = 3e-5 if name in ("o", "lse") else 3e-4
        np.testing.assert_allclose(a, b.reshape(a.shape), atol=tol, err_msg=name)


@pytest.mark.parametrize("backward", BACKWARDS, indirect=True)
def test_a_window_that_reaches_every_key_is_the_causal_kernel_to_the_bit(backward):
    """Spans from key 0 over every block are the walk without a window,
    and a window no key falls out of masks nothing more."""
    q, k, v, g = _inputs(4, 2, 256, 256, 64, seed=3)
    got = _kernels(q, k, v, g, *FULL, 256)
    plain = _kernels(q, k, v, g, *FULL, None)
    for name, a, b in zip(NAMES, got, plain):
        np.testing.assert_array_equal(a, b, err_msg=name)


# -- the fused backward against the pair, and where it is taken -------------

#          h, h_kv, tq,  tk,  d,   window, (block_q, block_k) of both
AGAINST = [
    pytest.param(8, 2, 512, 512, 128, None, (64, 128), id="full-gqa4-d128"),
    pytest.param(8, 1, 256, 256, 64, None, (64, 128), id="full-gqa8-d64"),
    pytest.param(2, 2, 256, 256, 128, None, (64, 128), id="full-mha-d128"),
    pytest.param(8, 2, 512, 512, 128, 256, (160, 64), id="window-gqa4-d128"),
    pytest.param(8, 1, 512, 512, 64, 257, (160, 64), id="window+1-gqa8-d64"),
    pytest.param(4, 2, 128, 512, 64, None, (64, 128), id="offset384"),
    pytest.param(4, 2, 384, 512, 64, 100, (160, 64), id="offset128-window100"),
]


@pytest.mark.parametrize("h,h_kv,tq,tk,d,window,blocks", AGAINST)
def test_the_fused_backward_against_dq_and_dkv(
    monkeypatch, h, h_kv, tq, tk, d, window, blocks
):
    """One walk, dk/dv's own: with the pair's dk/dv blocks the fused kernel
    adds the same tiles' products in the same order, from a tile it holds
    transposed; dq is summed a kv block at a time where the pair sums a q
    block's keys. All three agree to float32 rounding here (on the chip, in
    bfloat16, dk and dv came out the pair's to the bit: PR 34's probes)."""
    monkeypatch.setattr(A, "_SPAN_ALIGN", ALIGN)
    q, k, v, g = _inputs(h, h_kv, tq, tk, d, seed=5)
    fwd = A._fit_blocks(32, 128, tq, tk, window, "kv")
    rows = A._fit_blocks(*blocks, tq, tk, window, "q")
    run = lambda: _kernels(q, k, v, g, fwd, fwd, rows, window)  # noqa: E731
    fused, traced = _bwd_kernels_traced(run)
    assert traced == TRACED["fused"]
    monkeypatch.setattr(A, "_vmem_capacity", lambda: 0)
    pair, traced = _bwd_kernels_traced(run)
    assert traced == TRACED["pair"]
    for name, a, b in zip(NAMES, fused, pair):
        if name in ("o", "lse"):
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:
            np.testing.assert_allclose(a, b, atol=3e-5, err_msg=name)


def test_a_head_whose_dq_does_not_fit_takes_dq_and_dkv(monkeypatch):
    """The rule reads shapes, not names: the accumulator's ``tq * d * 4``
    bytes, its output's two buffers, the blocks and a tile's intermediates
    against half the core's VMEM. The cells' heads take 20-34 MB of a v5e's
    128; the same call on a core with 16 MiB, or a head of 131,072 rows,
    keeps the two kernels, and agrees."""
    acc, need = A._fused_bwd_vmem(8192, 128, 512, 1024, 2)
    assert acc == 8192 * 128 * 4 and acc + 2 * 8192 * 128 * 2 < need < 32 << 20
    assert A._fused_bwd_vmem(8192, 128, 1280, 512, 2)[1] < 40 << 20
    assert A._fused_bwd_vmem(8192, 64, 512, 1024, 2)[1] < 32 << 20
    assert A._fused_bwd_vmem(4096, 128, 512, 1024, 2)[1] < 32 << 20
    assert A._fused_bwd_vmem(131072, 128, 512, 1024, 2)[1] > A._VMEM_V5E // 2
    assert A._vmem_capacity() == A._VMEM_V5E  # off the chip: the v5e's
    q, k, v, g = _inputs(4, 2, 256, 256, 64, seed=7)
    blocks = ((32, 128), (64, 128), (64, 128))
    want = _reference(q, k, v, g, None)
    acc, need = A._fused_bwd_vmem(256, 64, 64, 128, 4)
    for capacity, kernels in (
        (2 * need, TRACED["fused"]), (2 * need - 2, TRACED["pair"]),
        (16 << 20, TRACED["fused"]), (1 << 20, TRACED["pair"]),
    ):
        monkeypatch.setattr(A, "_vmem_capacity", lambda: capacity)
        got, traced = _bwd_kernels_traced(
            lambda: _kernels(q, k, v, g, *blocks, None)
        )
        assert traced == kernels, capacity
        for name, a, b in zip(NAMES, got, want):
            tol = 3e-5 if name in ("o", "lse") else 3e-4
            np.testing.assert_allclose(a, b.reshape(a.shape), atol=tol, err_msg=name)


@pytest.mark.parametrize("backward", BACKWARDS, indirect=True)
@pytest.mark.parametrize("h,h_kv,tq,tk", [
    pytest.param(4, 4, 256, 256, id="mha"),
    pytest.param(8, 2, 256, 256, id="gqa4"),
    pytest.param(8, 2, 128, 256, id="gqa4-offset128"),
])
def test_block_grads_with_external_residuals(backward, h, h_kv, tq, tk):
    """Ring attention's building block: ``lse`` and ``delta`` of the global
    softmax come from outside, and the call is the flash2 backward's, fused
    or not by the same rule."""
    q, k, v, g = _inputs(h, h_kv, tq, tk, 64, seed=9)
    scale = 64 ** -0.5
    o, lse = A.attention_reference_with_lse(q, k, v, causal=True, scale=scale)
    delta = jnp.sum(g * o, axis=-1)
    got, traced = _bwd_kernels_traced(lambda: A.flash_block_grads(
        q, k, v, g, lse, delta, causal=True, block_q=64, block_k=128
    ))
    assert traced == TRACED[backward]
    want = _reference(q, k, v, g, None)[2:]
    for name, a, b in zip(NAMES[2:], got, want):
        np.testing.assert_allclose(np.asarray(a), b, atol=3e-4, err_msg=name)


# -- spans: dead steps copy nothing, live pairs are walked once --------------

#        tq,   tk,   window, (block_q, block_k) of fwd/dq, of dkv
SPANS = [
    pytest.param(8192, 8192, None, (256, 1024), (512, 1024), id="full"),
    pytest.param(8192, 8192, None, (256, 1024), (1024, 1024), id="full-bwd"),
    pytest.param(4096, 4096, None, (256, 1024), (1024, 1024), id="full4096-bwd"),
    # the blocks a full-causal call takes since PR 48's forward sweep
    pytest.param(8192, 8192, None, (1024, 1024), (1024, 1024), id="full-shipped"),
    pytest.param(4096, 4096, None, (1024, 1024), (1024, 1024), id="full4096-shipped"),
    pytest.param(16384, 16384, None, (1024, 1024), (1024, 1024), id="full16384-shipped"),
    pytest.param(8192, 8192, 2048, (512, 2560), (1280, 512), id="window2048"),
    pytest.param(8192, 8192, 2048, (256, 2304), (512, 1024), id="window2048-dq"),
    pytest.param(8192, 8192, 1000, (512, 1536), (768, 512), id="window1000"),
    pytest.param(384, 512, None, (64, 128), (64, 128), id="offset128"),
    pytest.param(128, 512, 100, (64, 128), (64, 128), id="offset384-window100"),
    pytest.param(2048, 8192, 2048, (512, 2560), (1024, 512), id="offset6144-window2048"),
]


@pytest.mark.parametrize("tq,tk,window,kv_side,q_side", SPANS)
def test_spans_cover_what_the_mask_leaves_and_dead_steps_hold_still(
    tq, tk, window, kv_side, q_side
):
    """The flash2 index maps as plain functions over every (block, step).
    The spans a block's steps name lie inside the other side, in ascending
    order, at whole sublanes; every pair the mask leaves lies in exactly one
    span of a step the kernel takes for live (``_tile_class``); a step it
    takes for dead holds the span of the nearest live step again, so Pallas
    sees the index stand still and copies nothing. Without a window that is
    112 of a head's 256 forward steps at T = 8192."""
    off, sees = tk - tq, _sees(tq, tk, window)
    repeats = 0
    for side, (bq, bk) in (("kv", kv_side), ("q", q_side)):
        assert A._spans_fit(bq, bk, tq, tk, window, side)
        walks = A._flash2_maps(True, window, bq, bk, tq, tk, 4)
        steps, index_map = walks[0] if side == "kv" else walks[1]
        block, span = (bq, bk) if side == "kv" else (bk, bq)
        total, blocks = (tk, tq // bq) if side == "kv" else (tq, tk // bk)
        for i in range(blocks):
            held = [int(index_map(5, i, s)[1]) for s in range(steps)]
            if window is None:  # block indices there, elements under a window
                held = [at * span for at in held]
            seen = (A._kv_range(i, bq, off, window, np) if side == "kv"
                    else A._q_range(i, bk, off, window, tq, np))
            start = int(A._spans(seen, span, steps, total, window, np)[0])
            nominal = [start + s * span for s in range(steps)]
            assert 0 <= nominal[0] and nominal[-1] + span <= total
            if side == "kv":
                dead = [bool(A._tile_class(i * bq + off, bq, at, bk, window)[0])
                        for at in nominal]
                mine = sees[i * bq:(i + 1) * bq]
                seen = [mine[:, at:at + span].any() for at in nominal]
                rest = mine.sum() - sum(mine[:, at:at + span].sum() for at in nominal)
            else:
                dead = [bool(A._tile_class(at + off, bq, i * bk, bk, window)[0])
                        for at in nominal]
                mine = sees[:, i * bk:(i + 1) * bk]
                seen = [mine[at:at + span].any() for at in nominal]
                rest = mine.sum() - sum(mine[at:at + span].sum() for at in nominal)
            assert rest == 0                      # nothing live left unwalked
            assert dead == [not s for s in seen]  # the kernel's own test
            live = [at for at, d in zip(nominal, dead) if not d]
            for s, (at, d) in enumerate(zip(held, dead)):
                assert at % 8 == 0 and 0 <= at <= total - span
                if not d:
                    assert at == nominal[s]
                elif live:  # a dead step: the nearest live span again
                    assert at == min(live, key=lambda x: abs(x - nominal[s]))
                    repeats += side == "kv"
    if (tq, window, kv_side) == (8192, None, (256, 1024)):
        assert repeats == 112
    if (tq, window, kv_side) == (8192, None, (1024, 1024)):
        assert repeats == 28  # of 64: q block i of eight leaves 7 - i dead


def test_a_windowed_calls_blocks_come_from_the_window_and_the_shapes():
    """The published window at T = 8192: one forward update of 2560 keys a
    512 rows, the backward two spans of 1280 rows a 512 keys (and where a
    head's dq does not fit the chip, dq one of 2304 keys a 256 rows);
    another window or length moves the spans, not the rule; a window that
    reaches every key takes blocks that divide the sequence; what a caller
    gives wins."""
    blocks = lambda *a: tuple(  # noqa: E731
        A._flash2_blocks(kind, *a) for kind in ("fwd", "dq", "bwd")
    )
    assert blocks(8192, 8192, 2048) == ((512, 2560), (256, 2304), (1280, 512))
    assert blocks(8192, 8192, 1000) == ((512, 1536), (256, 1280), (768, 512))
    assert blocks(32768, 32768, 4096) == ((512, 2304), (256, 2176), (1152, 512))
    assert blocks(8192, 8192, None) == ((1024, 1024), (512, 1024), (1024, 1024))
    assert blocks(4096, 4096, None) == ((1024, 1024), (512, 1024), (1024, 1024))
    for bq, bk in blocks(8192, 8192, 8192) + blocks(32, 32, 8):
        assert 8192 % bq == 0 and 8192 % bk == 0
    assert A._flash2_blocks("fwd", 8192, 8192, 2048, (None, 1024)) == (512, 1024)
    assert A._flash2_blocks("bwd", 8192, 8192, 2048, (256, None)) == (256, 512)
    # steps: one, one and two where whole blocks of 1024 took three and six
    assert A._span_steps(2048, 512, 2560, 8192, 8192)[0] == 1
    assert A._span_steps(2048, 256, 2304, 8192, 8192)[0] == 1
    assert A._span_steps(2048, 1280, 512, 8192, 8192)[1] == 2
    assert A._span_steps(2048, 512, 1024, 8192, 8192) == (3, 6)


# -- the census --------------------------------------------------------------

#         tq,   tk,   block_q, block_k, window, side
CENSUS = [
    pytest.param(512, 512, 32, 128, None, "kv", id="small-fwd"),
    pytest.param(512, 512, 64, 128, None, "q", id="small-dkv"),
    pytest.param(512, 512, 64, 320, 256, "kv", id="small-window-2-blocks"),
    pytest.param(512, 512, 64, 320, 257, "kv", id="small-window-2-blocks+1"),
    pytest.param(512, 512, 160, 64, 257, "q", id="small-window-dkv"),
    pytest.param(384, 512, 64, 128, None, "kv", id="small-offset128"),
    pytest.param(128, 512, 64, 128, 100, "q", id="small-offset384-window100"),
    # the four cells' real shapes
    pytest.param(8192, 8192, 256, 1024, None, "kv", id="granite-trinity-full-fwd"),
    pytest.param(8192, 8192, 512, 1024, None, "kv", id="granite-trinity-full-dq"),
    pytest.param(8192, 8192, 512, 1024, None, "q", id="granite-trinity-full-dkv"),
    pytest.param(8192, 8192, 1024, 1024, None, "q", id="granite-trinity-full-bwd"),
    pytest.param(8192, 8192, 512, 2560, 2048, "kv", id="trinity-window-fwd"),
    pytest.param(8192, 8192, 256, 2304, 2048, "kv", id="trinity-window-dq"),
    pytest.param(8192, 8192, 1280, 512, 2048, "q", id="trinity-window-bwd"),
]


@pytest.mark.parametrize("tq,tk,block_q,block_k,window,side", CENSUS)
def test_tile_census_against_a_count_over_the_mask(
    monkeypatch, tq, tk, block_q, block_k, window, side
):
    """numpy only: the mask itself under the tiles the kernel walks. The
    three shares sum to 1; the live pairs all lie in tiles called interior
    or edge; a tile called interior holds no masked pair; and the masked
    pairs of the edge tiles are what the walk wastes."""
    if tq < 8192:  # an eighth of the cells' blocks: an eighth of the alignment
        monkeypatch.setattr(A, "_SPAN_ALIGN", ALIGN)
    assert A._spans_fit(block_q, block_k, tq, tk, window, side)
    off, sees = tk - tq, _sees(tq, tk, window)
    census = A.tile_census(tq, tk, block_q, block_k, True, window, side)
    assert sum(census.values()) == pytest.approx(1.0)
    kv_steps, q_steps = A._span_steps(window, block_q, block_k, tq, tk)
    interior = edge = live_pairs = 0
    for i in range(tq // block_q if side == "kv" else tk // block_k):
        for s in range(kv_steps if side == "kv" else q_steps):
            if side == "kv":
                seen = A._kv_range(i, block_q, off, window, np)
                at = int(A._spans(seen, block_k, kv_steps, tk, window, np)[0])
                tile = sees[i * block_q:(i + 1) * block_q][:, at + s * block_k:at + (s + 1) * block_k]
            else:
                seen = A._q_range(i, block_k, off, window, tq, np)
                at = int(A._spans(seen, block_q, q_steps, tq, window, np)[0])
                tile = sees[at + s * block_q:at + (s + 1) * block_q][:, i * block_k:(i + 1) * block_k]
            assert tile.shape == (block_q, block_k)
            interior += tile.all()
            edge += tile.any() and not tile.all()
            live_pairs += tile.sum()
    area = block_q * block_k / (tq * tk)
    assert live_pairs == sees.sum()
    assert census["interior"] == pytest.approx(interior * area)
    assert census["edge"] == pytest.approx(edge * area)
    assert census["interior"] + census["edge"] >= sees.mean()


def test_the_census_at_the_cells_shapes():
    """Full-causal T = 8192: whole tiles of 256 x 1024 leave 44% of the
    rectangle dead and put 22% of what is walked on the diagonal. The 2048
    window sees 22% of the rectangle: three steps of 1024 keys a q block of
    256 compute 33% of it (84 tiles of 256), one span of 2560 keys a 512
    rows 31%, one of 2304 a 256 rows 28%."""
    full = A.tile_census(8192, 8192, 256, 1024, True)
    assert full["dead"] == pytest.approx(112 / 256)
    assert full["edge"] / (1 - full["dead"]) == pytest.approx(32 / 144)
    seen = _sees(8192, 8192, 2048).mean()
    assert seen == pytest.approx(0.2188, abs=1e-3)
    blocks = A.tile_census(8192, 8192, 256, 1024, True, 2048)
    spans = A.tile_census(8192, 8192, 512, 2560, True, 2048)
    # (of the first eight q blocks' three steps one or two pass the diagonal)
    assert 1 - blocks["dead"] == pytest.approx(84 / 256)
    assert 1 - spans["dead"] == pytest.approx(2560 / 8192)
    dq = A.tile_census(8192, 8192, 256, 2304, True, 2048)
    assert 1 - dq["dead"] == pytest.approx(2304 / 8192)
    assert A.tile_census(64, 64, 16, 16, False) == {
        "dead": 0.0, "interior": 1.0, "edge": 0.0,
    }
    # the fused backward, a kv block's rows: 1024 x 1024 walks 36 of 64 tiles
    # without a window, eight of them on the diagonal; under the window two
    # spans of 1280 rows a 512 keys (the last kv block sees one), nearly all
    # of them cut by the diagonal or by the window's old side
    bwd = A.tile_census(8192, 8192, *A._flash2_blocks("bwd", 8192, 8192, None), True, side="q")
    assert 1 - bwd["dead"] == pytest.approx(36 / 64)
    assert bwd["edge"] / (1 - bwd["dead"]) == pytest.approx(8 / 36)
    windowed = A.tile_census(8192, 8192, *A._flash2_blocks("bwd", 8192, 8192, 2048), True, 2048, "q")
    assert 1 - windowed["dead"] == pytest.approx(30 * 1280 * 512 / 8192 ** 2)
    assert windowed["interior"] < 0.01 < seen < 1 - windowed["dead"]


@pytest.mark.parametrize("backward", BACKWARDS, indirect=True)
def test_one_attn_tiles_instant_a_traced_shape(backward):
    """Each flash2 wrapper notes its census once for a shape it is traced
    at: kernel, shapes, blocks, window, the three shares and the masked
    share of what is walked; the fused backward also the bytes of the dq
    accumulator it holds, and a call that fell back to dq and dk/dv theirs."""
    obs_trace.get_tracer().reset_notes()
    ring = obs_trace.get_tracer()
    seen = lambda: [  # noqa: E731
        e["args"] for e in ring.to_events()
        if e["name"] == "attn_tiles" and e["args"]["tq"] == 96
    ]
    before = len(seen())
    q, k, v, g = _inputs(2, 1, 96, 96, 8)
    for _ in range(2):  # the second trace of the shape notes nothing
        _kernels(q, k, v, g, (16, 32), (32, 32), (32, 48), 40)
    new = seen()[before:]
    want = {
        "fused": [("flash2_fwd", (16, 32), "kv"), ("flash2_bwd", (32, 48), "q")],
        "pair": [("flash2_fwd", (16, 32), "kv"), ("flash2_dq", (32, 32), "kv"),
                 ("flash2_dkv", (32, 48), "q")],
    }[backward]
    assert [a["kernel"] for a in new] == [name for name, _, _ in want]
    for args, (name, (bq, bk), side) in zip(new, want):
        census = A.tile_census(96, 96, bq, bk, True, 40, side)
        assert {key: args[key] for key in census} == census
        assert (args["block_q"], args["block_k"], args["window"]) == (bq, bk, 40)
        assert args["masked_share"] == pytest.approx(
            census["edge"] / (census["edge"] + census["interior"])
        )
        assert args.get("acc_bytes") == (96 * 8 * 4 if name == "flash2_bwd" else None)
