"""Fast routing units for ops.attention.attention (no compile-heavy kernel
work — the composition numerics live in test_attention.py)."""

import importlib

import jax
import numpy as np
import pytest

from edl_tpu.ops.attention import attention, attention_reference


def _qkv(b=2, h=2, t=24, d=8, seed=0):
    rng = np.random.RandomState(seed)
    import jax.numpy as jnp
    mk = lambda: jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    return mk(), mk(), mk()


class TestDispatchFast:
    def test_entry_point_off_tpu_is_reference(self):
        q, k, v = _qkv(t=24)  # 24 is even ragged-ish; fine for dense
        out = attention(q, k, v, causal=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_flash_compile_guard_remaps_long_seq_to_flash2(self):
        A = importlib.import_module("edl_tpu.ops.attention")
        limit = A._WHOLE_KV_MAX_SEQ
        # within the compile limit the whole-KV family could serve, and since
        # PR 48 no call past the forward crossover asks it to; past the limit
        # on either side it cannot, and both directions are flash2's
        assert A._whole_kv_serves(limit, limit)
        assert A._route(limit, limit, False) == ("flash2", "flash2")
        for tq, tk in ((2 * limit, 2 * limit), (64, limit + 1), (limit + 1, 64)):
            assert not A._whole_kv_serves(tq, tk)
            assert A._route(tq, tk, False) == ("flash2", "flash2")

    def test_public_flash_entry_points_reroute_past_compile_limit(
        self, monkeypatch
    ):
        """flash_attention/flash_with_lse must not build the whole-KV
        kernel past the flash compile limit (it crashes the TPU
        compiler); with the limit shrunk, both must still match the
        reference through the grid-pipelined route."""
        A = importlib.import_module("edl_tpu.ops.attention")
        monkeypatch.setattr(A, "_WHOLE_KV_MAX_SEQ", 64)
        q, k, v = _qkv(t=128, d=8)
        out = A.flash_attention(q, k, v, causal=True)
        ref = A.attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
        o2, lse = A.flash_with_lse(q, k, v, causal=True)
        _, lse_ref = A.attention_reference_with_lse(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(o2), np.asarray(ref), atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(lse_ref), atol=2e-4
        )

    def test_kernel_blocks_table(self):
        A = importlib.import_module("edl_tpu.ops.attention")
        assert A._kernel_blocks(1024) == ((256, 512), (256, 512))
        assert A._kernel_blocks(2048) == ((512, 512), (256, 512))
        assert A._kernel_blocks(4096) == ((128, 512), (512, 512))
        assert A._kernel_blocks(65536) == ((128, 512), (512, 512))


# What `_route` gives a call on the TPU: (q heads, kv heads, tq, tk, head_dim,
# window) -> (forward, backward). The first seven are the attention calls of
# the benchmark's LM configurations (tests/test_tpu_compile.py compiles the
# same shapes), with the kernels PERF.md section 4 says those cells run; the
# rest are the rule's edges.
ROUTES = [
    pytest.param(32, 8, 4096, 4096, 128, None, ("flash2", "flash2"), id="mistral_7b"),
    pytest.param(16, 16, 4096, 4096, 128, None, ("flash2", "flash2"), id="olmoe_1b_7b"),
    pytest.param(32, 8, 8192, 8192, 64, None, ("flash2", "flash2"),
                 id="granite_4_0_h_micro"),
    pytest.param(32, 4, 8192, 8192, 128, 2048, ("flash2", "flash2"),
                 id="trinity_mini-window"),
    pytest.param(32, 4, 8192, 8192, 128, None, ("flash2", "flash2"),
                 id="trinity_mini-full"),
    pytest.param(15, 15, 8192, 8192, 128, None, ("flash2", "flash2"),
                 id="olmo_hybrid_7b"),
    pytest.param(32, 8, 8192, 8192, 64, None, ("flash2", "flash2"), id="lfm2_24b_a2b"),
    pytest.param(16, 16, 1024, 1024, 64, None, ("flash", "flash"), id="short"),
    pytest.param(16, 16, 2048, 2048, 64, None, ("flash", "flash"),
                 id="fwd-crossover"),  # chip_smoke's lm phase
    pytest.param(16, 16, 2049, 2049, 64, None, ("flash2", "flash2"),
                 id="fwd-crossover+1"),
    pytest.param(16, 16, 4096, 4096, 64, None, ("flash2", "flash2"), id="compile-limit"),
    pytest.param(16, 16, 4097, 4097, 64, None, ("flash2", "flash2"),
                 id="compile-limit+1"),
    pytest.param(16, 16, 1024, 8192, 64, None, ("flash2", "flash2"),
                 id="short-q-long-kv"),
    pytest.param(16, 16, 8192, 1024, 64, None, ("flash2", "flash2"),
                 id="long-q-short-kv"),
    pytest.param(16, 16, 1024, 4096, 64, None, ("flash", "flash"),
                 id="short-q-kv-at-limit"),
    pytest.param(16, 16, 4096, 1024, 64, None, ("flash2", "flash2"),
                 id="q-past-crossover-short-kv"),
    pytest.param(16, 4, 512, 512, 64, 512, ("flash2", "flash2"), id="window-512"),
    pytest.param(16, 4, 2048, 2048, 64, 2048, ("flash2", "flash2"),
                 id="window-at-crossover"),
]


@pytest.mark.parametrize("h,h_kv,tq,tk,d,window,want", ROUTES)
def test_route(monkeypatch, h, h_kv, tq, tk, d, window, want):
    """The routing function's answer, and that `attention()` on the TPU
    hands exactly that answer to `_auto` whatever the heads are."""
    A = importlib.import_module("edl_tpu.ops.attention")
    assert A._route(tq, tk, window is not None) == want
    assert ("flash" in want) <= A._whole_kv_serves(tq, tk, window is not None)
    seen = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(A, "_auto", lambda *a: seen.append(a[5:]))
    q = jax.ShapeDtypeStruct((1, h, tq, d), np.float32)
    k = jax.ShapeDtypeStruct((1, h_kv, tk, d), np.float32)
    A.attention(q, k, k, causal=True, window=window)
    assert seen == [want + (None, None, window)]


@pytest.mark.parametrize("h,h_kv,d", [(32, 8, 128), (16, 16, 128)],
                         ids=["mistral_7b", "olmoe_1b_7b"])
@pytest.mark.parametrize("given", [(None, None), (256, 512)],
                         ids=["measured-blocks", "explicit-blocks"])
def test_flash_attention_asks_the_route(monkeypatch, h, h_kv, d, given):
    """`flash_attention` names no family itself: at T = 4096 on the TPU it
    hands `_auto` what `_route` says (the benchmark's kernel check calls it
    at the cell's shape, so it checks the kernels the cell's step runs), and
    a caller's blocks reach the forward and the backward alike."""
    A = importlib.import_module("edl_tpu.ops.attention")
    seen = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(A, "_auto", lambda *a: seen.append(a[5:]))
    q = jax.ShapeDtypeStruct((2, h, 4096, d), np.float32)
    k = jax.ShapeDtypeStruct((2, h_kv, 4096, d), np.float32)
    A.flash_attention(q, k, k, causal=True, block_q=given[0], block_k=given[1])
    assert seen == [A._route(4096, 4096, False) + (given, given, None)]
    assert seen[0][:2] == ("flash2", "flash2")


def test_whole_kv_blocks_fill_what_the_caller_left_out():
    A = importlib.import_module("edl_tpu.ops.attention")
    assert A._whole_kv_blocks("fwd", 2048) == (512, 512)
    assert A._whole_kv_blocks("bwd", 2048, (None, None)) == (256, 512)
    assert A._whole_kv_blocks("fwd", 2048, (128, None)) == (128, 512)
    assert A._whole_kv_blocks("bwd", 1024, (None, 256)) == (256, 256)


def test_fused_backward_at_the_blocks_of_a_4096_call_matches_the_reference(monkeypatch):
    """Value and q/k/v gradients of the `flash2` pair `_route` gives a T =
    4096 call (the forward, the fused backward), in the interpreter at GQA
    4:1 with the blocks `_flash2_blocks` gives that call, against
    `attention_reference` (half the length, so the dense scores stay small:
    the blocks divide it)."""
    import jax.numpy as jnp

    A = importlib.import_module("edl_tpu.ops.attention")
    assert A._route(4096, 4096, False) == ("flash2", "flash2")
    fwd, dq, dkv = (
        A._flash2_blocks(kind, 4096, 4096, None) for kind in ("fwd", "dq", "bwd")
    )
    b, h, h_kv, t, d = 1, 4, 1, 2048, 32
    assert all(t % blk == 0 for blk in fwd + dq + dkv)
    rng = np.random.RandomState(48)
    mk = lambda heads: jnp.asarray(rng.randn(b, heads, t, d), jnp.float32)
    q, k, v, w = mk(h), mk(h_kv), mk(h_kv), mk(h)
    scale = d ** -0.5

    out, lse = A._flash2_forward(q, k, v, True, scale, *fwd, True)
    noted = []
    monkeypatch.setattr(
        A.obs_trace.get_tracer(), "note_once",
        lambda name, **args: noted.append(args.get("kernel")),
    )
    grads = A._flash2_backward(
        q, k, v, out, lse, w, True, scale, *dq, True, None, dkv
    )
    assert noted == ["flash2_bwd"]  # the fused kernel, not the pair
    ref, vjp = jax.vjp(
        lambda q, k, v: attention_reference(q, k, v, causal=True, scale=scale),
        q, k, v,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
    for got, want in zip(grads, vjp(w)):
        assert got.shape == want.shape
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=5e-4, rtol=1e-3
        )


# The forward calls of the benchmark's eight LM cells: (tq, head width of q
# and k, of v, window). Keye's is the masked copy in ops/sparse_attention.py.
CELL_FORWARDS = [
    pytest.param(4096, 128, 128, None, id="mistral_7b"),
    pytest.param(4096, 128, 128, None, id="olmoe_1b_7b"),
    pytest.param(8192, 64, 64, None, id="granite_4_0_h_micro"),
    pytest.param(8192, 64, 64, None, id="lfm2_24b_a2b"),
    pytest.param(8192, 128, 128, None, id="trinity_mini-full"),
    pytest.param(8192, 128, 128, 2048, id="trinity_mini-window"),
    pytest.param(8192, 128, 128, None, id="olmo_hybrid_7b"),
    pytest.param(8192, 192, 128, None, id="ling_3_0_flash_vl"),
    pytest.param(16384, 128, 128, "mask", id="keye_vl_2_0_30b_a3b"),
]


@pytest.mark.parametrize("t,d,d_v,window", CELL_FORWARDS)
def test_a_cells_forward_blocks_tile_its_call(t, d, d_v, window):
    """Every cell's forward blocks divide its `tq` / `tk` (a window's span of
    keys starts at an element and fits the side), a full-causal call takes
    the forward sweep's entry, and Keye's int8 mask tile stays 32 sublanes by
    whole lane tiles."""
    A = importlib.import_module("edl_tpu.ops.attention")
    if window == "mask":
        S = importlib.import_module("edl_tpu.ops.sparse_attention")
        (bq, bk), _ = S._attention_blocks(t, d, 2)
        assert bq % 32 == 0 and bk % 128 == 0
        window = None
    else:
        bq, bk = A._flash2_blocks("fwd", t, t, window)
    assert A._spans_fit(bq, bk, t, t, window, "kv")
    assert t % bq == 0 and bq % 8 == 0 and bk % 128 == 0
    if window is None:
        assert (bq, bk) == A._FLASH2_BLOCKS_FWD and t % bk == 0
