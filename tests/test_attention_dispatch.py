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

    def test_public_flash_entry_points_match_the_reference(self):
        """flash_attention / flash_with_lse at two blocks a side: value and
        lse against the reference through the grid-pipelined kernels."""
        A = importlib.import_module("edl_tpu.ops.attention")
        q, k, v = _qkv(t=128, d=8)
        out = A.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        ref = A.attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
        o2, lse = A.flash_with_lse(q, k, v, causal=True, block_q=64, block_k=64)
        _, lse_ref = A.attention_reference_with_lse(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(o2), np.asarray(ref), atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(lse_ref), atol=2e-4
        )


def _on_tpu(monkeypatch):
    """`attention()` as on the TPU, and every note it leaves: ``(A, notes)``.
    Nothing is lowered under it (``jax.eval_shape``, or the interpreter where
    `_interpret` is patched back)."""
    A = importlib.import_module("edl_tpu.ops.attention")
    notes = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        A.obs_trace.get_tracer(), "note_once",
        lambda name, **args: notes.append((name, args)),
    )
    return A, notes


FUSED = ("flash2_bwd",)
PAIR = ("flash2_dq", "flash2_dkv")
# What a call gets on the TPU: (q heads, kv heads, tq, tk, head_dim, window)
# -> the forward's blocks, and the backward's kernels with the blocks of the
# one that walks rows a kv block, or why the backward is the plain form's.
# The first seven are the attention calls of the benchmark's LM
# configurations (tests/test_tpu_compile.py compiles the same shapes); the
# rest are the edges of what the one family has to take, the shapes the
# whole-KV kernels served until PR 53 among them.
ROUTES = [
    pytest.param(32, 8, 4096, 4096, 128, None, (1024, 1024), (FUSED, (1024, 1024)),
                 id="mistral_7b"),
    pytest.param(16, 16, 4096, 4096, 128, None, (1024, 1024), (FUSED, (1024, 1024)),
                 id="olmoe_1b_7b"),
    pytest.param(32, 8, 8192, 8192, 64, None, (1024, 1024), (FUSED, (1024, 1024)),
                 id="granite_4_0_h_micro"),
    pytest.param(32, 4, 8192, 8192, 128, 2048, (512, 2560), (FUSED, (1280, 512)),
                 id="trinity_mini-window"),
    pytest.param(32, 4, 8192, 8192, 128, None, (1024, 1024), (FUSED, (1024, 1024)),
                 id="trinity_mini-full"),
    pytest.param(15, 15, 8192, 8192, 128, None, (1024, 1024), (FUSED, (1024, 1024)),
                 id="olmo_hybrid_7b"),
    pytest.param(32, 8, 8192, 8192, 64, None, (1024, 1024), (FUSED, (1024, 1024)),
                 id="lfm2_24b_a2b"),
    pytest.param(16, 16, 1024, 1024, 64, None, (1024, 1024), (FUSED, (1024, 1024)),
                 id="short"),
    pytest.param(16, 16, 2048, 2048, 64, None, (1024, 1024), (FUSED, (1024, 1024)),
                 id="chip_smoke-lm"),
    pytest.param(16, 16, 2049, 2049, 64, None, None, "lse", id="ragged"),
    pytest.param(16, 16, 4096, 4096, 64, None, (1024, 1024), (FUSED, (1024, 1024)),
                 id="4096"),
    pytest.param(16, 16, 4104, 4104, 64, None, (8, 8), (PAIR, (8, 8)),
                 id="4096+8"),
    pytest.param(16, 16, 1024, 8192, 64, None, (1024, 1024), (FUSED, (1024, 1024)),
                 id="short-q-long-kv"),
    pytest.param(16, 16, 8192, 1024, 64, None, None, "lse", id="long-q-short-kv"),
    pytest.param(16, 16, 1024, 4096, 64, None, (1024, 1024), (FUSED, (1024, 1024)),
                 id="short-q-kv-4096"),
    pytest.param(16, 16, 4096, 1024, 64, None, None, "lse",
                 id="more-rows-than-keys"),
    pytest.param(16, 4, 512, 512, 64, 512, (512, 512), (FUSED, (512, 512)),
                 id="window-512"),
    pytest.param(16, 4, 2048, 2048, 64, 2048, (512, 2048), (FUSED, (1024, 512)),
                 id="window-2048"),
    pytest.param(4, 4, 32, 32, 16, None, (32, 32), (FUSED, (32, 32)),
                 id="under-a-block"),
    pytest.param(1, 1, 32768, 32768, 256, None, (1024, 1024), (PAIR, (1024, 1024)),
                 id="dq-past-the-vmem"),
]


@pytest.mark.parametrize("h,h_kv,tq,tk,d,window,fwd,bwd", ROUTES)
def test_route(monkeypatch, h, h_kv, tq, tk, d, window, fwd, bwd):
    """What `attention()` gives a shape on the TPU, from the notes its value
    and gradient leave (traced, nothing lowered): the one family's forward
    and backward kernels with their blocks whatever the heads are, or the
    plain form and why."""
    A, notes = _on_tpu(monkeypatch)
    q = jax.ShapeDtypeStruct((1, h, tq, d), np.float32)
    k = jax.ShapeDtypeStruct((1, h_kv, tk, d), np.float32)
    jax.eval_shape(
        jax.grad(lambda q, k, v: A.attention(
            q, k, v, causal=True, window=window).sum(), argnums=(0, 1, 2)),
        q, k, k,
    )
    routes = [args for name, args in notes if name == "attn_route"]
    tiles = {args["kernel"]: (args["block_q"], args["block_k"])
             for name, args in notes if name == "attn_tiles"}
    assert routes[0] == {"tq": tq, "tk": tk, "window": window, "path": "kernel"}
    if fwd is None:  # the forward fell to the reference, so no lse was kept
        assert not tiles
        assert routes[1:] == [{
            "tq": tq, "tk": tk, "window": window, "side": "backward",
            "path": "plain", "why": bwd,
        }]
        return
    kernels, blocks = bwd
    assert routes[1:] == []
    assert tiles.pop("flash2_fwd") == fwd
    assert tuple(tiles) == kernels and tiles[kernels[-1]] == blocks


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("h,h_kv", [(2, 2), (4, 1)], ids=["mha", "gqa4"])
@pytest.mark.parametrize("t", [32, 128])
def test_attention_on_the_tpu_under_one_block_matches_the_reference(
    monkeypatch, t, h, h_kv, causal
):
    """`attention()` as the TPU runs it at a `tq` far under the measured
    blocks (the whole-KV kernels' until PR 53): the blocks shrink to the
    sequence, the kernels run (in the interpreter here), and value and
    gradients are the reference's."""
    import jax.numpy as jnp

    A, notes = _on_tpu(monkeypatch)
    monkeypatch.setattr(A, "_interpret", lambda: True)
    rng = np.random.RandomState(t + h)
    mk = lambda heads: jnp.asarray(rng.randn(2, heads, t, 8), jnp.float32)
    q, k, v, w = mk(h), mk(h_kv), mk(h_kv), mk(h)

    def value_and_grads(fn):
        out, vjp = jax.vjp(lambda q, k, v: fn(q, k, v, causal=causal), q, k, v)
        return (out, *vjp(w))

    got, want = value_and_grads(A.attention), value_and_grads(attention_reference)
    assert [args["kernel"] for name, args in notes if name == "attn_tiles"] == [
        "flash2_fwd", "flash2_bwd"
    ]
    assert [args["path"] for name, args in notes if name == "attn_route"] == ["kernel"]
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


@pytest.mark.parametrize("h,h_kv", [(2, 2), (4, 2)], ids=["mha", "gqa2"])
def test_a_ring_shards_calls_merge_to_the_whole_sequences_attention(h, h_kv):
    """`flash_with_lse` and `flash_block_grads` as `parallel/ring.py` calls
    them at a shard's shape (the whole-KV kernels' until PR 53): the second
    of two shards of 64 rows sees the first shard's keys in full and its
    own under the causal mask; merged by lse the two are the whole
    sequence's attention, and the block gradients under the global lse and
    delta are its gradients."""
    import jax.numpy as jnp

    A = importlib.import_module("edl_tpu.ops.attention")
    t = 64
    rng = np.random.RandomState(h)
    mk = lambda heads: jnp.asarray(rng.randn(1, heads, 2 * t, 16), jnp.float32)
    q, k, v, w = mk(h), mk(h_kv), mk(h_kv), mk(h)
    want, vjp = jax.vjp(
        lambda q, k, v: attention_reference(q, k, v, causal=True), q, k, v
    )
    want_dq, want_dk, want_dv = vjp(w)
    q1, w1 = q[:, :, t:], w[:, :, t:]
    shards = [(k[:, :, :t], v[:, :, :t], False), (k[:, :, t:], v[:, :, t:], True)]
    outs, lses = zip(*(
        A.flash_with_lse(q1, k_s, v_s, causal=causal) for k_s, v_s, causal in shards
    ))
    lse = jnp.logaddexp(*lses)
    out = sum(o * jnp.exp(l - lse)[..., None] for o, l in zip(outs, lses))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want[:, :, t:]), atol=2e-4)
    delta = jnp.sum(w1 * out, axis=-1)
    grads = [
        A.flash_block_grads(q1, k_s, v_s, w1, lse, delta, causal=causal)
        for k_s, v_s, causal in shards
    ]
    dq = grads[0][0] + grads[1][0]
    np.testing.assert_allclose(np.asarray(dq), np.asarray(want_dq[:, :, t:]), atol=3e-4)
    # the second shard's keys are seen by its own rows alone
    for got, whole in ((grads[1][1], want_dk), (grads[1][2], want_dv)):
        assert got.shape == whole[:, :, t:].shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(whole[:, :, t:]), atol=3e-4)


@pytest.mark.parametrize("h,h_kv,d", [(32, 8, 128), (16, 16, 128)],
                         ids=["mistral_7b", "olmoe_1b_7b"])
@pytest.mark.parametrize("given", [(None, None), (256, 512)],
                         ids=["measured-blocks", "explicit-blocks"])
def test_flash_attention_hands_on_the_callers_blocks(monkeypatch, h, h_kv, d, given):
    """`flash_attention` is `attention()`'s kernels (the benchmark's kernel
    check calls it at the cell's shape, so it checks the kernels the cell's
    step runs), and a caller's blocks reach the forward and the backward
    alike."""
    A = importlib.import_module("edl_tpu.ops.attention")
    seen = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(A, "_auto", lambda *a: seen.append(a[5:]))
    q = jax.ShapeDtypeStruct((2, h, 4096, d), np.float32)
    k = jax.ShapeDtypeStruct((2, h_kv, 4096, d), np.float32)
    A.flash_attention(q, k, k, causal=True, block_q=given[0], block_k=given[1])
    assert seen == [(given, given, None, None)]     # ..., window, block_diffusion
    seen.clear()
    A.attention(q, k, k, causal=True)
    assert seen == [(None, None, None, None)]
    seen.clear()
    wide = jax.ShapeDtypeStruct((2, h, 8192, d), np.float32)
    narrow = jax.ShapeDtypeStruct((2, h_kv, 8192, d), np.float32)
    A.attention(wide, narrow, narrow, causal=True, block_diffusion=[4096, 4])
    assert seen == [(None, None, None, (4096, 4))]            # hashable: it rides nondiff_argnums


def test_fused_backward_at_the_blocks_of_a_4096_call_matches_the_reference(monkeypatch):
    """Value and q/k/v gradients of the forward and the fused backward, in
    the interpreter at GQA 4:1 with the blocks `_flash2_blocks` gives a T =
    4096 call, against `attention_reference` (half the length, so the dense
    scores stay small: the blocks divide it)."""
    import jax.numpy as jnp

    A = importlib.import_module("edl_tpu.ops.attention")
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
