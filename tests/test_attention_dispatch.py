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
        # within the compile limit the whole-KV family serves; past it on
        # either side it does not, and both directions are flash2's
        assert A._whole_kv_serves(limit, limit)
        assert A._route(limit, limit, False)[1] == "flash"
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
    pytest.param(32, 8, 4096, 4096, 128, None, ("flash2", "flash"), id="mistral_7b"),
    pytest.param(16, 16, 4096, 4096, 128, None, ("flash2", "flash"), id="olmoe_1b_7b"),
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
    pytest.param(16, 16, 2049, 2049, 64, None, ("flash2", "flash"),
                 id="fwd-crossover+1"),
    pytest.param(16, 16, 4096, 4096, 64, None, ("flash2", "flash"), id="compile-limit"),
    pytest.param(16, 16, 4097, 4097, 64, None, ("flash2", "flash2"),
                 id="compile-limit+1"),
    pytest.param(16, 16, 1024, 8192, 64, None, ("flash2", "flash2"),
                 id="short-q-long-kv"),
    pytest.param(16, 16, 8192, 1024, 64, None, ("flash2", "flash2"),
                 id="long-q-short-kv"),
    pytest.param(16, 16, 1024, 4096, 64, None, ("flash", "flash"),
                 id="short-q-kv-at-limit"),
    pytest.param(16, 16, 4096, 1024, 64, None, ("flash2", "flash"),
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
