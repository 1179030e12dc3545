"""Attention ops + ring/sequence parallelism + Transformer tests.

Ring attention is validated against dense reference attention on the
8-virtual-device CPU mesh; the Pallas flash kernel runs in interpret mode
on CPU (compiled on real TPU).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from edl_tpu.models import TransformerLM
from edl_tpu.ops import attention_reference, flash_attention
from edl_tpu.parallel import (
    TRANSFORMER_TP_RULES,
    make_mesh,
    ring_attention_sharded,
    shard_batch,
    shard_params_by_rules,
    ulysses_attention_sharded,
)
from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

pytestmark = pytest.mark.slow  # compile-heavy / multi-process integration



def _qkv(b=2, h=2, t=32, d=8, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    return mk(), mk(), mk()


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        ref = attention_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_grad_flows(self):
        q, k, v = _qkv(t=16)

        def loss(q, k, v):
            return flash_attention(
                q, k, v, causal=True, block_q=8, block_k=8
            ).sum()

        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        ref_grads = jax.grad(
            lambda q, k, v: attention_reference(q, k, v, causal=True).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for g, r in zip(grads, ref_grads):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_cross_length_matches_reference(self, causal):
        """tq != tk (e.g. decode chunks against a longer KV cache): the
        kernel's causal mask must align sequence *ends* like the reference
        (qpos = arange(tq) + (tk - tq)), and forward/backward must agree."""
        rng = np.random.RandomState(3)
        b, h, tq, tk, d = 2, 2, 16, 48, 8
        q = jnp.asarray(rng.randn(b, h, tq, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, h, tk, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, h, tk, d), jnp.float32)
        ref = attention_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

        # gradients: the custom_vjp backward recomputes with the reference,
        # so any forward-mask mismatch shows up as fwd/bwd inconsistency
        g, gr = (
            jax.grad(lambda a: fn(a, k, v).sum())(q)
            for fn in (
                lambda a, k, v: flash_attention(
                    a, k, v, causal=causal, block_q=8, block_k=8
                ),
                lambda a, k, v: attention_reference(a, k, v, causal=causal),
            )
        )
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=2e-4)

    def test_ragged_fallback(self):
        q, k, v = _qkv(t=10)  # not divisible by blocks
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
        # the fallback's backward must be the reference's too
        g = jax.grad(
            lambda a: flash_attention(
                a, k, v, causal=True, block_q=16, block_k=16
            ).sum()
        )(q)
        gr = jax.grad(
            lambda a: attention_reference(a, k, v, causal=True).sum()
        )(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_more_queries_than_keys(self, causal):
        """tq > tk: causal end-alignment leaves early q rows fully masked
        (reference: uniform softmax); the kernel routes causal to the
        reference fallback rather than diverge silently."""
        rng = np.random.RandomState(11)
        b, h, tq, tk, d = 2, 2, 32, 16, 8
        q = jnp.asarray(rng.randn(b, h, tq, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, h, tk, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, h, tk, d), jnp.float32)
        ref = attention_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
        g = jax.grad(
            lambda a: flash_attention(
                a, k, v, causal=causal, block_q=16, block_k=16
            ).sum()
        )(q)
        gr = jax.grad(
            lambda a: attention_reference(a, k, v, causal=causal).sum()
        )(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_backward_full_grads(self, causal):
        """dq, dk AND dv from the Pallas backward kernels vs the reference
        VJP, on a cross-length shape whose block_k must divisor-shrink
        (tk=48 with block_k=32 -> 16) and with a weighted loss so any
        transposition bug shows."""
        rng = np.random.RandomState(7)
        b, h, tq, tk, d = 2, 3, 16, 48, 8
        q = jnp.asarray(rng.randn(b, h, tq, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, h, tk, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, h, tk, d), jnp.float32)
        w = jnp.asarray(rng.randn(b, h, tq, d), jnp.float32)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) * w).sum()

        flash = loss(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, block_q=8, block_k=32
            )
        )
        ref = loss(
            lambda q, k, v: attention_reference(q, k, v, causal=causal)
        )
        got = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b_ in zip("q k v".split(), got, want):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=3e-4, rtol=1e-3,
                err_msg="d%s" % name,
            )

    def test_pallas_backward_bf16(self):
        rng = np.random.RandomState(9)
        b, h, t, d = 2, 2, 64, 16
        mk = lambda: jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
        q, k, v = mk(), mk(), mk()
        got = jax.grad(
            lambda q: flash_attention(
                q, k, v, causal=True, block_q=32, block_k=32
            ).astype(jnp.float32).sum(),
        )(q)
        want = jax.grad(
            lambda q: attention_reference(q, k, v, causal=True)
            .astype(jnp.float32).sum(),
        )(q)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=0.15, rtol=0.1,
        )


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        mesh = make_mesh({"dp": 2, "sp": 4})
        q, k, v = _qkv(b=2, h=2, t=64, d=8)
        ref = attention_reference(q, k, v, causal=causal)

        out = jax.jit(
            lambda q, k, v: ring_attention_sharded(
                q, k, v, mesh, causal=causal
            )
        )(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=3e-5
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_full_grads_match_dense(self, causal):
        """The ring's custom VJP (blockwise backward kernels + rotating
        dk/dv accumulators) vs the dense reference VJP, weighted loss."""
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        mesh = make_mesh({"dp": 2, "sp": 4})
        rng = np.random.RandomState(8)
        b, h, t, d = 2, 2, 64, 8
        mk = lambda: jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
        q, k, v = mk(), mk(), mk()
        w = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
        got = jax.grad(
            lambda q, k, v: (
                ring_attention_sharded(q, k, v, mesh, causal=causal) * w
            ).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        want = jax.grad(
            lambda q, k, v: (
                attention_reference(q, k, v, causal=causal) * w
            ).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for name, a, b_ in zip("qkv", got, want):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=3e-4, rtol=1e-3,
                err_msg="d%s causal=%s" % (name, causal),
            )

    def test_sp1_uses_flash(self):
        mesh = make_mesh({"dp": 1, "sp": 1}, devices=jax.devices()[:1])
        q, k, v = _qkv(t=16)
        out = ring_attention_sharded(q, k, v, mesh, causal=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _tiny_lm(**kw):
    return TransformerLM(
        vocab_size=64, d_model=32, num_heads=4, num_layers=2, d_ff=64,
        dtype=jnp.float32, **kw,
    )


class TestTransformerLM:
    def test_forward_shapes(self):
        model = _tiny_lm()
        tokens = jnp.zeros((2, 16), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        logits = model.apply({"params": params}, tokens)
        assert logits.shape == (2, 16, 64)

    def test_remat_matches(self):
        tokens = jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % 64
        model = _tiny_lm()
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        plain = model.apply({"params": params}, tokens)
        rematted = _tiny_lm(remat=True).apply({"params": params}, tokens)
        np.testing.assert_allclose(
            np.asarray(plain), np.asarray(rematted), atol=1e-5
        )

    def test_tp_sharded_training_matches_single(self):
        """One train step with Megatron-style tp sharding == unsharded."""
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, 64, (4, 16)), jnp.int32
        )
        labels = jnp.roll(tokens, -1, axis=1)
        model = _tiny_lm()
        state = create_state(
            model,
            jax.random.PRNGKey(1),
            tokens,
            optax.sgd(0.1),
        )
        loss_head = lambda logits, y: cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), y.reshape(-1)
        )
        step = make_train_step(loss_head, donate=False)
        plain, m_plain = step(state, (tokens, labels))

        mesh = make_mesh({"dp": 2, "tp": 4})
        sharded = state.replace(
            params=shard_params_by_rules(
                mesh, state.params, TRANSFORMER_TP_RULES
            )
        )
        with mesh:
            batch = shard_batch(mesh, (tokens, labels))
            out, m_shard = step(sharded, batch)
        np.testing.assert_allclose(
            float(m_plain["loss"]), float(m_shard["loss"]), rtol=1e-5
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5
            ),
            plain.params,
            out.params,
        )


class TestUlyssesAttention:
    """All-to-all sequence parallelism vs dense reference (and vs ring)."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        rng = np.random.RandomState(5)
        b, h, t, d = 2, 8, 64, 8  # sp=4 needs h % 4 == 0
        mk = lambda: jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
        q, k, v = mk(), mk(), mk()
        want = attention_reference(q, k, v, causal=causal)
        mesh = make_mesh({"dp": 2, "sp": 4})
        got = jax.jit(
            lambda q, k, v: ulysses_attention_sharded(
                q, k, v, mesh, causal=causal
            )
        )(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4
        )

    def test_grads_match_dense(self):
        rng = np.random.RandomState(6)
        b, h, t, d = 2, 4, 32, 8
        mk = lambda: jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
        q, k, v = mk(), mk(), mk()
        w = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
        mesh = make_mesh({"dp": 2, "sp": 4})
        got = jax.grad(
            lambda q, k, v: (
                ulysses_attention_sharded(q, k, v, mesh, causal=True) * w
            ).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        want = jax.grad(
            lambda q, k, v: (
                attention_reference(q, k, v, causal=True) * w
            ).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b_ in zip(got, want):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=3e-4, rtol=1e-3
            )

    def test_sp1_passthrough_and_head_divisibility(self):
        q, k, v = _qkv(t=32)
        mesh1 = make_mesh({"dp": 1, "sp": 1}, devices=jax.devices()[:1])
        out = ulysses_attention_sharded(q, k, v, mesh1, causal=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
        # h=2 not divisible by sp=4: a clear error, not silent corruption
        mesh = make_mesh({"dp": 2, "sp": 4})
        with pytest.raises(ValueError, match="heads"):
            jax.jit(
                lambda q, k, v: ulysses_attention_sharded(q, k, v, mesh)
            )(q, k, v)

    def test_in_transformer_lm(self):
        """The model TRAINS with ulysses as its attention_fn on a dp x sp
        mesh: one optimizer step whose loss and updated params match the
        same model stepped with dense attention."""
        import functools

        mesh = make_mesh({"dp": 2, "sp": 4})
        attn = functools.partial(
            ulysses_attention_sharded, mesh=mesh, sp_axis="sp"
        )
        lm_u = tiny_lm_attn(attn)
        lm_d = tiny_lm_attn(attention_reference)
        tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 32), 0, 64)
        lm_loss = lambda logits, y: cross_entropy_loss(
            logits.reshape(-1, logits.shape[-1]), y.reshape(-1)
        )
        step = make_train_step(lm_loss, donate=False)
        results = {}
        for name, lm in (("ulysses", lm_u), ("dense", lm_d)):
            state = create_state(
                lm, jax.random.PRNGKey(1), tokens, optax.sgd(0.1)
            )
            with mesh:
                state, metrics = step(state, (tokens, tokens))
            assert int(state.step) == 1
            results[name] = (float(metrics["loss"]), state.params)
        assert abs(results["ulysses"][0] - results["dense"][0]) < 1e-4
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-3
            ),
            results["ulysses"][1],
            results["dense"][1],
        )


def tiny_lm_attn(attn_fn):
    return TransformerLM(
        vocab_size=64, d_model=32, num_heads=4, num_layers=2, d_ff=64,
        dtype=jnp.float32, attention_fn=attn_fn,
    )


class TestDispatchedAttention:
    """What the routed entry point (ops.attention.attention) runs on the
    TPU, `_auto`: the kernels' backward, and the reference's vjp a backward
    falls back to where its blocks do not tile the shape (the forward's lse
    is then unused), must match the dense reference in values AND grads."""

    @pytest.mark.parametrize("bwd_blocks", [None, (24, 24)], ids=["kernel", "plain"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_value_and_grads_match_reference(self, bwd_blocks, causal):
        import importlib

        A = importlib.import_module("edl_tpu.ops.attention")
        q, k, v = _qkv(t=32)
        scale = q.shape[-1] ** -0.5
        assert A._spans_fit(
            *A._flash2_blocks("bwd", 32, 32, None, bwd_blocks), 32, 32, None, "q"
        ) == (bwd_blocks is None)
        auto = lambda q, k, v: A._auto(q, k, v, causal, scale, None, bwd_blocks)
        out = auto(q, k, v)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

        grads = jax.grad(
            lambda q, k, v: auto(q, k, v).sum(), argnums=(0, 1, 2),
        )(q, k, v)
        ref_grads = jax.grad(
            lambda q, k, v: attention_reference(q, k, v, causal=causal).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for g, r in zip(grads, ref_grads):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-4)


class TestFlash2:
    """Grid-pipelined forward: the KV walk lives in the grid, so the
    online-softmax carry (m/l/acc in VMEM scratch) crosses grid steps —
    these force num_k > 1 to exercise exactly that machinery."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_multi_kv_block_carry_matches_reference(self, causal):
        from edl_tpu.ops.attention import (
            _flash2_forward, attention_reference_with_lse,
        )

        q, k, v = _qkv(t=64, d=16, seed=5)
        scale = q.shape[-1] ** -0.5
        # block_k=16 over t=64 -> num_k=4: init/update/correction/finalize
        # all cross grid steps; causal additionally hits dead-tile skips
        o2, lse2 = _flash2_forward(q, k, v, causal, scale, 16, 16, True)
        oref, lseref = attention_reference_with_lse(
            q, k, v, causal=causal, scale=scale
        )
        b, h, t, _ = q.shape
        np.testing.assert_allclose(np.asarray(o2), np.asarray(oref), atol=3e-5)
        np.testing.assert_allclose(
            np.asarray(lse2).reshape(b, h, t), np.asarray(lseref), atol=3e-5
        )

    def test_cross_length_causal_end_aligned(self):
        from edl_tpu.ops.attention import (
            _flash2_forward, attention_reference,
        )

        rng = np.random.RandomState(9)
        q = jnp.asarray(rng.randn(1, 2, 32, 8), jnp.float32)
        k = jnp.asarray(rng.randn(1, 2, 64, 8), jnp.float32)
        v = jnp.asarray(rng.randn(1, 2, 64, 8), jnp.float32)
        o2, lse = _flash2_forward(q, k, v, True, 8 ** -0.5, 16, 16, True)
        assert lse is not None
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(o2), np.asarray(ref), atol=3e-5)

    def test_ragged_falls_back_dense(self):
        from edl_tpu.ops.attention import _flash2_forward

        o, lse = _flash2_forward(
            jnp.ones((1, 1, 32, 8)), jnp.ones((1, 1, 16, 8)),
            jnp.ones((1, 1, 16, 8)), True, 8 ** -0.5, 16, 16, True,
        )
        assert lse is None and o.shape == (1, 1, 32, 8)

    @pytest.mark.parametrize("backward", ["fused", "pair"])
    def test_flash2_backward_multi_block_grads(self, monkeypatch, backward):
        """Force num_k > 1 AND num_q > 1 through the grid-pipelined
        backward (one fused kernel; dq and dk/dv where no VMEM holds a
        head's dq): the scratch accumulation across grid steps is the
        machinery under test (the _auto tests run at one block)."""
        import importlib

        from edl_tpu.ops.attention import (
            _flash2_backward, _flash2_forward, attention_reference,
        )

        if backward == "pair":
            monkeypatch.setattr(
                importlib.import_module("edl_tpu.ops.attention"),
                "_vmem_capacity", lambda: 0,
            )

        rng = np.random.RandomState(11)
        q = jnp.asarray(rng.randn(2, 2, 64, 16), jnp.float32)
        k = jnp.asarray(rng.randn(2, 2, 64, 16), jnp.float32)
        v = jnp.asarray(rng.randn(2, 2, 64, 16), jnp.float32)
        g = jnp.asarray(rng.randn(2, 2, 64, 16), jnp.float32)
        scale = 16 ** -0.5
        for causal in (False, True):
            o, lse = _flash2_forward(q, k, v, causal, scale, 16, 16, True)
            dq, dk, dv = _flash2_backward(
                q, k, v, o.reshape(4, 64, 16), lse, g, causal, scale,
                16, 16, True,
            )
            _, vjp = jax.vjp(
                lambda q, k, v: attention_reference(
                    q, k, v, causal=causal, scale=scale
                ), q, k, v,
            )
            for got, want in zip((dq, dk, dv), vjp(g)):
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), atol=3e-4
                )


class TestGQAKernels:
    """GQA-aware kernel paths: grouped k/v consumed directly (no repeat),
    fwd AND dk/dv-at-grouped-width backward, vs the broadcast dense
    reference."""

    def _mk(self, h, h_kv, t=256, b=2, d=32, dtype=jnp.float32, tk=None):
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(b, h, t, d), dtype)
        k = jnp.asarray(rng.randn(b, h_kv, tk or t, d), dtype)
        v = jnp.asarray(rng.randn(b, h_kv, tk or t, d), dtype)
        w = jnp.asarray(rng.randn(b, h, t, d), dtype)
        return q, k, v, w

    def _want(self, q, k, v, w, causal):
        g = q.shape[1] // k.shape[1]
        kk, vv = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        def f(q, kk, vv):
            return (attention_reference(q, kk, vv, causal=causal) * w).sum()
        val, vjp = jax.value_and_grad(f, argnums=(0, 1, 2))(q, kk, vv)
        dq, dk_full, dv_full = vjp
        b, h, tk, d = kk.shape[0], kk.shape[1], kk.shape[2], kk.shape[3]
        h_kv = k.shape[1]
        dk = dk_full.reshape(b, h_kv, g, tk, d).sum(2)
        dv = dv_full.reshape(b, h_kv, g, tk, d).sum(2)
        return val, dq, dk, dv

    @pytest.mark.parametrize("h,h_kv", [(4, 2), (4, 1)])
    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_grouped_matches_broadcast_reference(self, h, h_kv, causal):
        q, k, v, w = self._mk(h, h_kv)
        want_val, want_dq, want_dk, want_dv = self._want(q, k, v, w, causal)

        def f(q, k, v):
            return (flash_attention(q, k, v, causal=causal) * w).sum()

        got_val, (dq, dk, dv) = jax.value_and_grad(f, argnums=(0, 1, 2))(
            q, k, v
        )
        assert dk.shape == k.shape and dv.shape == v.shape
        # the value is a sum over 65k elements: block-skip accumulation
        # order shifts the total a few ulp beyond 1e-5
        np.testing.assert_allclose(float(got_val), float(want_val), rtol=2e-4)
        for a, b_ in ((dq, want_dq), (dk, want_dk), (dv, want_dv)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=3e-4, rtol=1e-3
            )

    def test_flash2_grouped_multi_block(self):
        # several blocks a side (the measured ones are one block at t = 256):
        # the i // g index maps under the grid's walk
        q, k, v, w = self._mk(4, 2)
        want_val, want_dq, want_dk, want_dv = self._want(q, k, v, w, True)

        def f(q, k, v):
            out = flash_attention(q, k, v, causal=True, block_q=64, block_k=128)
            return (out * w).sum()

        got_val, (dq, dk, dv) = jax.value_and_grad(f, argnums=(0, 1, 2))(
            q, k, v
        )
        assert dk.shape == k.shape
        np.testing.assert_allclose(float(got_val), float(want_val), rtol=2e-4)
        for a, b_ in ((dq, want_dq), (dk, want_dk), (dv, want_dv)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=3e-4, rtol=1e-3
            )

    def test_cross_length_grouped(self):
        """tq != tk with grouped k/v: the end-aligned causal offset must
        compose with the i // g index maps."""
        q, k, v, w = self._mk(4, 2, t=64, tk=256)
        want_val, want_dq, want_dk, want_dv = self._want(q, k, v, w, True)

        def f(q, k, v):
            return (flash_attention(q, k, v, causal=True) * w).sum()

        got_val, (dq, dk, dv) = jax.value_and_grad(f, argnums=(0, 1, 2))(
            q, k, v
        )
        assert dk.shape == k.shape
        np.testing.assert_allclose(float(got_val), float(want_val), rtol=2e-4)
        for a, b_ in ((dq, want_dq), (dk, want_dk), (dv, want_dv)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=3e-4, rtol=1e-3
            )

    def test_block_grads_grouped(self):
        q, k, v, w = self._mk(4, 2)
        from edl_tpu.ops.attention import flash_block_grads, flash_with_lse

        o, lse = flash_with_lse(q, k, v, causal=True)
        delta = jnp.sum(
            w.astype(jnp.float32) * o.astype(jnp.float32), -1
        )
        dq, dk, dv = flash_block_grads(q, k, v, w, lse, delta, causal=True)
        _, want_dq, want_dk, want_dv = self._want(q, k, v, w, True)
        assert dk.shape == k.shape
        for a, b_ in ((dq, want_dq), (dk, want_dk), (dv, want_dv)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=3e-4, rtol=1e-3
            )

    def test_kv_heads_must_divide(self):
        q, k, v, _ = self._mk(4, 3)
        with pytest.raises(ValueError, match="divide"):
            flash_attention(q, k, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_grouped_matches_dense(self, causal):
        """Grouped k/v around the ring: the rotating shards stay at the
        grouped width and the result (and grads) match the broadcast
        dense reference."""
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        mesh = make_mesh({"dp": 2, "sp": 4})
        q, k, v, w = self._mk(4, 2, t=64, d=8)
        assert ring_attention_sharded.supports_gqa
        want_val, want_dq, want_dk, want_dv = self._want(q, k, v, w, causal)

        def f(q, k, v):
            return (
                ring_attention_sharded(q, k, v, mesh, causal=causal) * w
            ).sum()

        got_val, (dq, dk, dv) = jax.jit(
            jax.value_and_grad(f, argnums=(0, 1, 2))
        )(q, k, v)
        assert dk.shape == k.shape and dv.shape == v.shape
        np.testing.assert_allclose(float(got_val), float(want_val), rtol=2e-4)
        for a, b_ in ((dq, want_dq), (dk, want_dk), (dv, want_dv)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=3e-4, rtol=1e-3
            )

    @pytest.mark.parametrize(
        "h,h_kv",
        [
            (8, 4),   # kv % sp == 0: grouped kv all-to-all
            (8, 1),   # MQA: all-gather + per-device head slice
            (12, 6),  # middle ground: internal broadcast fallback
        ],
    )
    @pytest.mark.parametrize("causal", [False, True])
    def test_ulysses_grouped_matches_dense(self, h, h_kv, causal):
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        mesh = make_mesh({"dp": 2, "sp": 4})
        q, k, v, w = self._mk(h, h_kv, t=64, d=8)
        assert ulysses_attention_sharded.supports_gqa
        want_val, want_dq, want_dk, want_dv = self._want(q, k, v, w, causal)

        def f(q, k, v):
            return (
                ulysses_attention_sharded(q, k, v, mesh, causal=causal) * w
            ).sum()

        got_val, (dq, dk, dv) = jax.jit(
            jax.value_and_grad(f, argnums=(0, 1, 2))
        )(q, k, v)
        assert dk.shape == k.shape and dv.shape == v.shape
        np.testing.assert_allclose(float(got_val), float(want_val), rtol=2e-4)
        for a, b_ in ((dq, want_dq), (dk, want_dk), (dv, want_dv)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=3e-4, rtol=1e-3
            )

    def test_gqa_model_passes_grouped_to_supporting_fn(self):
        """The model must hand GROUPED k/v to an attention_fn that
        declares supports_gqa, and broadcast for one that doesn't."""
        from edl_tpu.models.transformer import TransformerLM

        seen = {}

        def spy_plain(q, k, v, causal=False):
            seen["plain"] = (q.shape[1], k.shape[1])
            return v

        def spy_gqa(q, k, v, causal=False):
            seen["gqa"] = (q.shape[1], k.shape[1])
            g = q.shape[1] // k.shape[1]
            return jnp.repeat(v, g, axis=1)

        def spy_partial(q, k, v, causal=False, tag="partial"):
            seen[tag] = (q.shape[1], k.shape[1])
            g = q.shape[1] // k.shape[1]
            return jnp.repeat(v, g, axis=1)

        spy_gqa.supports_gqa = True
        spy_partial.supports_gqa = True
        import functools

        # the repo's standard ring wiring is functools.partial — the
        # attribute must be found through the wrapping
        wrapped = functools.partial(
            functools.partial(spy_partial, tag="partial")
        )
        tokens = jnp.zeros((2, 16), jnp.int32)
        for name, fn in (
            ("plain", spy_plain), ("gqa", spy_gqa), ("partial", wrapped),
        ):
            m = TransformerLM(
                vocab_size=32, d_model=32, num_heads=4, num_layers=1,
                d_ff=64, num_kv_heads=2, attention_fn=fn,
                dtype=jnp.float32,
            )
            m.init(jax.random.PRNGKey(0), tokens)
        assert seen["plain"] == (4, 4), seen
        assert seen["gqa"] == (4, 2), seen
        assert seen["partial"] == (4, 2), seen


class TestGQA:
    """Grouped-query attention in the LM family (net-new vs the
    reference, which has no LMs at all)."""

    def test_gqa_param_savings_and_forward(self):
        from edl_tpu.models.transformer import TransformerLM

        cfg = dict(vocab_size=64, d_model=32, num_heads=4, num_layers=2,
                   d_ff=64, dtype=jnp.float32)
        tokens = jnp.zeros((2, 16), jnp.int32)
        rng = jax.random.PRNGKey(0)

        mha = TransformerLM(**cfg)
        gqa = TransformerLM(**cfg, num_kv_heads=2)
        p_mha = mha.init(rng, tokens)["params"]
        p_gqa = gqa.init(rng, tokens)["params"]
        # K/V projections halve with num_kv_heads=2 of 4
        k_mha = p_mha["layer_0"]["attn"]["k"]["kernel"]
        k_gqa = p_gqa["layer_0"]["attn"]["k"]["kernel"]
        assert k_mha.shape == (32, 4, 8) and k_gqa.shape == (32, 2, 8)

        logits = gqa.apply({"params": p_gqa}, tokens)
        assert logits.shape == (2, 16, 64)
        assert bool(jnp.isfinite(logits).all())
        # grads flow to the grouped projections
        g = jax.grad(
            lambda p: gqa.apply({"params": p}, tokens).sum()
        )(p_gqa)
        assert float(jnp.abs(g["layer_0"]["attn"]["k"]["kernel"]).sum()) > 0

    def test_gqa_equals_mha_when_kv_heads_match(self):
        """num_kv_heads == num_heads must be EXACTLY the MHA module
        (same param tree, same outputs)."""
        from edl_tpu.models.transformer import TransformerLM

        cfg = dict(vocab_size=64, d_model=32, num_heads=4, num_layers=1,
                   d_ff=64, dtype=jnp.float32)
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, 64, (2, 12)))
        rng = jax.random.PRNGKey(1)
        a = TransformerLM(**cfg)
        b = TransformerLM(**cfg, num_kv_heads=4)
        pa = a.init(rng, tokens)
        pb = b.init(rng, tokens)
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)),
            pa, pb,
        )
        np.testing.assert_array_equal(
            np.asarray(a.apply(pa, tokens)), np.asarray(b.apply(pb, tokens)))

    def test_gqa_matches_explicitly_repeated_mha(self):
        """GQA must equal dense attention over explicitly repeated KV
        heads — broadcasting happens before the kernel, so every
        dispatch implementation sees ordinary MHA shapes."""
        from edl_tpu.models.transformer import Attention
        from edl_tpu.ops.attention import attention_reference

        x = jnp.asarray(np.random.RandomState(3).randn(2, 16, 32), jnp.float32)
        positions = jnp.broadcast_to(jnp.arange(16)[None, :], (2, 16))
        attn = Attention(num_heads=4, dtype=jnp.float32, num_kv_heads=2,
                         attention_fn=attention_reference)
        p = attn.init(jax.random.PRNGKey(0), x, positions)
        out = attn.apply(p, x, positions)
        assert out.shape == x.shape and bool(jnp.isfinite(out).all())

    def test_invalid_group_raises(self):
        from edl_tpu.models.transformer import Attention

        x = jnp.zeros((1, 8, 32), jnp.float32)
        positions = jnp.zeros((1, 8), jnp.int32)
        attn = Attention(num_heads=4, dtype=jnp.float32, num_kv_heads=3)
        with pytest.raises(ValueError):
            attn.init(jax.random.PRNGKey(0), x, positions)

    def test_invalid_zero_kv_heads_raises(self):
        from edl_tpu.models.transformer import Attention

        x = jnp.zeros((1, 8, 32), jnp.float32)
        positions = jnp.zeros((1, 8), jnp.int32)
        with pytest.raises(ValueError):
            Attention(num_heads=4, dtype=jnp.float32, num_kv_heads=0).init(
                jax.random.PRNGKey(0), x, positions
            )

    def test_gqa_through_pipeline_matches_direct(self):
        """The stage-split pipeline must carry num_kv_heads: pipeline
        logits == direct apply for a GQA model."""
        from edl_tpu.models.transformer import TransformerLM
        from edl_tpu.parallel import (
            make_mesh, pipeline_lm_logits, split_lm_params,
        )

        model = TransformerLM(
            vocab_size=64, d_model=32, num_heads=4, num_layers=2, d_ff=64,
            dtype=jnp.float32, num_kv_heads=2,
        )
        tokens = jnp.asarray(np.random.RandomState(5).randint(0, 64, (4, 8)))
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        want = model.apply({"params": params}, tokens)
        mesh = make_mesh({"pp": 2, "dp": 4})
        split = split_lm_params(model, params, pp=2)
        with mesh:
            got = pipeline_lm_logits(
                model, split, tokens, mesh, num_microbatches=2
            )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5
        )

    def test_gqa_tp_rules_replicate_grouped_kv(self):
        """TP rules on a GQA model: q/o shard on tp, the narrowed k/v
        head axis (2 KV heads, tp=4) falls back to replication instead
        of failing."""
        from edl_tpu.models.transformer import TransformerLM
        from edl_tpu.parallel import make_mesh
        from edl_tpu.parallel.sharding_rules import (
            TRANSFORMER_TP_RULES, shard_params_by_rules,
        )

        model = TransformerLM(
            vocab_size=64, d_model=32, num_heads=4, num_layers=1, d_ff=64,
            dtype=jnp.float32, num_kv_heads=2,
        )
        tokens = jnp.zeros((2, 8), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        mesh = make_mesh({"tp": 4, "dp": 2})
        placed = shard_params_by_rules(mesh, params, TRANSFORMER_TP_RULES)
        q_spec = placed["layer_0"]["attn"]["q"]["kernel"].sharding.spec
        k_spec = placed["layer_0"]["attn"]["k"]["kernel"].sharding.spec
        assert tuple(q_spec) == (None, "tp", None)
        assert tuple(k_spec) == (None, None, None)  # replicated fallback
