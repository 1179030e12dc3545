"""``LMHead``'s backward: the logits' cotangent is rounded to the operand
dtype once, as one array that both head matmuls read. Values against a
float32 reference and against autodiff of the plain product; the structure
on the lowered text of a small step, for a model that the trainer splits in
halves and for one that it does not."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from edl_tpu.models import MoESpec, TransformerLM
from edl_tpu.models.transformer import LMHead
from edl_tpu.train import create_state, cross_entropy_loss, make_train_step

V = 64


def head_case(dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((3, 10, 32)), dtype)
    kernel = jnp.asarray(rng.standard_normal((32, V)) / 6, jnp.float32)
    ct = jnp.asarray(rng.standard_normal((3, 10, V)), jnp.float32)
    return x, kernel, ct


def head_vjp(x, kernel, ct):
    logits, pull = jax.vjp(
        lambda x, kernel: LMHead(V).apply({"params": {"kernel": kernel}}, x), x, kernel
    )
    return (logits, *pull(ct))


@pytest.mark.parametrize("what", ["logits", "dx", "dW"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_head_equals_a_float32_reference(dtype, what):
    """The reference states the rule in float32 ``jnp``: every operand of a
    head matmul is first rounded to the activation dtype, the cotangent too."""
    x, kernel, ct = head_case(dtype)
    with jax.default_matmul_precision("highest"):
        got = dict(zip(("logits", "dx", "dW"), head_vjp(x, kernel, ct)))
        x32 = x.astype(jnp.float32)
        w32 = kernel.astype(dtype).astype(jnp.float32)
        g32 = ct.astype(dtype).astype(jnp.float32)
        want = {
            "logits": x32 @ w32,
            "dx": g32 @ w32.T,
            "dW": jnp.einsum("btd,btv->dv", x32, g32),
        }[what]
    assert got[what].dtype == {"logits": jnp.float32, "dx": dtype, "dW": jnp.float32}[what]
    if dtype == jnp.float32 or what == "logits":
        # the LM's own tolerances (tests/test_olmoe.py): nothing is rounded
        atol = {"logits": 2e-5, "dx": 2e-6, "dW": 2e-5}[what]
        np.testing.assert_allclose(got[what], want, atol=atol)
    else:
        # one rounding of the result to bfloat16, as autodiff hands a
        # bfloat16 operand its cotangent
        np.testing.assert_allclose(
            got[what].astype(jnp.float32), want, rtol=2.0 ** -8, atol=1e-6
        )


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_head_gradients_are_what_autodiff_gave(dtype):
    """On a cotangent that the operand dtype holds exactly: the CPU multiplies
    a float32 cotangent as it is, where the chip's MXU rounds it."""
    x, kernel, ct = head_case(dtype)
    ct = ct.astype(dtype).astype(jnp.float32)
    plain = jax.vjp(
        lambda x, kernel: jax.lax.dot_general(
            x, kernel.astype(x.dtype), (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ), x, kernel)
    logits, dx, dw = head_vjp(x, kernel, ct)
    np.testing.assert_array_equal(logits, plain[0])
    want_dx, want_dw = plain[1](ct)
    np.testing.assert_allclose(
        dx.astype(jnp.float32), want_dx.astype(jnp.float32), rtol=2.0 ** -8, atol=1e-6
    )
    np.testing.assert_allclose(dw, want_dw, rtol=2.0 ** -8, atol=1e-6)


def lm_loss(logits, y):
    return cross_entropy_loss(logits.reshape(-1, logits.shape[-1]), y.reshape(-1))


def small_lm(sows):
    moe = MoESpec(num_experts=4, top_k=2, d_ff=24, aux_weight=0.01, z_weight=0.001)
    return TransformerLM(vocab_size=V, d_model=32, num_heads=4, num_layers=1, d_ff=48,
                         remat=True, moe=moe if sows else None)


@pytest.mark.parametrize("sows", [False, True], ids=["split", "unsplit"])
def test_the_step_rounds_the_logits_cotangent_once(sows):
    """A dense LM's step is split in halves under the numerics plane, a model
    that sows losses never is: XLA gave the first one materialised gradient a
    half by accident and the second none. Both are pinned here."""
    lm = small_lm(sows)
    tokens = np.zeros((4, 12), np.int32)
    state = jax.eval_shape(
        lambda: create_state(lm, jax.random.PRNGKey(0), tokens, optax.adamw(3e-4))
    )
    assert bool(state.sown) == sows
    text = make_train_step(lm_loss, numerics=True).lower(state, (tokens, tokens)).as_text()
    passes = 1 if sows else 2
    rows = "%dx12x" % (4 // passes)
    logits32, logits16 = "tensor<%s%dxf32>" % (rows, V), "tensor<%s%dxbf16>" % (rows, V)
    hidden16 = "tensor<%s32xbf16>" % rows

    forward = re.findall(r"stablehlo\.dot_general .*-> %s" % re.escape(logits32), text)
    assert len(forward) == passes and all(hidden16 in line for line in forward)

    rounded = re.findall(
        r"(%\w+) = stablehlo\.convert (%\w+) : \({0}\) -> {1}".format(
            re.escape(logits32), re.escape(logits16)), text)
    barriers = re.findall(
        r"(%\w+) = stablehlo\.optimization_barrier (%\w+) : {0}\n".format(
            re.escape(logits16)), text)
    assert len(rounded) == len(barriers) == passes
    assert {arg for _, arg in barriers} == {name for name, _ in rounded}

    pinned = {name for name, _ in barriers}
    readers = [
        line for line in re.findall(r"stablehlo\.dot_general .*", text)
        if logits16 in line.split(" : ")[1].split(" -> ")[0]
    ]
    assert len(readers) == 2 * passes
    for line in readers:
        assert set(re.findall(r"%\w+", line.split(", contracting_dims")[0])) & pinned, line
