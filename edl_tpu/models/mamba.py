"""Mamba-2 mixer: the sequence layer of a state-space / attention hybrid.

The layer of Dao and Gu (arXiv:2405.21060) as Hugging Face's
``GraniteMoeHybridMambaLayer`` lays it out, for normalised input ``n``
``[B, T, d_model]``::

    [z | xBC | dt] = W_in n              widths d_inner | d_inner + 2 G N | H
    xBC  = silu(causal depthwise conv_{d_conv}(xBC) + b_conv)    (ops/causal_conv.py)
    x, B, C = split(xBC)                  x: H heads of P; B, C: G groups of N
    dt   = softplus(dt + dt_bias)         per head
    y    = ssd_scan(x, dt, -exp(A_log), B, C, D)        (ops/ssd.py)
    out  = W_out RMSNorm(y * silu(z))     over each group's d_inner / G channels
                                          (all d_inner at G = 1), learned scale

``H`` and ``G`` may be one chip's share of the published heads and groups, a
group's ``H / G`` heads whole: a head's state is its own and the norm a group's
own, so what the other groups' heads give before ``W_out`` is computed where
they are held, and concatenates to the whole layer's.

The convolution, its bias and its SiLU are one operation,
``ops/causal_conv.py:causal_conv_silu``: on a TPU a pair of Pallas kernels that
read ``xBC`` in place out of the in projection's output and write it once, both
in the model's dtype, while the float32 taps, sum, bias and SiLU stay in VMEM
and registers; elsewhere the plain float32 form, ``causal_conv`` there.

The scan is a pair of Pallas kernels on a TPU backend at shapes they take
(``ops/ssd.py``: ``ssd_forward`` / ``ssd_backward``, time along the lanes as
the convolution's kernels leave ``xBC`` and as the gate below reads ``y``; they
carry the state from chunk to chunk themselves, in VMEM, so a layer holds no
``lax.scan``) and plain ``jax.numpy`` around a ``lax.scan`` elsewhere; each
traced shape leaves one ``ssm_chunks`` instant in the ring (``chunk``,
``chunks``, ``heads``, ``groups``, ``d_head``, ``d_state``, ``state_bytes``,
``path`` ``kernel`` / ``plain``, ``carry`` ``kernel`` / ``loop`` and, on
``plain``, ``why``), which ``step_plain_fallbacks`` counts.

The device time of its four parts carries the names ``ssm_proj`` (both
projections), ``ssm_conv``, ``ssm_scan`` and ``ssm_gate``
(``jax.named_scope``; ``obs/profile.py:step_scopes`` joins them to a trace).
Sown into ``"metrics"``: ``ssm_decay_mean``, the mean of ``exp(dt * A)`` over
steps and heads (how fast the state forgets: 1 never, 0 at once); into
``"intermediates"``, for a check that asks for the collection, ``gated``: the
normed and gated ``[B, T, d_inner]`` that ``W_out`` reads.

For a remat policy around the block the in projection's output ``[z | xBC |
dt]`` bears the name ``mixer_in`` (:func:`projected`, whole and before the
slices): a policy that saves it (``TransformerLM.remat_policy``
``"save_flash"``) hands the block's recomputation the array the forward wrote,
so the convolution, the gate and ``dt`` read it again and no ``in_proj`` matmul
runs a second time; one that saves none multiplies again.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from edl_tpu.obs import trace as obs_trace
from edl_tpu.ops.causal_conv import causal_conv_silu
from edl_tpu.ops.ssd import ssd_scan

SSM_SCOPES = ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate")

# The ``checkpoint_name`` of what a recurrent mixer's in projections hand on
# (this mixer's and ``models/gated_delta.py``'s two; ``models/short_conv.py``
# says why its own bears none); ``models/transformer.py:_remat_policy`` saves it
IN_NAME = "mixer_in"
REMAT_NAMES = (IN_NAME,)


def projected(mixer: str, *arrays):
    """``arrays``, the outputs of a mixer's in projections, each under
    ``IN_NAME`` for a remat policy that keeps names: the convolution's backward
    wants its input and the gate's wants ``z``, and a block's recomputation
    that is handed them runs no in projection again. Once a (mixer, shape) and
    stage a ``mixer_saved`` instant: ``mixer``, the ``arrays``' count and the
    ``bytes`` one layer leaves under the name."""
    obs_trace.get_tracer().note_once(
        "mixer_saved", mixer=mixer, arrays=len(arrays),
        bytes=sum(a.size * a.dtype.itemsize for a in arrays),
    )
    return tuple(checkpoint_name(a, IN_NAME) for a in arrays)


@dataclasses.dataclass(frozen=True)
class MambaSpec:
    """The shape of a :class:`Mamba2Mixer`, as one hashable field."""

    num_heads: int         # H; d_inner = num_heads * head_dim
    head_dim: int          # P
    d_state: int           # N
    n_groups: int = 1      # G: B and C are shared by H / G heads
    d_conv: int = 4
    chunk: int = 256       # steps a chunk of the scan
    conv_bias: bool = True


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log U(1, 16)``: Mamba-2's own, a spread of decay rates."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32, low=1e-3, high=1e-1):
    """The inverse softplus of a step drawn log-uniformly in [low, high]."""
    dt = jnp.exp(
        jax.random.uniform(key, shape, dtype) * (math.log(high) - math.log(low))
        + math.log(low)
    )
    return dt + jnp.log(-jnp.expm1(-dt))


class Mamba2Mixer(nn.Module):
    spec: MambaSpec
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        s = self.spec
        batch, t, d_model = x.shape
        d_inner, gn = s.num_heads * s.head_dim, s.n_groups * s.d_state
        conv_dim = d_inner + 2 * gn
        f32 = jnp.float32
        dense = lambda width, name: nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=self.dtype, name=name
        )

        with jax.named_scope("ssm_proj"):
            zxbcdt, = projected(
                "mamba2", dense(d_inner + conv_dim + s.num_heads, "in_proj")(x)
            )
        z, dt = zxbcdt[..., :d_inner], zxbcdt[..., d_inner + conv_dim:]

        with jax.named_scope("ssm_conv"):
            bound = s.d_conv ** -0.5  # torch's Conv1d default, fan-in d_conv
            kernel = self.param(
                "conv_kernel",
                lambda key, shape: jax.random.uniform(key, shape, f32, -bound, bound),
                (s.d_conv, conv_dim),
            )
            bias = (
                self.param("conv_bias", nn.initializers.zeros, (conv_dim,))
                if s.conv_bias else None
            )
            xbc = causal_conv_silu(zxbcdt, kernel, bias, offset=d_inner)
        xs, b, c = jnp.split(xbc, [d_inner, d_inner + gn], axis=-1)

        a_log = self.param("A_log", _a_log_init, (s.num_heads,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (s.num_heads,))
        skip = self.param("D", nn.initializers.ones, (s.num_heads,))
        with jax.named_scope("ssm_scan"):
            dt, a = jax.nn.softplus(dt.astype(f32) + dt_bias), -jnp.exp(a_log)
            y = ssd_scan(
                xs.reshape(batch, t, s.num_heads, s.head_dim), dt, a,
                b.reshape(batch, t, s.n_groups, s.d_state),
                c.reshape(batch, t, s.n_groups, s.d_state),
                skip, chunk=s.chunk,
            )
            self.sow("metrics", "ssm_decay_mean", jnp.mean(jnp.exp(dt * a)))

        with jax.named_scope("ssm_gate"):
            scale = self.param("norm", nn.initializers.ones, (d_inner,))
            gated = y.reshape(batch, t, d_inner).astype(f32) * nn.silu(z.astype(f32))
            if s.n_groups > 1:  # a group's channels are normalised among themselves
                gated = gated.reshape(batch, t, s.n_groups, d_inner // s.n_groups)
            gated = gated * jax.lax.rsqrt(
                jnp.mean(gated * gated, axis=-1, keepdims=True) + self.norm_eps
            )
            if s.n_groups > 1:
                gated = gated.reshape(batch, t, d_inner)
            gated = (gated * scale).astype(self.dtype)
            self.sow("intermediates", "gated", gated)  # before W_out (a check's)

        with jax.named_scope("ssm_proj"):
            return dense(d_model, "out_proj")(gated)
