"""Gated short-convolution mixer: the cheap sequence layer of a convolution /
attention hybrid.

The layer of Liquid AI's LFM2 line as Hugging Face's ``Lfm2ShortConv`` lays it
out, for the block's normalised input ``x`` ``[B, T, d_model]``::

    [B_g | C_g | x~] = W_in x            three thirds of d_model each, no bias
    u   = B_g * x~                       a gate before the taps
    c_t = sum_k w[k] * u_{t - (taps - 1) + k}     depthwise, causal, zeros before 0
    out = W_out (C_g * c)                a gate after them; no bias

No activation, no bias on the taps and no state beyond the ``taps - 1`` newest
steps of ``u``. The two gates, the taps and their sum are one operation,
``ops/causal_conv.py:gated_causal_conv``, which reads the three thirds in place
out of the in projection's output: float32 arithmetic, rounded once to the
model's dtype.

The device time of its two parts carries the names ``sconv_proj`` (both
projections) and ``sconv_conv`` (the gates and the taps) (``jax.named_scope``;
``obs/profile.py:step_scopes`` joins them to a trace).

The in projection's output bears no ``checkpoint_name`` (the recurrent mixers'
bears ``mixer_in``, ``models/mamba.py:projected``): kept under ``"save_flash"``
it took the seven recomputed ``in_proj`` matmuls out of
``lfm2_24b_a2b.steady``'s step (7.9 ms) and the step was 0.6 ms slower for 0.7
GB more, because the matmuls of the mixers' backward then found their operands
in HBM where XLA had prefetched them under the recomputed matmul (PERF.md
section 6, PR 54).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from edl_tpu.ops.causal_conv import gated_causal_conv

SCONV_SCOPES = ("sconv_proj", "sconv_conv")


@dataclasses.dataclass(frozen=True)
class ShortConvSpec:
    """The shape of a :class:`ShortConvMixer`, as one hashable field; its
    width is the model's."""

    taps: int = 3


class ShortConvMixer(nn.Module):
    spec: ShortConvSpec
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d_model, taps = x.shape[-1], self.spec.taps
        dense = lambda width, name: nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=self.dtype, name=name
        )
        with jax.named_scope("sconv_proj"):
            proj = dense(3 * d_model, "in_proj")(x)
        with jax.named_scope("sconv_conv"):
            bound = taps ** -0.5  # torch's Conv1d default, fan-in taps
            kernel = self.param(
                "conv_kernel",
                lambda key, shape: jax.random.uniform(
                    key, shape, jnp.float32, -bound, bound
                ),
                (taps, d_model),
            )
            gated = gated_causal_conv(proj, kernel)
        with jax.named_scope("sconv_proj"):
            return dense(d_model, "out_proj")(gated)
