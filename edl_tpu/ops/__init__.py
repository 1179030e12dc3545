"""Pallas TPU kernels and their reference implementations.

The compute-path hot ops of the framework. Each op ships a pure-jnp
reference (differentiable, runs anywhere) and, where it pays, a Pallas
TPU kernel selected automatically on TPU backends (interpret mode keeps
the kernels testable on CPU).

Net-new capability versus the reference system, which has no kernels at
all (SURVEY §1: "EDL contains no compute kernels"): the task charter makes
long-context attention + distributed compute first-class here.
"""

from edl_tpu.ops.attention import attention, attention_reference, flash_attention
from edl_tpu.ops.causal_conv import causal_conv_silu, gated_causal_conv
from edl_tpu.ops.gated_delta import gated_delta_rule, kda_rule
from edl_tpu.ops.grouped_matmul import grouped_matmul
from edl_tpu.ops.sparse_attention import sparse_attention
from edl_tpu.ops.ssd import ssd_scan

__all__ = ["attention", "attention_reference", "causal_conv_silu", "flash_attention",
           "gated_causal_conv", "gated_delta_rule", "grouped_matmul", "kda_rule",
           "sparse_attention", "ssd_scan"]
