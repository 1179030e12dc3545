"""The compiled step accounts for itself: every instruction under one part and
one pass (``obs/profile.py:parts_of_hlo``), a census of it in the ring once a
stage (``publish_step_census``, on a thread ``fit`` waits for), one
``SpanTracer.note_once`` for what a call site says of a shape (``path`` /
``why`` where it chooses between a kernel and a plain form), and the sown
gauges on every ``step_retired`` mark."""

import importlib
import json
import os
import re
import threading
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from edl_tpu.models import ArchSpec, MoESpec, TransformerLM
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import profile as obs_profile
from edl_tpu.obs import trace as obs_trace
from edl_tpu.train import ElasticTrainer, cross_entropy_loss
from edl_tpu.train import loop as train_loop

# ``edl_tpu.ops.attention`` the module: the package's attribute is the function
A = importlib.import_module("edl_tpu.ops.attention")

# -- one table: part and pass ---------------------------------------------------

HLO = """
HloModule jit_step

%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%fused_forward (x: bf16[8,8], w: bf16[8,8]) -> bf16[8,8] {
  %x = bf16[8,8]{1,0} parameter(0)
  %w = bf16[8,8]{1,0} parameter(1)
  %mm = bf16[8,8]{1,0} convolution(%x, %w), metadata={op_name="jit(step)/jvp(forward)/TransformerLM/layer_0/mlp/gate/dot_general"}
  ROOT %act = bf16[8,8]{1,0} multiply(%mm, %mm), metadata={op_name="jit(step)/jvp(forward)/TransformerLM/layer_0/mlp/jit(silu)/mul"}
}

%fused_dw (x: bf16[8,8], p: f32[8,8]) -> f32[8,8] {
  %x = bf16[8,8]{1,0} parameter(0)
  %p = f32[8,8]{1,0} parameter(1)
  %dw = f32[8,8]{1,0} convolution(%x, %x), metadata={op_name="jit(step)/transpose(jvp(forward))/TransformerLM/lm_head/dot_general"}
  ROOT %new = f32[8,8]{1,0} add(%p, %dw), metadata={op_name="jit(step)/optimizer/add"}
}

%fused_pass (g: f32[8,8], p: f32[8,8]) -> (f32[], f32[8,8]) {
  %g = f32[8,8]{1,0} parameter(0)
  %p = f32[8,8]{1,0} parameter(1)
  %sq = f32[8,8]{1,0} multiply(%g, %g), metadata={op_name="jit(step)/numerics/square"}
  %zero = f32[] constant(0)
  %norm = f32[] reduce(%sq, %zero), dimensions={0,1}, to_apply=%region_add, metadata={op_name="jit(step)/numerics/reduce_sum"}
  %new.1 = f32[8,8]{1,0} add(%p, %g), metadata={op_name="jit(step)/optimizer/add"}
  ROOT %tuple = (f32[], f32[8,8]{1,0}) tuple(%norm, %new.1)
}

%carry_body (s: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %s = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%s), index=0
  %h = f32[8,8]{1,0} get-tuple-element(%s), index=1
  %step = f32[8,8]{1,0} dot(%h, %h), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jvp(forward)/TransformerLM/layer_1/kda/kda_scan/checkpoint/while/body/dot_general"}
  ROOT %next = (s32[], f32[8,8]{1,0}) tuple(%i, %step)
}

%carry_cond (s: (s32[], f32[8,8])) -> pred[] {
  %s = (s32[], f32[8,8]{1,0}) parameter(0)
  %j = s32[] get-tuple-element(%s), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(%j, %n), direction=LT
}

%branch_a (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  ROOT %gmm.7 = f32[8,8]{1,0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(forward)/TransformerLM/layer_1/moe/cond/branch_0_fun/moe_experts/jit(gmm)/pallas_call"}
}

%branch_b (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  ROOT %gmm.8 = f32[8,8]{1,0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(forward)/TransformerLM/layer_1/moe/cond/branch_1_fun/moe_experts/jit(gmm)/pallas_call"}
}

ENTRY %main (x: bf16[8,8], w: bf16[8,8], p: f32[8,8], t: (s32[], f32[8,8]), k: pred[]) -> f32[8,8] {
  %x = bf16[8,8]{1,0} parameter(0)
  %w = bf16[8,8]{1,0} parameter(1)
  %p = f32[8,8]{1,0} parameter(2), metadata={op_name="state.params['lm_head']['kernel']"}
  %t = (s32[], f32[8,8]{1,0}) parameter(3)
  %k = pred[] parameter(4)
  %fusion.0 = bf16[8,8]{1,0} fusion(%x, %w), kind=kOutput, calls=%fused_forward
  %attn.3 = bf16[8,8]{1,0} custom-call(%x, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(forward)/TransformerLM/layer_0/attn/pallas_call"}
  %flash.1 = bf16[8,8]{1,0} custom-call(%x, %w), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(forward))/TransformerLM/jvp(forward)/TransformerLM/checkpoint/layer_0/attn/attn_mla/pallas_call"}
  %remat = bf16[8,8]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/transpose(jvp(forward))/TransformerLM/jvp(forward)/TransformerLM/checkpoint/rematted_computation/layer_0/mlp/down/dot_general"}
  %qnorm = bf16[8,8]{1,0} multiply(%x, %w), metadata={op_name="jit(step)/jvp(forward)/TransformerLM/layer_0/attn/q_norm/mul"}
  %resid = bf16[8,8]{1,0} add(%x, %w), metadata={op_name="jit(step)/jvp(forward)/TransformerLM/layer_0/add"}
  %tied = bf16[8,8]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jvp(forward)/TransformerLM/dot_general"}
  %nll = bf16[8,8]{1,0} negate(%x), metadata={op_name="jit(step)/jvp(forward)/jit(log_softmax)/neg"}
  %plain = bf16[8,8]{1,0} copy(%x)
  %stray = bf16[8,8]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jvp(forward)/TransformerLM/mystery/dot_general"}
  %novel = bf16[8,8]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jvp(forward)/TransformerLM/layer_0/newmixer/proj/dot_general"}
  %bare = bf16[8,8]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jvp(forward)/dot_general"}
  %while.2 = (s32[], f32[8,8]{1,0}) while(%t), condition=%carry_cond, body=%carry_body, metadata={op_name="jit(step)/jvp(forward)/TransformerLM/layer_1/kda/kda_scan/checkpoint/while"}
  %conditional.4 = f32[8,8]{1,0} conditional(%k, %p, %p), true_computation=%branch_a, false_computation=%branch_b, metadata={op_name="jit(step)/jvp(forward)/TransformerLM/layer_1/moe/cond"}
  %all-reduce.1 = f32[8,8]{1,0} all-reduce(%p), to_apply=%region_add, metadata={op_name="jit(step)/grad_mean/psum"}
  %convert_reduce_fusion = (f32[], f32[8,8]{1,0}) fusion(%p, %p), kind=kLoop, calls=%fused_pass, metadata={op_name="jit(step)/numerics/reduce_sum"}
  ROOT %fusion.9 = f32[8,8]{1,0} fusion(%x, %p), kind=kOutput, calls=%fused_dw, metadata={op_name="jit(step)/transpose(jvp(forward))/TransformerLM/lm_head/dot_general"}
}
"""


def test_every_instruction_has_one_part_and_one_pass():
    table = obs_profile.parts_of_hlo(HLO)
    # a fusion counts where its root does, a matmul inside it where it is
    assert table["fusion.0"] == ("mlp", "forward") and table["mm"] == ("mlp", "forward")
    # an optimizer's pass under `optimizer` whatever name it took; the fused dW
    # stays the matmul's
    assert table["convert_reduce_fusion"] == ("other", "optimizer")
    assert table["fusion.9"] == ("head", "backward")
    # what jax.checkpoint runs again: the part it recomputes, the backward pass
    assert table["remat"] == ("mlp", "backward")
    # under two names: the innermost (a scope inside a module, a module's own
    # norm under the module); a Pallas call right under `attn` is the kernel
    assert table["flash.1"] == ("attn_mla", "backward")
    assert table["qnorm"] == ("attn", "forward")
    assert table["attn.3"] == ("attn_kernel", "forward")
    # no module: a block's residual, a tied head, the loss; no name: other
    assert table["resid"] == ("block", "forward")
    assert table["tied"] == ("head", "forward")
    assert table["nll"] == ("loss", "forward")
    assert table["plain"] == ("other", "other")
    assert table["stray"] == ("other", "forward")
    # a module under a layer that the table does not list, and no name at all
    assert table["novel"] == ("block", "forward")
    assert table["bare"] == ("loss", "forward")
    assert table["all-reduce.1"] == ("other", "backward")
    # a loop is a container and its body's instructions are placed themselves
    assert table["while.2"] == (obs_profile.CONTAINER, "forward")
    assert table["step"] == ("kda_scan", "forward")
    assert table["conditional.4"] == (obs_profile.CONTAINER, "forward")
    assert table["gmm.7"] == table["gmm.8"] == ("moe_experts", "forward")
    assert {part for part, _ in table.values()} <= (
        {part for _, part in obs_profile.STEP_PARTS}
        | {"attn_kernel", "block", "loss", "other", obs_profile.CONTAINER}
    )
    assert {phase for _, phase in table.values()} <= set(obs_profile.PHASES)


def test_the_phase_and_scope_tables_are_what_they_were():
    """``step_phases()`` / ``step_scopes()`` stand on the same parse and return
    what the three functions returned before it."""
    phases = obs_profile.phases_of_hlo(HLO)
    assert phases == {
        "sum": "other", "mm": "forward", "act": "forward", "dw": "backward",
        "new": "optimizer", "new.1": "optimizer", "sq": "numerics", "norm": "numerics",
        "step": "forward", "gmm.7": "forward", "gmm.8": "forward",
        "p": "other", "fusion.0": "forward", "attn.3": "forward",
        "flash.1": "backward", "remat": "backward", "qnorm": "forward",
        "resid": "forward", "tied": "forward", "nll": "forward",
        "stray": "forward", "novel": "forward", "bare": "forward", "while.2": "forward", "conditional.4": "forward",
        "all-reduce.1": "backward", "convert_reduce_fusion": "optimizer",
        "fusion.9": "backward",
    }
    assert obs_profile.update_passes_of_hlo(HLO) == ["convert_reduce_fusion"]
    assert obs_profile.scopes_of_hlo(HLO, ("kda_scan", "moe_experts", "attn_mla")) == {
        "step": "kda_scan", "while.2": "kda_scan", "gmm.7": "moe_experts",
        "gmm.8": "moe_experts", "flash.1": "attn_mla",
    }
    assert {p for p, _ in obs_profile.parts_of_hlo(HLO).values()} >= {"kda_scan"}
    # the parts of a table that predates the scopes: none, as the phases'
    assert obs_profile.parts_of_hlo(HLO.replace("jvp(forward)", "jvp(fwd)")) == {}


def test_the_census_counts_what_runs_as_written():
    census = obs_profile.HloProgram(HLO).census()
    # the entry's 17, a body's 4, a condition's 3 and two branches' 1 each,
    # parameters apart; nothing inside a fusion or a reducer. Unplaced: the
    # matmuls under `other`, under a layer's unlisted module and under no name
    assert census["totals"] == {
        "instructions": 17 + 4 + 3 + 2, "matmuls": 12, "kernel_calls": 4,
        "loops": 1, "conditionals": 1, "collectives": 1, "fused_dw": 1,
        "unplaced_matmuls": 3,
    }
    assert census["parts"]["block/forward"]["unplaced_matmuls"] == 1
    assert census["parts"]["loss/forward"]["unplaced_matmuls"] == 1
    assert census["kernels"] == {
        "attn/forward": 1, "flash/backward": 1, "gmm/forward": 2,
    }
    assert census["parts"]["head/backward"] == {
        "instructions": 1, "matmuls": 1, "fused_dw": 1,
    }
    # a loop and a conditional count under the part their own names give
    assert census["parts"]["kda_scan/forward"] == {
        "instructions": 2, "matmuls": 1, "loops": 1,
    }
    assert census["parts"]["moe/forward"] == {"instructions": 1, "conditionals": 1}
    assert not [key for key in census["parts"] if key.startswith("container")]
    assert census["parts"]["other/forward"]["unplaced_matmuls"] == 1
    assert sum(row["instructions"] for row in census["parts"].values()) == 26


def test_the_text_is_got_without_the_call_that_holds_the_gil():
    """``Compiled.as_text()`` held the GIL for 3.4 s on the chip and the step
    loop dispatched nothing meanwhile; ``hlo_modules()`` releases it."""
    class Module:
        def __init__(self, text):
            self.text = text

        def to_string(self):
            return self.text

    class Loaded:
        def hlo_modules(self):
            return [Module("HloModule a"), Module("HloModule b")]

    class Compiled:
        def __init__(self, executable):
            self.executable = executable

        def runtime_executable(self):
            return self.executable

        def as_text(self):
            return "the one call"

    assert obs_profile.executable_text(Compiled(Loaded())) == "HloModule a\n\nHloModule b"
    assert obs_profile.executable_text(Compiled(object())) == "the one call"
    real = jax.jit(lambda x: (x @ x).sum()).lower(jnp.ones((8, 8))).compile()
    assert obs_profile.executable_text(real) == real.as_text()


# -- note_once --------------------------------------------------------------------


def _named(tracer, name):
    return [e for e in tracer.to_events() if e["name"] == name]


def test_a_note_is_written_once_a_tracer_and_stage():
    tracer = obs_trace.SpanTracer("test")
    for kernel, name in (("gmm", "gmm_tiles"), ("gmm", "gmm_tiles"),
                         ("tgmm", "gmm_tiles"), ("gmm", "kda_chunks")):
        assert tracer.note_once(name, kernel=kernel, tiling=[8, 128]) is None
    assert [e["ph"] for e in _named(tracer, "gmm_tiles")] == ["i", "i"]
    assert tracer.notes() == [
        ("gmm_tiles", {"kernel": "gmm", "tiling": [8, 128]}),
        ("gmm_tiles", {"kernel": "tgmm", "tiling": [8, 128]}),
        ("kda_chunks", {"kernel": "gmm", "tiling": [8, 128]}),
    ]
    # another tracer has seen nothing
    other = obs_trace.SpanTracer("other")
    other.note_once("gmm_tiles", kernel="gmm")
    assert len(_named(other, "gmm_tiles")) == 1
    # a new stage notes its shapes again, and the ring keeps the old instants
    tracer.reset_notes()
    assert tracer.notes() == []
    tracer.note_once("gmm_tiles", kernel="gmm", tiling=[8, 128])
    assert len(_named(tracer, "gmm_tiles")) == 3
    # clear() forgets the ring and the notes together
    tracer.clear()
    assert len(tracer) == 0 and tracer.notes() == []
    tracer.note_once("gmm_tiles", kernel="gmm", tiling=[8, 128])
    assert len(_named(tracer, "gmm_tiles")) == 1


def test_no_cached_note_writer_is_left():
    """The nine ``functools.lru_cache``d writers went: a process-wide cache
    keyed by shape is what kept a second stage from noting anything."""
    import edl_tpu.models.transformer as transformer
    import edl_tpu.ops.attention as attention
    import edl_tpu.ops.causal_conv as causal_conv
    import edl_tpu.ops.gated_delta as gated_delta
    import edl_tpu.ops.grouped_matmul as grouped_matmul
    import edl_tpu.ops.sparse_attention as sparse_attention
    import edl_tpu.train.step as step

    for module in (transformer, attention, causal_conv, gated_delta,
                   grouped_matmul, sparse_attention, step):
        for name, value in vars(module).items():
            if name.startswith("_note"):
                assert not hasattr(value, "cache_clear"), (module.__name__, name)


# -- which form a shape took, and why ---------------------------------------------


def _new_notes(name, trace):
    """What ``trace()`` (a function that traces an op) notes under ``name``."""
    tracer = obs_trace.get_tracer()
    tracer.reset_notes()
    trace()
    return [args for noted, args in tracer.notes() if noted == name]


def _kda_operands(h=2, d=128, t=64, dtype=jnp.bfloat16):
    wide = jax.ShapeDtypeStruct((1, t, h, d), dtype)
    return wide, wide, wide, wide, jax.ShapeDtypeStruct((1, t, h), dtype)


@pytest.mark.parametrize("case,path,why", [
    (dict(interpret=True), "kernel", None),
    (dict(), "plain", "backend"),
    (dict(interpret=True, dtype=jnp.float32), "plain", "dtype"),
    (dict(interpret=True, chunk=32), "plain", "chunk"),
    (dict(interpret=True, t=96), "plain", "steps"),
    (dict(interpret=True, h=3), "plain", "heads_odd"),
    (dict(interpret=True, d=64), "plain", "width"),
])
def test_the_kda_rule_says_which_form_it_took(case, path, why):
    from edl_tpu.ops.gated_delta import kda_rule

    case = dict(case)
    rule = dict(chunk=case.pop("chunk", 64), interpret=case.pop("interpret", False))
    operands = _kda_operands(**case)
    noted = _new_notes("kda_chunks", lambda: jax.eval_shape(
        lambda *a: kda_rule(*a, **rule), *operands
    ))
    assert len(noted) == 1
    assert noted[0]["path"] == path and noted[0].get("why") == why


@pytest.mark.parametrize("case,path,why", [
    (dict(interpret=True), "kernel", None),
    (dict(), "plain", "backend"),
    (dict(interpret=True, t=100), "plain", "blocks"),
    (dict(t=100), "plain", "backend"),  # the first condition not met
])
def test_the_causal_convolution_says_which_form_it_took(case, path, why):
    from edl_tpu.ops.causal_conv import causal_conv_silu

    # a batch of two: the kernels' bodies are traced once a shape and process,
    # and tests/test_startup_spans.py counts those of ``[1, 128, 32]``
    x = jax.ShapeDtypeStruct((2, case.get("t", 128), 32), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((4, 32), jnp.float32)
    noted = _new_notes("conv_shape", lambda: jax.eval_shape(
        lambda x, w: causal_conv_silu(x, w, interpret=case.get("interpret", False)),
        x, w,
    ))
    assert len(noted) == 1
    assert noted[0]["path"] == path and noted[0].get("why") == why
    assert (noted[0]["channels"], noted[0]["taps"]) == (32, 4)


@pytest.mark.parametrize("implementation,path,why", [
    ("pallas", "kernel", None), (None, "plain", "backend"),
    ("ragged_dot", "plain", "asked"),
])
def test_the_grouped_matmul_says_which_form_it_took(implementation, path, why):
    from edl_tpu.ops.grouped_matmul import grouped_matmul

    lhs = jax.ShapeDtypeStruct((256, 128), jnp.float32)
    rhs = jax.ShapeDtypeStruct((2, 128, 256), jnp.float32)
    sizes = jax.ShapeDtypeStruct((2,), jnp.int32)
    noted = _new_notes("gmm_tiles", lambda: jax.eval_shape(
        lambda l, r, s: grouped_matmul(l, r, s, implementation, interpret=True),
        lhs, rhs, sizes,
    ))
    assert [(n["path"], n.get("why")) for n in noted] == [(path, why)]
    assert (noted[0]["rows"], noted[0]["contracting"], noted[0]["columns"]) == (
        256, 128, 256
    )


def test_attention_says_which_form_it_took():
    q = jax.ShapeDtypeStruct((1, 2, 256, 64), jnp.bfloat16)
    call = lambda: jax.eval_shape(  # noqa: E731
        lambda q: A.attention(q, q, q, causal=True), q
    )
    assert _new_notes("attn_route", call) == [
        {"tq": 256, "tk": 256, "window": None, "path": "plain", "why": "backend"}
    ]
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert _new_notes("attn_route", call) == [{
            "tq": 256, "tk": 256, "window": None, "path": "kernel",
        }]


@pytest.mark.parametrize("interpret,t,path,why", [
    (True, 128, "kernel", None), (None, 128, "plain", "backend"),
    (True, 96, "plain", "blocks"),
])
def test_sparse_attention_says_which_form_it_took(interpret, t, path, why):
    from edl_tpu.ops.sparse_attention import sparse_attention

    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    operands = (bf16(1, 2, t, 64), bf16(1, 1, t, 64), bf16(1, 1, t, 64),
                bf16(1, 2, t, 32), bf16(1, t, 32), bf16(1, t, 2))
    noted = _new_notes("dsa_shape", lambda: jax.eval_shape(
        lambda *a: sparse_attention(*a, topk=32, interpret=interpret), *operands
    ))
    assert len(noted) == 1
    assert noted[0]["path"] == path and noted[0].get("why") == why
    assert (noted[0]["tq"], noted[0]["topk"], noted[0]["index_heads"]) == (t, 32, 2)


def test_an_op_with_one_form_notes_no_path():
    from edl_tpu.ops.causal_conv import gated_causal_conv

    x = jax.ShapeDtypeStruct((1, 64, 3 * 16), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((3, 16), jnp.float32)
    noted = _new_notes(
        "sconv_shape", lambda: jax.eval_shape(gated_causal_conv, x, w)
    )
    assert len(noted) == 1 and "path" not in noted[0]


@pytest.mark.parametrize("case,path,why", [
    (dict(interpret=True), "kernel", None), (dict(), "plain", "backend"),
    (dict(interpret=True, dtype=jnp.float32), "plain", "dtype"),
    (dict(interpret=True, chunk=32), "plain", "chunk"),
])
def test_the_scalar_delta_rule_says_which_form_it_took(case, path, why):
    """Two forms since PR 58 (it noted no ``path`` while plain XLA was its
    only one): any count of heads is the kernels', of any width in 16s."""
    from edl_tpu.ops.gated_delta import gated_delta_rule

    wide = jax.ShapeDtypeStruct((1, 128, 3, 16), case.get("dtype", jnp.bfloat16))
    thin = jax.ShapeDtypeStruct((1, 128, 3), jnp.float32)
    noted = _new_notes("gdn_chunks", lambda: jax.eval_shape(
        lambda *a: gated_delta_rule(
            *a, chunk=case.get("chunk", 64), interpret=case.get("interpret", False)
        ), wide, wide, wide, thin, thin
    ))
    assert len(noted) == 1
    assert noted[0]["path"] == path and noted[0].get("why") == why
    assert (noted[0]["heads"], noted[0]["d_k"], noted[0]["d_v"]) == (3, 16, 16)


# -- the census of a stage, and the window's steps as a series ----------------------

TOKENS = np.zeros((8, 16), np.int32)  # a row a device of the rig


def _lm(moe=None):
    return TransformerLM(
        vocab_size=32, d_model=24, num_heads=4, num_kv_heads=2, num_layers=2,
        d_ff=16, dtype=jnp.bfloat16, remat=True, arch=ArchSpec(head_dim=8),
        moe=moe,
    )


def _lm_loss(logits, y):
    return cross_entropy_loss(logits.reshape(-1, logits.shape[-1]), y.reshape(-1))


def _trainer(lm):
    return ElasticTrainer(
        lm, optax.adamw(1e-3), _lm_loss, sample_input=TOKENS, log=False
    )


def _batches(steps):
    return lambda epoch: ((TOKENS, TOKENS) for _ in range(steps))


def _gauge():
    series = obs_metrics.default_registry().snapshot()["edl_train_step_program_count"]
    return {re.search(r'what="(\w+)"', k).group(1): v for k, v in series.items()}


@pytest.fixture
def ring():
    tracer = obs_trace.get_tracer()
    tracer.clear()
    yield tracer
    obs_profile.set_step_executable(None)


@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_a_stage_leaves_the_census_of_its_step(ring, kind):
    moe = MoESpec(num_experts=4, top_k=2, d_ff=16) if kind == "moe" else None
    _trainer(_lm(moe)).fit(_batches(20), epochs=1)
    # the thread is done when fit returns
    assert not [t for t in threading.enumerate() if t.name == "edl-step-census"]
    (instant,) = _named(ring, "step_program")
    args = instant["args"]
    # counted from the same executable, by other means
    program = obs_profile.step_program()
    text = obs_profile._step.compiled.as_text()
    run = [n for n, home in program.home.items() if home in program.run]
    assert args["loops"] == len(
        [n for n in run if re.search(r"^\s*(?:ROOT )?%%?%s = .* while\(" % re.escape(n),
                                     text, re.M)]
    )
    assert args["instructions"] == sum(
        row["instructions"] for row in args["parts"].values()
    ) == len([n for n in run if program.opcode[n] != "parameter"])
    assert args["matmuls"] == sum(r.get("matmuls", 0) for r in args["parts"].values())
    assert args["kernel_calls"] == 0 and args["kernels"] == {}   # a CPU's step
    assert args["collectives"] > 0   # the rig's eight devices average the gradient
    assert set(args["plan"]) == {"argument", "output", "temp", "alias", "generated_code"}
    assert args["text_s"] >= 0 and args["parse_s"] >= 0 and args["text_bytes"] == len(text)
    # on the CPU every dispatching op took its plain form, a note a shape: the
    # attention layers' one, and the expert layers' three grouped matmuls'
    # shapes (gate and up share theirs)
    fallbacks = sorted((f["note"], f["why"]) for f in args["fallbacks"])
    want = [("attn_route", "backend")] + [("gmm_tiles", "backend")] * 2 * (kind == "moe")
    assert fallbacks == want and args["plain_fallbacks"] == len(want)
    parts = {key.split("/")[0] for key in args["parts"]}
    assert {"attn", "norm", "embed", "head", "loss"} <= parts
    assert ({"moe_route", "moe_experts", "moe_combine"} <= parts) == (kind == "moe")
    assert ("mlp" in parts) == (kind == "dense")
    # the gauges are those of the instant's totals that a listed metric reads
    assert _gauge() == {
        what: float(args[what]) for what in
        ("matmuls", "unplaced_matmuls", "kernel_calls", "loops", "plain_fallbacks")
    }
    assert {"instructions", "conditionals", "collectives", "fused_dw"} <= set(args)
    # what is traced after fit (a check's float32 call) is not in the stage's count
    jax.eval_shape(
        lambda q: A.attention(q, q, q),
        jax.ShapeDtypeStruct((1, 1, 512, 8), jnp.float32),
    )
    assert _gauge()["plain_fallbacks"] == len(want)
    # the ring exports with a table as an argument
    doc = json.loads(json.dumps({"traceEvents": ring.to_events()}, default=str))
    (again,) = [e for e in doc["traceEvents"] if e["name"] == "step_program"]
    assert again["args"]["parts"] == args["parts"]


def test_the_loop_does_not_wait_for_the_census(ring, monkeypatch):
    """The census blocks until the loop has gone on to its second epoch: a loop
    that waited for it would never get there."""
    moved_on = threading.Event()
    seen = []
    publish = obs_profile.publish_step_census

    def slow(*args, **kwargs):
        seen.append(moved_on.wait(timeout=60))
        return publish(*args, **kwargs)

    monkeypatch.setattr(obs_profile, "publish_step_census", slow)

    def data_fn(epoch):
        if epoch == 1:
            moved_on.set()
        return _batches(4)(epoch)

    _trainer(_lm()).fit(data_fn, epochs=2)
    assert seen == [True]
    assert len(_named(ring, "step_program")) == 1   # and fit's exit waited for it


def test_a_stage_that_leaves_early_does_not_wait_for_its_census(ring, monkeypatch):
    """A resize or an exception: the stage's exit waits for no text of seconds,
    the next stage's ``set_step_executable`` does not wait behind the parse, and
    the dropped census is published into nobody's ring."""
    release = threading.Event()
    text = obs_profile.executable_text

    def slow(compiled):
        assert release.wait(timeout=60)
        return text(compiled)

    monkeypatch.setattr(obs_profile, "executable_text", slow)
    monkeypatch.setattr(train_loop, "CENSUS_JOIN_S", 60.0)

    def data_fn(epoch):
        yield from _batches(3)(epoch)
        raise RuntimeError("the stage leaves")

    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="the stage leaves"):
        _trainer(_lm()).fit(data_fn, epochs=1)
    (census,) = [t for t in threading.enumerate() if t.name == "edl-step-census"]
    assert census.is_alive()   # still inside the text
    obs_profile.set_step_executable(None)   # the next stage's: no wait either
    assert time.monotonic() - t0 < 30
    release.set()
    census.join(timeout=60)
    assert not census.is_alive() and not _named(ring, "step_program")


def test_a_census_that_fails_takes_nothing_down(ring, monkeypatch, capsys):
    def broken(*args, **kwargs):
        raise RuntimeError("no text")

    monkeypatch.setattr(obs_profile, "publish_step_census", broken)
    _trainer(_lm()).fit(_batches(3), epochs=1)
    assert not _named(ring, "step_program")
    assert "no census of the compiled step (no text)" in capsys.readouterr().err


def test_a_second_stage_in_one_process_leaves_its_own_census_and_notes(ring):
    """``_hot_restage``'s path: ``_fit_stage`` again in the same process. The
    nine cached writers noted nothing the second time."""
    trainer = _trainer(_lm())
    for _ in range(2):
        trainer._fit_stage(_batches(3), 1, None, None)
    assert len(_named(ring, "step_program")) == 2
    routes = _named(ring, "attn_route")
    assert len(routes) == 2 and routes[0]["args"] == routes[1]["args"]
    assert len(_named(ring, "grad_apart")) == 2
    assert len(_named(ring, "dw_apart")) == 2 * 4   # q, k, v, o: a stage each
    first, second = _named(ring, "step_program")
    assert first["args"]["instructions"] == second["args"]["instructions"]
    assert second["args"]["plain_fallbacks"] == 1


def test_every_mark_carries_what_the_model_sowed(ring):
    _trainer(_lm(MoESpec(num_experts=4, top_k=2, d_ff=16))).fit(
        _batches(20), epochs=1
    )
    marks = _named(ring, "step_retired")
    assert len(marks) >= 3   # the first fetch, every eighth step, the epoch's sync
    for mark in marks:
        gauges = mark["args"]["gauges"]
        assert {"aux_loss", "moe_load_max"} <= set(gauges)
        assert all(isinstance(v, float) for v in gauges.values())
        assert gauges["moe_load_max"] >= 1.0
    paced = [m["args"] for m in marks if "seconds_per_step" in m["args"]]
    assert paced and all(a["steps"] >= 1 for a in paced)
    # the last mark's are what the gauges were left at
    registry = obs_metrics.default_registry().snapshot()
    assert registry["edl_train_moe_load_max"][""] == marks[-1]["args"]["gauges"]["moe_load_max"]


def test_a_mark_without_sown_gauges_is_what_it_was():
    tracer = obs_trace.SpanTracer("test")
    clock = train_loop.RetireClock(tracer)
    clock.start_epoch()
    clock.mark(3, 1.0, epoch=0)
    clock.mark(11, 1.8, epoch=0, gauges={"moe_held_load_max": np.float32(5.5)})
    first, second = _named(tracer, "step_retired")
    assert first["args"] == {"step": 3, "epoch": 0}
    assert second["args"] == {
        "step": 11, "epoch": 0, "steps": 8,
        "seconds_per_step": pytest.approx(0.1), "gauges": {"moe_held_load_max": 5.5},
    }


# -- the ring's export with a table in it --------------------------------------------


def test_merge_and_startup_split_read_an_export_that_holds_the_census(ring, tmp_path):
    import subprocess
    import sys

    from edl_tpu.obs import merge as obs_merge

    _trainer(_lm()).fit(_batches(3), epochs=1)
    path = ring.export(str(tmp_path / "worker-0.trace.json"))
    assert path is not None
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    (census,) = [e for e in events if e["name"] == "step_program"]
    assert isinstance(census["args"]["parts"], dict)
    merged = str(tmp_path / "merged.json")
    assert obs_merge.main([path, "-o", merged]) == 0
    with open(merged) as f:
        assert [e for e in json.load(f)["traceEvents"] if e["name"] == "step_program"]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "benchmark/tools/startup_split.py", path], cwd=root,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "step_relower" in proc.stdout
