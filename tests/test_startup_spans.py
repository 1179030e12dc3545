"""A worker's start in the span ring: jax's own trace / lower / compile events
as ``jit_trace`` / ``jit_lower`` / ``jit_compile`` (``train/aot.py:
instrument_compile_spans``), the persistent cache's read as ``cache_load``,
the step's second lowering as ``step_relower``, and the Pallas bodies a trace
enters as ``kernel_trace``."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from edl_tpu.models import MLP
from edl_tpu.obs import trace as obs_trace
from edl_tpu.ops import causal_conv_silu, flash_attention, grouped_matmul
from edl_tpu.train import ElasticTrainer, aot, mse_loss
from edl_tpu.train import loop as train_loop
from edl_tpu.train.context import enable_compilation_cache
from edl_tpu.train.step import create_state, make_train_step

JAX_SPANS = ("jit_trace", "jit_lower", "jit_compile")


def _spans(events, name):
    return [e for e in events if e.get("name") == name and e.get("ph") == "X"]


def _inside(child, parent, slack_us=1.0):
    return (parent["ts"] - slack_us <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + slack_us)


def _records(epoch, n=64, d=8):
    rs = np.random.RandomState(100 + epoch)
    w = np.linspace(-1, 1, d)[:, None].astype(np.float32)
    for _ in range(n):
        x = rs.randn(d).astype(np.float32)
        yield x, (x @ w).astype(np.float32)


# -- one fit, on a cold persistent cache ----------------------------------------


@pytest.fixture(scope="module")
def fit_ring(tmp_path_factory):
    """The ring after one epoch of eight steps of a toy model, with the
    program's compile cache placed in an empty directory."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    enable_compilation_cache(str(tmp_path_factory.mktemp("xla")))
    # jax opens its cache once: one an earlier test of this worker left open
    # on another directory would answer for the empty one
    compilation_cache.reset_cache()
    tracer = obs_trace.get_tracer()
    tracer.clear()
    try:
        ElasticTrainer(
            MLP(hidden=(16,), features=1), optax.sgd(0.05), mse_loss,
            sample_input=np.zeros((8, 8), np.float32), batch_size=8, log=False,
        ).fit(_records, epochs=1)
        yield {
            "events": tracer.to_events(),
            "loop_tid": threading.get_ident() & 0x7FFFFFFF,
        }
    finally:
        # the rest of this worker's tests run uncached, as they would have
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize(
    "name", JAX_SPANS + ("cache_load", "step_relower", "step_launch", "first_step",
                         "state_init")
)
def test_fit_leaves_the_span_of_a_start(fit_ring, name):
    spans = _spans(fit_ring["events"], name)
    assert spans, name
    if name in ("first_step", "state_init", "step_relower", "step_launch"):
        assert len(spans) == 1


@pytest.mark.parametrize("name", JAX_SPANS)
def test_a_jax_span_names_its_function_and_its_thread(fit_ring, name):
    for span in _spans(fit_ring["events"], name):
        assert isinstance(span["args"]["fun"], str) and span["args"]["fun"], span
    # the step is traced, lowered and compiled by the loop's own thread
    step = [s for s in _spans(fit_ring["events"], name)
            if "step" in s["args"]["fun"]]
    assert step and {s["tid"] for s in step} == {fit_ring["loop_tid"]}


def test_the_steps_three_jax_spans_lie_in_first_step_and_sum_to_less(fit_ring):
    (first,) = _spans(fit_ring["events"], "first_step")
    total = 0.0
    for name in JAX_SPANS:
        # the step's own: the outermost span of its kind in the interval
        inside = [s for s in _spans(fit_ring["events"], name) if _inside(s, first)]
        assert inside, name
        outer = max(inside, key=lambda s: s["dur"])
        assert "step" in outer["args"]["fun"], outer
        total += outer["dur"]
    assert 0 < total <= first["dur"]
    # trace, then lower, then compile
    order = [max((s for s in _spans(fit_ring["events"], n) if _inside(s, first)),
                 key=lambda s: s["dur"])["ts"] for n in JAX_SPANS]
    assert order == sorted(order)


def test_first_steps_data_wait_and_dispatch_are_its_children_too(fit_ring):
    (first,) = _spans(fit_ring["events"], "first_step")
    for name in ("data_wait", "step_dispatch"):
        assert [s for s in _spans(fit_ring["events"], name) if _inside(s, first)]
    # the backend compile happens inside the dispatch that asked for it
    (dispatch,) = [s for s in _spans(fit_ring["events"], "step_dispatch")
                   if _inside(s, first)]
    assert [s for s in _spans(fit_ring["events"], "jit_compile")
            if _inside(s, dispatch)]


def test_step_launch_runs_from_the_steps_compile_to_its_dispatchs_end(fit_ring):
    (first,) = _spans(fit_ring["events"], "first_step")
    (launch,) = _spans(fit_ring["events"], "step_launch")
    (dispatch,) = [s for s in _spans(fit_ring["events"], "step_dispatch")
                   if _inside(s, first)]
    compiled = max((s for s in _spans(fit_ring["events"], "jit_compile")
                    if _inside(s, dispatch)), key=lambda s: s["ts"])
    assert launch["tid"] == fit_ring["loop_tid"]
    assert launch["ts"] == pytest.approx(compiled["ts"] + compiled["dur"], abs=1.0)
    assert launch["ts"] + launch["dur"] == pytest.approx(
        dispatch["ts"] + dispatch["dur"], abs=1.0)


def test_a_dispatch_that_compiled_nothing_has_no_launch_to_name():
    tracer = obs_trace.SpanTracer("test")
    now = time.monotonic()
    tracer.record("jit_compile", now - 9.0, 1.0, fun="jit_init_state")
    tracer.record("step_dispatch", now - 5.0, 0.002, epoch=0, step=0)
    train_loop._record_step_launch(tracer)
    assert not _spans(tracer.to_events(), "step_launch")
    tracer.record("jit_compile", now - 3.0, 1.0, fun="jit_step")
    tracer.record("step_dispatch", now - 4.0, 2.5, epoch=0, step=0)
    train_loop._record_step_launch(tracer)
    (launch,) = _spans(tracer.to_events(), "step_launch")
    assert launch["dur"] == pytest.approx(0.5e6, abs=10.0)


def test_state_inits_jax_spans_lie_inside_it(fit_ring):
    (init,) = _spans(fit_ring["events"], "state_init")
    for name in JAX_SPANS:
        assert [s for s in _spans(fit_ring["events"], name) if _inside(s, init)], name


def test_cache_load_says_which_module_and_whether_it_hit(fit_ring):
    loads = _spans(fit_ring["events"], "cache_load")
    for load in loads:
        assert isinstance(load["args"]["hit"], bool), load
        assert load["args"]["module"].startswith("jit_"), load
        assert load["args"]["ladder"] is False
    # an empty directory: the step's program was looked for and not found
    step = [s for s in loads if "step" in s["args"]["module"]]
    assert step and not step[0]["args"]["hit"]
    # and the read is part of the backend compile that asked for it
    compiles = _spans(fit_ring["events"], "jit_compile")
    assert all(any(_inside(load, c) for c in compiles) for load in loads)


def test_missed_modules_reads_the_cache_load_spans(fit_ring, monkeypatch):
    tracer = obs_trace.SpanTracer("test")
    for span in _spans(fit_ring["events"], "cache_load"):
        tracer.record("cache_load", time.monotonic(), 0.0, **span["args"])
    monkeypatch.setattr(obs_trace, "get_tracer", lambda *a: tracer)
    missed = aot.missed_modules()
    assert missed and any("step" in m for m in missed)


def test_step_relower_follows_first_step_and_says_it_compiled(fit_ring):
    (first,) = _spans(fit_ring["events"], "first_step")
    (relower,) = _spans(fit_ring["events"], "step_relower")
    assert relower["ts"] >= first["ts"] + first["dur"] - 1.0
    assert relower["args"] == {"compiled": True}
    # it is set-up of the first train_step's successor, not of a later step
    second = [s for s in _spans(fit_ring["events"], "train_step")
              if s["args"]["step"] == 1]
    assert second and relower["ts"] + relower["dur"] <= second[0]["ts"] + second[0]["dur"]


# -- the listener ------------------------------------------------------------------


def test_installing_the_listener_twice_records_once():
    aot.instrument_compile_spans()
    aot.instrument_compile_spans()
    tracer = obs_trace.get_tracer()

    def only_once_here(x):
        return x * 3 + 1

    tracer.clear()
    jax.jit(only_once_here)(jnp.ones((3,)))
    mine = [e["name"] for e in tracer.to_events()[1:]
            if e["name"] in JAX_SPANS and "only_once_here" in e["args"]["fun"]]
    # (a trace under a millisecond is left out)
    assert sorted(set(mine)) == sorted(mine)
    assert {"jit_lower", "jit_compile"} <= set(mine)


@pytest.mark.parametrize("name,seconds,kept", [
    ("jit_trace", 0.0005, False), ("jit_trace", 0.002, True),
    ("jit_lower", 0.0005, True), ("jit_compile", 0.0005, True),
])
def test_only_a_trace_under_a_millisecond_is_left_out(name, seconds, kept):
    (event,) = [k for k, v in aot.JAX_COMPILE_SPANS.items() if v == name]
    tracer = obs_trace.get_tracer()
    tracer.clear()
    now = time.time()
    aot._on_jax_time_span(event, now, now + seconds, fun_name="f")
    aot._on_jax_time_span("/jax/some/other/event", now, now + 1.0)
    assert [e["name"] for e in tracer.to_events()[1:]] == ([name] if kept else [])


def test_a_wall_clock_span_lands_where_a_monotonic_one_would():
    tracer = obs_trace.SpanTracer("test")
    wall, mono = time.time(), time.monotonic()
    tracer.record_wall("by_wall", wall, wall + 0.25, fun="f")
    tracer.record("by_mono", mono, 0.25)
    by_wall, by_mono = tracer.to_events()[1:]
    assert by_wall["args"] == {"fun": "f"}
    assert abs(by_wall["dur"] - 250e3) < 1.0
    assert abs(by_wall["ts"] - by_mono["ts"]) < 50e3  # the two clocks, read 1 apart


def test_a_jax_span_under_an_open_operation_stitches_into_it(monkeypatch):
    monkeypatch.setenv("EDL_TRACE_PROPAGATE", "1")
    obs_trace.PROPAGATION.rearm()
    aot.instrument_compile_spans()
    tracer = obs_trace.get_tracer()
    ctx = obs_trace.begin_process_op("restage", "stage-of-the-test")
    try:
        tracer.clear()
        jax.jit(lambda x: x - 7)(jnp.ones((5,)))
        mine = [e for e in tracer.to_events()[1:] if e["name"] in JAX_SPANS]
    finally:
        obs_trace.end_process_op()
        monkeypatch.delenv("EDL_TRACE_PROPAGATE")
        obs_trace.PROPAGATION.rearm()
    assert mine
    for span in mine:
        assert span["args"]["trace_id"] == ctx.trace_id
        assert span["args"]["parent_id"] == ctx.span_id


def test_a_new_shape_after_the_first_step_leaves_a_second_jit_compile():
    """The operator's recompile signal: a ``jit_compile`` of the step after
    ``first_step``."""
    aot.instrument_compile_spans()
    tracer = obs_trace.get_tracer()
    model = MLP(hidden=(4,), features=1)
    state = create_state(
        model, jax.random.PRNGKey(0), np.zeros((4, 8), np.float32), optax.sgd(0.1)
    )
    step = make_train_step(mse_loss, numerics=False)
    tracer.clear()

    def compiles():
        return [e for e in tracer.to_events()[1:]
                if e["name"] == "jit_compile" and "step" in e["args"]["fun"]]

    for rows, expected in ((4, 1), (4, 1), (6, 2), (6, 2)):
        batch = (jnp.ones((rows, 8)), jnp.ones((rows, 1)))
        state, _ = step(state, batch)
        assert len(compiles()) == expected, (rows, compiles())
    first, second = compiles()
    assert second["ts"] >= first["ts"] + first["dur"]


# -- the kernels' bodies ---------------------------------------------------------------


def _attention(window):
    q = jnp.ones((1, 2, 128, 16), jnp.float32)
    return jax.jit(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=window).sum(),
        argnums=(0, 1, 2),
    )), (q, q, q)


def _conv():
    x, w = jnp.ones((1, 128, 32), jnp.bfloat16), jnp.ones((4, 32), jnp.float32)
    return jax.jit(jax.grad(
        lambda x, w: causal_conv_silu(x, w, interpret=True).astype(jnp.float32).sum(),
        argnums=(0, 1),
    )), (x, w)


def _experts():
    lhs, rhs = jnp.ones((64, 16), jnp.float32), jnp.ones((2, 16, 16), jnp.float32)
    sizes = jnp.array([32, 32], jnp.int32)
    return jax.jit(jax.grad(
        lambda lhs, rhs: grouped_matmul(
            lhs, rhs, sizes, implementation="pallas", interpret=True
        ).sum(),
        argnums=(0, 1),
    )), (lhs, rhs)


@pytest.mark.parametrize("make,kernels", [
    (lambda: _attention(None), ["flash2_fwd", "flash2_bwd"]),
    (lambda: _attention(64), ["flash2_fwd", "flash2_bwd"]),
    (_conv, ["causal_conv_fwd", "causal_conv_bwd"]),
    (_experts, ["gmm", "gmm_dlhs", "tgmm"]),
], ids=["flash2", "flash2_window", "causal_conv", "megablox"])
def test_kernel_trace_once_a_shape_and_never_from_the_compiled_function(make, kernels):
    aot.instrument_compile_spans()
    tracer = obs_trace.get_tracer()
    fn, operands = make()

    tracer.clear()
    jax.block_until_ready(fn(*operands))
    bodies = _spans(tracer.to_events(), "kernel_trace")
    assert [e["args"]["kernel"] for e in bodies] == kernels
    # a body is traced while the function that calls it is
    outer = [e for e in _spans(tracer.to_events(), "jit_trace")
             if all(_inside(b, e) for b in bodies)]
    assert outer and max(e["dur"] for e in outer) >= sum(b["dur"] for b in bodies)
    tracer.clear()
    jax.block_until_ready(fn(*operands))
    assert len(tracer) == 0
