"""One host timeline for the train path: ``SpanTracer.span`` into the ring
and the profiler's trace, the spans of the step loop and the prefetch feeder,
the step program's named phases (``obs/profile.py:step_phases``), and the
step time the device paced (``train/loop.py:RetireClock``)."""

import contextlib
import glob
import os
import queue
import re
import subprocess
import sys
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from edl_tpu.data import prefetch as data_prefetch
from edl_tpu.models import MLP
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import profile as obs_profile
from edl_tpu.obs import trace as obs_trace
from edl_tpu.train import ElasticTrainer, aot, mse_loss
from edl_tpu.train import loop as train_loop
from edl_tpu.train import step as train_step_module
from edl_tpu.train.step import create_state, make_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LOOP_SPANS = ("data_wait", "step_dispatch", "numerics_fetch", "epoch_sync",
              "epoch_end_hook", "ckpt_stamp")
FEEDER_SPANS = ("feed_next", "feed_put", "feed_queue")


# -- one span call, two sinks -------------------------------------------------


def test_a_span_under_a_profile_is_in_the_ring_and_in_the_xplane(tmp_path):
    tracer = obs_trace.SpanTracer("test")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("two_sinks", k=1):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (ring,) = [e for e in tracer.to_events() if e.get("name") == "two_sinks"]
    assert ring["args"] == {"k": 1} and ring["dur"] >= 2000
    (path,) = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    data = jax.profiler.ProfileData.from_file(path)
    host = [
        e for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name == "edl:two_sinks"
    ]
    assert len(host) == 1 and host[0].duration_ns >= 2e6


def test_record_after_the_fact_stays_in_the_ring_only():
    tracer = obs_trace.SpanTracer("test")
    tracer.record("late", time.monotonic(), 0.001)
    assert [e["name"] for e in tracer.to_events()[1:]] == ["late"]


def test_a_process_without_jax_records_spans_without_importing_it():
    code = (
        "import sys\n"
        "from edl_tpu.obs import trace\n"
        "with trace.span('no_jax', k=1):\n"
        "    pass\n"
        "names = [e['name'] for e in trace.get_tracer().to_events()]\n"
        "assert 'no_jax' in names, names\n"
        "assert 'jax' not in sys.modules\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


# -- the spans of a fit ---------------------------------------------------------


def _records(epoch, n=192, d=8):
    rs = np.random.RandomState(100 + epoch)
    w = np.linspace(-1, 1, d)[:, None].astype(np.float32)
    for _ in range(n):
        x = rs.randn(d).astype(np.float32)
        yield x, (x @ w).astype(np.float32)


class _AskedQueue(queue.Queue):
    """The prefetch queue of one epoch; ``asked`` is set once the consumer
    has looked into it (``prefetch_to_device`` reads ``empty()`` before each
    ``get()``: that reading is what the starvation counter counts)."""

    made = []

    def __init__(self, maxsize=0):
        super().__init__(maxsize)
        self.asked = threading.Event()
        _AskedQueue.made.append(self)

    def empty(self):
        self.asked.set()
        return super().empty()


def _records_after_the_consumer_asked(epoch):
    """``_records``, held back until the loop has asked this epoch's queue
    for its first batch: that batch is then waited for in every
    interleaving of the loop and the feeder."""
    assert _AskedQueue.made[-1].asked.wait(timeout=60)
    yield from _records(epoch)


@pytest.fixture(scope="module")
def fit_events(tmp_path_factory):
    """The ring after two epochs of 24 steps of a toy model, with a
    callback and a save after each, and how far the step-time histogram
    and the prefetch counters moved."""
    with pytest.MonkeyPatch.context() as patch:
        # the name `queue` as prefetch.py sees it, not the module itself:
        # other planes make queues of their own during a fit
        patch.setattr(data_prefetch, "queue", types.SimpleNamespace(
            Queue=_AskedQueue, Full=queue.Full, Empty=queue.Empty,
        ))
        yield _fit_events(tmp_path_factory)
    _AskedQueue.made.clear()


def _fit_events(tmp_path_factory):
    tracer = obs_trace.get_tracer()
    tracer.clear()
    registry = obs_metrics.default_registry()
    before = registry.snapshot()
    trainer = ElasticTrainer(
        MLP(hidden=(16,), features=1), optax.sgd(0.05), mse_loss,
        sample_input=np.zeros((8, 8), np.float32), batch_size=8,
        ckpt_dir=str(tmp_path_factory.mktemp("ckpt")), log=False,
    )
    loop_tid = threading.get_ident() & 0x7FFFFFFF
    trainer.fit(
        _records_after_the_consumer_asked, epochs=2,
        on_epoch_end=lambda e, m: None,
    )
    after = registry.snapshot()

    def grown(name, key=""):
        return after.get(name, {}).get(key, 0.0) - before.get(name, {}).get(key, 0.0)

    return {
        "events": [e for e in tracer.to_events() if e.get("ph") in ("X", "i")],
        "loop_tid": loop_tid,
        "grown": grown,
    }


def _named(fit_events, name):
    return [e for e in fit_events["events"] if e["name"] == name]


@pytest.mark.parametrize("name", LOOP_SPANS + FEEDER_SPANS)
def test_fit_leaves_the_span_with_its_epoch_and_step(fit_events, name):
    spans = _named(fit_events, name)
    if name == "numerics_fetch":
        # the probe's closing flush, after the last epoch, belongs to none
        assert "epoch" not in spans.pop()["args"]
    assert spans, name
    key = "batch" if name in FEEDER_SPANS else "step"
    for span in spans:
        assert span["ph"] == "X" and span["args"]["epoch"] in (0, 1), span
        if name != "epoch_end_hook":
            assert isinstance(span["args"][key], int), span
    on_loop = {s["tid"] == fit_events["loop_tid"] for s in spans}
    # the feeder's spans carry the feeder's thread, not the loop's
    assert on_loop == {name not in FEEDER_SPANS}
    if name in ("data_wait", "step_dispatch", "feed_put", "feed_queue"):
        # batch k of an epoch is step k: 24 of each an epoch (data_wait
        # once more, for the pull that found the end)
        per_epoch = [s["args"][key] for s in spans
                     if s["args"]["epoch"] == 1 and "error" not in s["args"]]
        assert per_epoch == list(range(24))


def test_train_steps_children_never_sum_to_more_than_it(fit_events):
    steps = _named(fit_events, "train_step")
    assert len(steps) == 48
    children = [
        e for name in ("data_wait", "step_dispatch", "numerics_fetch")
        for e in _named(fit_events, name)
        # not the pull that found the end, nor the probe's closing flush
        if "error" not in e["args"] and "epoch" in e["args"]
    ]
    for parent in steps:
        start, end = parent["ts"], parent["ts"] + parent["dur"]
        inside = [c for c in children if start <= c["ts"] and c["ts"] + c["dur"] <= end + 1]
        names = {c["name"] for c in inside}
        assert {"data_wait", "step_dispatch"} <= names, parent
        assert sum(c["dur"] for c in inside) <= parent["dur"] + 1, parent
    # every child lies inside some train_step: none is counted twice or lost
    assert sum(
        1 for c in children
        if any(p["ts"] <= c["ts"] and c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1
               for p in steps)
    ) == len(children)


def test_the_step_time_is_observed_once_a_stretch_not_once_a_step(fit_events):
    marks = _named(fit_events, "step_retired")
    derived = [m for m in marks if "seconds_per_step" in m["args"]]
    # EDL_NUMERICS_EVERY 8 over 24 steps an epoch: fetches after calls 8,
    # 16, 24 (the first call of the stage fetches too), then the sync
    assert len(derived) >= 4 and all(m["ph"] == "i" for m in marks)
    assert all(m["args"]["seconds_per_step"] > 0 for m in derived)
    assert sum(m["args"]["steps"] for m in derived) <= 48
    assert fit_events["grown"]("edl_train_step_seconds", "count") == len(derived)
    # a mark never reaches across an epoch boundary
    for epoch in (0, 1):
        first = min(
            (m for m in marks if m["args"]["epoch"] == epoch),
            key=lambda m: m["ts"],
        )
        assert "seconds_per_step" not in first["args"]


def test_the_prefetch_queue_counts_batches_and_starvation(fit_events):
    assert fit_events["grown"]("edl_data_prefetch_batches_total") == 48
    starved = fit_events["grown"]("edl_data_prefetch_starved_total")
    assert 2 <= starved <= 48  # at least each epoch's first batch


# -- a step time the device paced ---------------------------------------------


def test_marks_under_a_fake_10ms_step_give_10ms_while_dispatch_is_bimodal():
    """A host that dispatches in 0.2 ms and waits for the device at every
    eighth call (for the step before: the numerics plane's cadence), a
    device that retires one step every 10 ms. On a made-up clock."""
    tracer = obs_trace.SpanTracer("test")
    clock = train_loop.RetireClock(tracer)
    clock.start_epoch()
    histogram = obs_metrics.default_registry().get("edl_train_step_seconds")
    count_before = histogram.count()
    host, retired_at, intervals, per_step = 0.0, [], [], []
    for k in range(64):
        t_prev = host
        host += 0.0002                                   # the dispatch
        device_free = retired_at[-1] if retired_at else 0.0
        retired_at.append(max(device_free, host) + 0.010)
        if (k + 1) % 8 == 0:
            host = max(host, retired_at[k - 1])          # the fetch returns
            got = clock.mark(k - 1, host, epoch=0)
            if got is not None:
                per_step.append(got)
        intervals.append(host - t_prev)
    assert len(per_step) == 7
    assert all(abs(v - 0.010) <= 0.001 for v in per_step), per_step
    short = [v for v in intervals if v < 0.001]
    long = [v for v in intervals if v > 0.050]
    assert len(short) == 56 and len(long) == 8           # bimodal: no 10 ms
    assert histogram.count() - count_before == 7
    marks = [e for e in tracer.to_events() if e.get("name") == "step_retired"]
    assert [m["args"]["step"] for m in marks] == list(range(6, 64, 8))
    assert [m["args"].get("steps") for m in marks] == [None] + [8] * 7


# -- names inside the step program ----------------------------------------------


def _toy_step_and_inputs():
    state = create_state(
        MLP(hidden=(16,), features=1), jax.random.PRNGKey(0),
        np.zeros((8, 8), np.float32), optax.adamw(1e-3),
    )
    rs = np.random.RandomState(0)
    batch = (rs.randn(8, 8).astype(np.float32), rs.randn(8, 1).astype(np.float32))
    return state, batch


def test_step_phases_puts_an_instruction_in_each_phase():
    state, batch = _toy_step_and_inputs()
    step = make_train_step(mse_loss, numerics=True)
    obs_profile.set_step_executable(step.lower(state, batch).compile())
    try:
        table = obs_profile.step_phases()
    finally:
        obs_profile.set_step_executable(None)
    assert set(table.values()) <= set(obs_profile.PHASES)
    for phase in ("forward", "backward", "optimizer", "numerics"):
        assert phase in table.values(), phase
    assert obs_profile.step_phases() == {}  # no step: no table


@pytest.mark.parametrize("op_name,phase", [
    ("jit(step)/jvp(forward)/MLP/Dense_0/dot_general", "forward"),
    ("jit(step)/transpose(jvp(forward))/MLP/Dense_0/transpose", "backward"),
    ("jit(step)/transpose(jvp(forward))/checkpoint/rematted_computation/"
     "jvp(forward)/mul", "backward"),
    ("jit(step)/grad_mean/div", "backward"),
    ("jit(step)/optimizer/jit(_where)/select_n", "optimizer"),
    ("jit(step)/numerics/reduce_sum", "numerics"),
    ("jit(step)/transpose(jvp(forward))/mul;jit(step)/numerics/sqrt", "backward"),
    ("jit(step)/slice", "other"),
    ("state.params['Dense_0']['bias']", "other"),
    ("jit(forward_pass)/dot_general", "other"),
])
def test_phase_of_an_op_name(op_name, phase):
    assert obs_profile.phase_of(op_name) == phase


HLO_BEFORE_THE_SCOPES = """
HloModule jit_step

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(step)/add"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="state.params"}
  ROOT %fusion = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation
}
"""


def test_an_executable_older_than_the_scopes_gives_no_table():
    assert obs_profile.phases_of_hlo(HLO_BEFORE_THE_SCOPES) == {}
    named = HLO_BEFORE_THE_SCOPES.replace(
        "jit(step)/add", "jit(step)/jvp(forward)/add"
    )
    # a fusion without a name of its own takes its root's
    assert obs_profile.phases_of_hlo(named) == {
        "add.1": "forward", "fusion": "forward", "a": "other",
    }


HLO_WITH_AN_UPDATE_PASS = """
HloModule jit_step

%fused_pass (g: f32[8,8], p: f32[8,8]) -> (f32[], f32[8,8]) {
  %g = f32[8,8]{1,0} parameter(0)
  %p = f32[8,8]{1,0} parameter(1)
  %sq = f32[8,8]{1,0} multiply(%g, %g), metadata={op_name="jit(step)/numerics/square"}
  %norm = f32[] reduce(%sq), metadata={op_name="jit(step)/numerics/reduce_sum"}
  %new = f32[8,8]{1,0} add(%p, %g), metadata={op_name="jit(step)/optimizer/add"}
  ROOT %tuple = (f32[], f32[8,8]{1,0}) tuple(%norm, %new)
}

%fused_dw (x: f32[8,8], p: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  %p = f32[8,8]{1,0} parameter(1)
  %dw = f32[8,8]{1,0} convolution(%x, %x), metadata={op_name="jit(step)/transpose(jvp(forward))/dot_general"}
  ROOT %new = f32[8,8]{1,0} add(%p, %dw), metadata={op_name="jit(step)/optimizer/add"}
}

%fused_forward (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  ROOT %y = f32[8,8]{1,0} add(%x, %x), metadata={op_name="jit(step)/jvp(forward)/add"}
}

ENTRY %main (a: f32[8,8], b: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %b = f32[8,8]{1,0} parameter(1)
  %fusion.0 = f32[8,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_forward
  %convert_reduce_fusion = (f32[], f32[8,8]{1,0}) fusion(%a, %b), kind=kLoop, calls=%fused_pass, metadata={op_name="jit(step)/numerics/reduce_sum"}
  ROOT %fusion.1 = f32[8,8]{1,0} fusion(%a, %b), kind=kOutput, calls=%fused_dw, metadata={op_name="jit(step)/transpose(jvp(forward))/dot_general"}
}
"""


def test_the_optimizers_pass_counts_as_optimizer_whatever_name_it_took():
    """A fusion of the update with the bundle's norms is named after a norm's
    reduce; one that still holds the gradient's matmul stays the matmul's."""
    assert obs_profile.update_passes_of_hlo(HLO_WITH_AN_UPDATE_PASS) == [
        "convert_reduce_fusion"
    ]
    table = obs_profile.phases_of_hlo(HLO_WITH_AN_UPDATE_PASS)
    assert table["convert_reduce_fusion"] == "optimizer"
    assert table["fusion.1"] == "backward"
    assert table["fusion.0"] == "forward"
    assert obs_profile.update_passes_of_hlo(HLO_BEFORE_THE_SCOPES) == []


HLO_WITH_A_FENCED_HEAD_PROJECTION = """
HloModule jit_step

%fused_dq (x: bf16[16,8], dy: bf16[16,4,2]) -> bf16[8,4,2] {
  %x = bf16[16,8]{1,0} parameter(0)
  %dy = bf16[16,4,2]{2,1,0} parameter(1)
  ROOT %dw = bf16[8,4,2]{2,0,1} convolution(%x, %dy), metadata={op_name="jit(step)/transpose(jvp(forward))/TransformerLM/checkpoint/layer_4/attn/q/dot_general"}
}

%fused_pass (g: bf16[8,4,2], p: f32[8,4,2]) -> (f32[], f32[8,4,2]) {
  %g = bf16[8,4,2]{2,0,1} parameter(0)
  %p = f32[8,4,2]{2,0,1} parameter(1)
  %g32 = f32[8,4,2]{2,0,1} convert(%g), metadata={op_name="jit(step)/transpose(jvp(forward))/TransformerLM/checkpoint/layer_4/attn/q/convert_element_type"}
  %sq = f32[8,4,2]{2,0,1} multiply(%g32, %g32), metadata={op_name="jit(step)/numerics/square"}
  %norm = f32[] reduce(%sq), metadata={op_name="jit(step)/numerics/reduce_sum"}
  %new = f32[8,4,2]{2,0,1} add(%p, %g32), metadata={op_name="jit(step)/optimizer/add"}
  ROOT %tuple = (f32[], f32[8,4,2]{2,0,1}) tuple(%norm, %new)
}

%fused_forward (x: bf16[16,8]) -> bf16[16,8] {
  %x = bf16[16,8]{1,0} parameter(0)
  ROOT %y = bf16[16,8]{1,0} add(%x, %x), metadata={op_name="jit(step)/jvp(forward)/add"}
}

ENTRY %main (x: bf16[16,8], dy: bf16[16,4,2], p: f32[8,4,2]) -> f32[8,4,2] {
  %x = bf16[16,8]{1,0} parameter(0)
  %dy = bf16[16,4,2]{2,1,0} parameter(1)
  %p = f32[8,4,2]{2,0,1} parameter(2)
  %fusion.0 = bf16[16,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_forward
  %fusion.3132 = bf16[8,4,2]{2,0,1} fusion(%x, %dy), kind=kOutput, calls=%fused_dq, metadata={op_name="jit(step)/transpose(jvp(forward))/TransformerLM/checkpoint/layer_4/attn/q/dot_general"}
  ROOT %convert_reduce_fusion.46 = (f32[], f32[8,4,2]{2,0,1}) fusion(%fusion.3132, %p), kind=kLoop, calls=%fused_pass, metadata={op_name="jit(step)/numerics/reduce_sum"}
}
"""


def test_the_pass_behind_a_fenced_head_projection_counts_as_optimizer():
    """The step as the chip compiles it behind ``models/transformer.py``'s
    fence (names and op_names of ``trinity_mini``'s own ``q``, shapes cut):
    the matmul that writes the bfloat16 dW is ``backward``, its reader, which
    converts it, updates the leaf and carries a norm, is ``optimizer``."""
    assert obs_profile.update_passes_of_hlo(HLO_WITH_A_FENCED_HEAD_PROJECTION) == [
        "convert_reduce_fusion.46"
    ]
    table = obs_profile.phases_of_hlo(HLO_WITH_A_FENCED_HEAD_PROJECTION)
    assert table["fusion.3132"] == "backward"
    assert table["convert_reduce_fusion.46"] == "optimizer"
    assert table["fusion.0"] == "forward"


def test_tracing_a_step_leaves_one_dw_apart_instant_a_shape():
    """``q`` and ``g`` share a shape and each has an instant of its own, as
    ``k`` and ``v``, and ``o`` has its; two layers and a second trace add none."""
    from edl_tpu.models import ArchSpec, TransformerLM
    from edl_tpu.train import cross_entropy_loss

    obs_trace.get_tracer().reset_notes()
    tracer = obs_trace.get_tracer()
    before = len([e for e in tracer.to_events() if e["name"] == "dw_apart"])
    lm = TransformerLM(
        vocab_size=32, d_model=24, num_heads=4, num_kv_heads=2, num_layers=2,
        d_ff=16, dtype=jnp.bfloat16, remat=True,
        arch=ArchSpec(head_dim=8, attn_gate=True),
    )
    tokens = np.zeros((2, 8), np.int32)
    state = create_state(lm, jax.random.PRNGKey(0), tokens, optax.adamw(1e-3))
    for _ in range(2):
        make_train_step(cross_entropy_loss, numerics=True).lower(state, (tokens, tokens))
    noted = [e for e in tracer.to_events() if e["name"] == "dw_apart"][before:]
    assert all(e["ph"] == "i" for e in noted)
    assert sorted((e["args"] for e in noted), key=lambda a: a["kernel"]) == [
        {"kernel": "g", "shape": [24, 4, 8], "dtype": "bfloat16", "bytes": 1536},
        {"kernel": "k", "shape": [24, 2, 8], "dtype": "bfloat16", "bytes": 768},
        {"kernel": "o", "shape": [4, 8, 24], "dtype": "bfloat16", "bytes": 1536},
        {"kernel": "q", "shape": [24, 4, 8], "dtype": "bfloat16", "bytes": 1536},
        {"kernel": "v", "shape": [24, 2, 8], "dtype": "bfloat16", "bytes": 768},
    ]
    # a forward-only program holds no fence and notes nothing
    obs_trace.get_tracer().reset_notes()
    jax.jit(lambda p: lm.apply({"params": p}, tokens)).lower(state.params)
    assert len([e for e in tracer.to_events() if e["name"] == "dw_apart"]) == before + 5


def test_the_step_is_bit_equal_with_and_without_the_scopes(monkeypatch):
    state, batch = _toy_step_and_inputs()
    with_scopes = make_train_step(mse_loss, numerics=True, donate=False)
    new_a, metrics_a = with_scopes(state, batch)

    @contextlib.contextmanager
    def no_scope(name):
        yield

    monkeypatch.setattr(jax, "named_scope", no_scope)
    without = make_train_step(mse_loss, numerics=True, donate=False)
    # the scope as a path component ("jit(step)/jvp(forward)/..."), not the
    # bare word: jax caches a jitted jnp helper's jaxpr with its first
    # caller's source names, and another test file's ("_flash2_forward")
    # ride into this text when both run in one worker
    lowered = without.lower(state, batch).as_text(debug_info=True)
    assert not re.search(r"[/(]forward[/)]", lowered)
    new_b, metrics_b = without(state, batch)
    for a, b in zip(jax.tree.leaves((new_a, metrics_a)),
                    jax.tree.leaves((new_b, metrics_b))):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_cache_key_constant_lists_the_scopes_the_step_enters(monkeypatch):
    """jax keys a program with its names stripped, so a PR that moves a
    scope has to bump ``STEP_SCOPES_KEY`` for old cache entries to miss."""
    entered = []
    real = jax.named_scope

    def recording(name):
        if sys._getframe(1).f_code.co_filename == train_step_module.__file__:
            entered.append(name)
        return real(name)

    monkeypatch.setattr(jax, "named_scope", recording)
    state, batch = _toy_step_and_inputs()
    make_train_step(mse_loss, numerics=True).lower(state, batch)
    names, _, count = aot.STEP_SCOPES_KEY.partition("/")
    assert sorted(set(entered)) == sorted(names.split(",")) and int(count) >= 1
    # and the masked twin enters no scope of its own
    entered.clear()
    train_step_module.make_masked_train_step(
        lambda out, y: (jnp.mean((out - y) ** 2), {})
    ).lower(state, batch, np.ones((8,), bool))
    assert set(entered) <= set(names.split(","))


def test_the_constant_is_in_every_cache_key():
    from jax._src import cache_key

    aot.enable_portable_cache_keys()
    seen = []

    class Recorder:
        def update(self, data):
            seen.append(bytes(data))

    cache_key._hash_accelerator_config(Recorder(), np.array(jax.devices()[:1]))
    assert seen[0] == aot.STEP_SCOPES_KEY.encode()
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
