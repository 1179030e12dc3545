"""A worker's start as one chain of spans in the ring: ``process_boot`` (the
OS's start of the process -> the package's first statement), ``package_import``,
``backend_init`` (``train/aot.py:instrument_backend_init``), ``trainer_init``
and ``model_trace`` (the model's Python under a trace), and their linkage into
an open restage operation."""

import contextlib
import json
import os
import subprocess
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import edl_tpu
from edl_tpu.models import MLP, ResNet, TransformerLM
from edl_tpu.obs import trace as obs_trace
from edl_tpu.ops import flash_attention
from edl_tpu.train import ElasticTrainer, aot, mse_loss
from edl_tpu.train import context as train_context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spans(events, name):
    return [e for e in events if e.get("name") == name and e.get("ph") == "X"]


def _inside(child, parent, slack_us=1.0):
    return (parent["ts"] - slack_us <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + slack_us)


@pytest.fixture
def fresh_tracer(monkeypatch):
    """``get_tracer()`` as a process's first call finds it; the worker's own
    tracer is back afterwards."""
    monkeypatch.setattr(obs_trace, "_tracer", None)
    yield
    obs_trace.reset_context()


# -- process_boot ---------------------------------------------------------------


@pytest.mark.parametrize("case", ["once", "interval", "arguments", "no_proc",
                                  "torn_stat", "stamp_is_first"])
def test_process_boot(case, fresh_tracer, monkeypatch, tmp_path):
    if case == "no_proc":
        monkeypatch.setattr(
            obs_trace, "process_start_mono",
            partial(obs_trace.process_start_mono, str(tmp_path / "no" / "stat")),
        )
        assert _spans(obs_trace.get_tracer().to_events(), "process_boot") == []
        return
    if case == "torn_stat":
        for text in ("", "12 (a b) S 1", "12 (python) S " + "x " * 30):
            (tmp_path / "stat").write_text(text)
            assert obs_trace.process_start_mono(str(tmp_path / "stat")) is None
        return
    if case == "stamp_is_first":
        # the stamp is the package's first statement: nothing of the program
        # is imported before it, and a control-plane process stays light
        out = subprocess.run(
            [sys.executable, "-c",
             "import sys, edl_tpu; print(edl_tpu.IMPORT_STAMP[1:], "
             "sorted(m for m in sys.modules if m.startswith('edl_tpu')))"],
            cwd=ROOT, capture_output=True, text=True, check=True,
        ).stdout
        modules, rest = out.split(", ", 1)
        assert "False) ['edl_tpu']" in rest and int(modules.strip("(")) < 200
        return
    before = time.monotonic()
    tracer = obs_trace.get_tracer()
    assert obs_trace.get_tracer() is tracer          # and records nothing more
    (boot,) = _spans(tracer.to_events(), "process_boot")
    if case == "once":
        return
    started = obs_trace.process_start_mono()
    if case == "interval":
        anchor_us = tracer._to_epoch_us(tracer._anchor_mono)
        assert boot["ts"] < anchor_us
        # no more than the process's age ago (a clock tick of /proc: 10 ms)
        assert boot["ts"] == pytest.approx(tracer._to_epoch_us(started), abs=2e4)
        assert 0 < before - started < 3600 * 24
        # it ends at the stamp the package took as its first statement
        assert boot["ts"] + boot["dur"] == pytest.approx(
            tracer._to_epoch_us(edl_tpu.IMPORT_STAMP[0]), abs=2e4)
    else:
        assert boot["args"] == {"modules": edl_tpu.IMPORT_STAMP[1],
                                "jax_loaded": edl_tpu.IMPORT_STAMP[2]}
        assert 0 < boot["args"]["modules"] <= len(sys.modules)


# -- package_import ---------------------------------------------------------------


def test_package_import_counts_the_modules_it_added_and_nests(fresh_tracer):
    with obs_trace.package_import("outer"):
        with obs_trace.package_import("inner"):
            sys.modules["edl_test_fake_module"] = sys
    del sys.modules["edl_test_fake_module"]
    inner, outer = _spans(obs_trace.get_tracer().to_events(), "package_import")
    assert (inner["args"], outer["args"]) == (
        {"package": "inner", "modules": 1}, {"package": "outer", "modules": 1})
    assert _inside(inner, outer)


def test_the_programs_packages_leave_their_import_in_a_fresh_process():
    out = subprocess.run(
        [sys.executable, "-c",
         "import json\n"
         "from edl_tpu.train import ElasticTrainer\n"
         "from edl_tpu.obs import trace\n"
         "print(json.dumps([e for e in trace.get_tracer().to_events()"
         " if e['name'] in ('process_boot', 'package_import')]))"],
        cwd=ROOT, capture_output=True, text=True, check=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    ).stdout
    events = json.loads(out.splitlines()[-1])
    assert events[0]["name"] == "process_boot"
    by_package = {e["args"]["package"]: e for e in events[1:]}
    loop, models = by_package["edl_tpu.train.loop"], by_package["edl_tpu.models"]
    # the loop's import brought jax and the models' package in, inside it
    assert _inside(models, loop) and loop["args"]["modules"] > models["args"]["modules"] > 0
    assert events[0]["ts"] + events[0]["dur"] <= loop["ts"]


# -- backend_init ---------------------------------------------------------------

BACKEND_SCRIPT = """
import json, os, sys
import jax
from edl_tpu.obs import trace
from edl_tpu.train import context

def init():
    context.init(context.WorkerEnv())

def arm():
    context.enable_compilation_cache(sys.argv[2])

for call in sys.argv[1].split(","):
    {"devices": jax.devices, "init": init, "arm": arm}[call]()
print(json.dumps([e for e in trace.get_tracer().to_events()
                  if e["name"] in ("backend_init", "worker_boot")]))
"""


@pytest.mark.parametrize("calls,spans", [
    ("arm,devices,devices,init", 1),   # the benchmark's order: jax.devices() first
    ("init,devices,devices", 1),       # a worker's: init() arms, the mesh asks
    ("devices,arm,init,devices", 0),   # backends up before the hook: none
])
def test_backend_init_in_a_fresh_interpreter(calls, spans, tmp_path):
    env = {k: v for k, v in os.environ.items() if not k.startswith("EDL_")}
    env.update(JAX_PLATFORMS="cpu", EDL_COMPILE_CACHE_DIR=str(tmp_path / "xla"),
               EDL_STAGE="stage-1", EDL_SPAWN_TS=str(time.time() - 1.0),
               EDL_TRACE_PROPAGATE="1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", BACKEND_SCRIPT, calls, str(tmp_path / "xla")],
        cwd=ROOT, env=env, capture_output=True, text=True,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    events = json.loads(out.stdout.splitlines()[-1])
    found = _spans(events, "backend_init")
    assert len(found) == spans
    for span in found:
        assert span["args"]["platform"] == "cpu" and span["args"]["devices"] >= 1
        assert span["dur"] > 0
    if calls.startswith("init") and found:
        # after worker_boot has ended, and a segment of the operation's own
        (boot,) = _spans(events, "worker_boot")
        op = obs_trace.op_context("restage", "stage-1")
        assert found[0]["ts"] >= boot["ts"] + boot["dur"]
        assert found[0]["args"]["trace_id"] == op.trace_id
        assert found[0]["args"]["parent_id"] == op.span_id


def test_backend_init_hook_is_installed_once_and_only_ahead_of_the_backends(monkeypatch):
    from jax._src import xla_bridge

    jax.devices()
    found = getattr(xla_bridge, aot.JAX_BACKEND_INIT)
    aot.instrument_backend_init()
    assert getattr(xla_bridge, aot.JAX_BACKEND_INIT) is found  # backends are up
    # a fit earlier in this process, ahead of the backends, left the hook in
    was = found.__wrapped__ if getattr(found, "_edl_span", False) else found
    monkeypatch.setattr(xla_bridge, "backends_are_initialized", lambda: False)
    monkeypatch.setattr(xla_bridge, aot.JAX_BACKEND_INIT, was)
    aot.instrument_backend_init()
    traced = getattr(xla_bridge, aot.JAX_BACKEND_INIT)
    assert traced is not was and traced._edl_span
    aot.instrument_backend_init()
    assert getattr(xla_bridge, aot.JAX_BACKEND_INIT) is traced
    # a jax without the private name: nothing installed, nothing raised
    monkeypatch.delattr(xla_bridge, aot.JAX_BACKEND_INIT)
    aot.instrument_backend_init()
    assert not hasattr(xla_bridge, aot.JAX_BACKEND_INIT)


# -- model_trace ----------------------------------------------------------------

TOKENS = np.zeros((1, 64), np.int32)


def _toy_lm(**kw):
    # flash_attention runs its kernels on every backend (here interpreted); a
    # shape no other test traces: a body is traced once a shape and process
    return TransformerLM(vocab_size=48, d_model=24, num_heads=3, num_layers=2,
                         d_ff=40, attention_fn=flash_attention, **kw)


@pytest.fixture(scope="module")
def lm_params():
    return jax.jit(_toy_lm().init)(jax.random.PRNGKey(0), TOKENS)


def test_a_traced_lm_leaves_one_model_trace_a_part_and_layer(lm_params):
    model = _toy_lm()
    tracer = obs_trace.get_tracer()
    tracer.clear()
    jax.jit(lambda p, t: model.apply(p, t)).lower(lm_params, TOKENS)
    events = tracer.to_events()
    parts = [
        tuple(e["args"].get(k) for k in ("part", "layer", "mixer", "ffn"))
        for e in _spans(events, "model_trace")
    ]
    assert parts == [
        ("embed", None, None, None),
        ("block", "layer_0", "attn", "mlp"),
        ("block", "layer_1", "attn", "mlp"),
        ("head", None, None, None),
    ]
    # each block's call site traces the kernel's body, inside the block's span
    bodies = _spans(events, "kernel_trace")
    blocks = [e for e in _spans(events, "model_trace") if e["args"]["part"] == "block"]
    assert [b["args"]["kernel"] for b in bodies] == ["flash2_fwd"] * len(blocks)
    for body, block in zip(bodies, blocks):
        assert _inside(body, block) and body["dur"] < block["dur"]


@pytest.mark.parametrize("remat", [False, True])
def test_a_blocks_python_runs_once_under_value_and_grad(lm_params, remat):
    model = _toy_lm(remat=remat)
    tracer = obs_trace.get_tracer()
    tracer.clear()
    loss = lambda p, t: model.apply(p, t).astype(jnp.float32).mean()  # noqa: E731
    jax.jit(jax.value_and_grad(loss)).lower(lm_params, TOKENS)
    layers = [e["args"]["layer"] for e in _spans(tracer.to_events(), "model_trace")
              if e["args"]["part"] == "block"]
    assert layers == ["layer_0", "layer_1"]


@pytest.mark.parametrize("arch,want", [
    (dict(layer_types=("conv", "mamba"), one_branch=False), [("sconv", "mlp"), ("mamba", "mlp")]),
    (dict(layer_types=("attention", "mlp"), one_branch=True), [("attn", None), (None, "mlp")]),
], ids=["mixers", "one_branch"])
def test_model_trace_names_a_blocks_branches_as_step_parts_does(arch, want):
    from edl_tpu.models import ArchSpec, MambaSpec
    from edl_tpu.obs import profile as obs_profile

    model = TransformerLM(
        vocab_size=48, d_model=32, num_heads=2, num_layers=2, d_ff=40,
        arch=ArchSpec(mamba=MambaSpec(num_heads=4, head_dim=8, d_state=8, chunk=16),
                      **arch),
    )
    tracer = obs_trace.get_tracer()
    tracer.clear()
    jax.eval_shape(model.init, jax.random.PRNGKey(0), np.zeros((1, 32), np.int32))
    got = [(e["args"]["mixer"], e["args"]["ffn"])
           for e in _spans(tracer.to_events(), "model_trace")
           if e["args"]["part"] == "block"]
    assert got == want
    parts = {part for _, part in obs_profile.STEP_PARTS}
    assert {name for pair in got for name in pair if name} <= parts


def test_a_traced_resnet_leaves_one_model_trace_a_stage():
    model = ResNet(stage_sizes=(1, 2), num_classes=4, width=8)
    images = np.zeros((1, 16, 16, 3), np.float32)
    tracer = obs_trace.get_tracer()
    tracer.clear()
    jax.eval_shape(partial(model.init, train=False), jax.random.PRNGKey(0), images)
    stages = _spans(tracer.to_events(), "model_trace")
    assert [(e["args"]["part"], e["args"]["layer"]) for e in stages] == [
        ("stage", 0), ("stage", 1)]
    assert stages[0]["ts"] + stages[0]["dur"] <= stages[1]["ts"]


def test_the_lowered_step_is_what_it_is_without_the_spans(lm_params, monkeypatch):
    """The spans are host Python around the same calls: the StableHLO of a toy
    step with the tracer's ``span`` replaced by a no-op is the same text."""

    def lowered():
        model = _toy_lm(remat=True)

        def step(p, t):
            loss = lambda q: model.apply(q, t).astype(jnp.float32).mean()  # noqa: E731
            value, grads = jax.value_and_grad(loss)(p)
            return value, jax.tree.map(lambda a, g: a - 0.1 * g, p, grads)

        return jax.jit(step).lower(lm_params, TOKENS).as_text()

    with_spans = lowered()
    assert _spans(obs_trace.get_tracer().to_events(), "model_trace")
    obs_trace.get_tracer().clear()
    monkeypatch.setattr(
        obs_trace, "span", lambda name, **args: contextlib.nullcontext()
    )
    without = lowered()
    assert _spans(obs_trace.get_tracer().to_events(), "model_trace") == []
    assert with_spans == without and "stablehlo" in with_spans


# -- trainer_init, and the linkage into a restage operation ----------------------


def test_trainer_init_says_whether_a_manager_will_be_built(tmp_path):
    tracer = obs_trace.get_tracer()
    tracer.clear()
    for ckpt_dir in (None, str(tmp_path)):
        ElasticTrainer(MLP(hidden=(4,), features=1), optax.sgd(0.1), mse_loss,
                       sample_input=np.zeros((2, 2), np.float32), ckpt_dir=ckpt_dir)
    assert [e["args"] for e in _spans(tracer.to_events(), "trainer_init")] == [
        {"ckpt": False}, {"ckpt": True}]


def test_the_new_spans_stitch_into_an_open_restage_operation(
    fresh_tracer, monkeypatch
):
    from jax._src import xla_bridge

    # a launcher's stamp: a second before the OS started this process
    age = time.monotonic() - obs_trace.process_start_mono() + 1.0
    monkeypatch.setenv("EDL_SPAWN_TS", str(time.time() - age))
    monkeypatch.setattr(obs_trace.PROPAGATION, "armed", True)
    monkeypatch.setattr(train_context, "_boot_recorded", False)
    # before init(): the tracer's first use, a package's import
    tracer = obs_trace.get_tracer()
    with obs_trace.package_import("edl_tpu.somewhere"):
        pass
    assert all("trace_id" not in e.get("args", {}) for e in tracer.to_events())
    # init(): the operation opens, worker_boot is recorded over what was taken
    op = obs_trace.begin_process_op("restage", "stage-7", rank="0")
    train_context._record_boot_span(obs_trace)
    # after it: a platform's runtime starts, a trainer is built, a model traced
    fake = type("Backend", (), {"device_count": lambda self: 4})()
    monkeypatch.setattr(xla_bridge, "backends_are_initialized", lambda: False)
    monkeypatch.setattr(xla_bridge, aot.JAX_BACKEND_INIT, lambda platform: fake)
    aot.instrument_backend_init()
    assert getattr(xla_bridge, aot.JAX_BACKEND_INIT)("tpu") is fake
    ElasticTrainer(MLP(hidden=(4,), features=1), optax.sgd(0.1), mse_loss,
                   sample_input=np.zeros((2, 2), np.float32))
    with obs_trace.span("model_trace", part="embed"):
        pass
    obs_trace.end_process_op()

    by_name = {e["name"]: e for e in tracer.to_events() if e["ph"] == "X"}
    for name in ("process_boot", "package_import", "worker_boot", "backend_init",
                 "trainer_init", "model_trace"):
        assert by_name[name]["args"]["trace_id"] == op.trace_id, name
    boot = by_name["worker_boot"]["args"]
    assert boot["parent_id"] == op.span_id
    for name in ("process_boot", "package_import"):
        assert by_name[name]["args"]["parent_id"] == boot["span_id"], name
        assert _inside(by_name[name], by_name["worker_boot"], slack_us=2e4)
    for name in ("backend_init", "trainer_init", "model_trace"):
        assert by_name[name]["args"]["parent_id"] == op.span_id, name
    assert by_name["backend_init"]["args"]["devices"] == 4
    assert len({e["args"]["span_id"] for e in by_name.values()}) == len(by_name)


def test_worker_boot_without_an_armed_trace_links_nothing(fresh_tracer, monkeypatch):
    monkeypatch.setenv("EDL_SPAWN_TS", str(time.time() - 2.0))
    monkeypatch.setattr(obs_trace.PROPAGATION, "armed", False)
    monkeypatch.setattr(train_context, "_boot_recorded", False)
    tracer = obs_trace.get_tracer()
    obs_trace.begin_process_op("restage", "stage-8")
    train_context._record_boot_span(obs_trace)
    events = [e for e in tracer.to_events() if e["ph"] == "X"]
    assert [e["name"] for e in events] == ["process_boot", "worker_boot"]
    assert all("trace_id" not in e.get("args", {}) for e in events)
