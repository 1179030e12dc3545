"""ElasticTrainer: the one-call elastic loop (reference intent:
test_train.py:28-67 PaddleState/register_adjust_function sketch)."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from edl_tpu.checkpoint import AdjustRegistry, linear_scaled_lr
from edl_tpu.models import MLP
from edl_tpu.train import ElasticTrainer, mse_loss


def _records(epoch, n=256, d=8, seed_base=100):
    rs = np.random.RandomState(seed_base + epoch)
    w = np.linspace(-1, 1, d)[:, None].astype(np.float32)
    for _ in range(n):
        x = rs.randn(d).astype(np.float32)
        yield x, (x @ w).astype(np.float32)


def test_fit_record_stream_loss_decreases():
    seen = []
    trainer = ElasticTrainer(
        MLP(hidden=(16,), features=1),
        optax.sgd(0.05),
        mse_loss,
        sample_input=jnp.zeros((8, 8)),
        batch_size=8,
        log=False,
    )
    state = trainer.fit(
        _records, epochs=3,
        on_epoch_end=lambda e, m: seen.append(float(m["loss"])),
    )
    assert len(seen) == 3
    assert seen[-1] < seen[0] * 0.5, seen
    assert int(state.step) == 3 * (256 // 8)


def test_fit_resumes_from_checkpoint(tmp_path):
    def make(log=False):
        return ElasticTrainer(
            MLP(hidden=(16,), features=1),
            optax.sgd(0.05),
            mse_loss,
            sample_input=jnp.zeros((8, 8)),
            batch_size=8,
            ckpt_dir=str(tmp_path / "ckpt"),
            log=log,
        )

    s1 = make().fit(_records, epochs=2)
    assert int(s1.step) == 2 * 32
    # second run resumes at epoch 2 and only trains epochs 2..3
    epochs_run = []
    s2 = make().fit(
        _records, epochs=4,
        on_epoch_end=lambda e, m: epochs_run.append(e),
    )
    assert epochs_run == [2, 3]
    assert int(s2.step) == 4 * 32


def test_adjust_registry_feeds_optimizer_factory(monkeypatch):
    monkeypatch.setenv("EDL_NUM_WORKERS", "4")
    adjusts = AdjustRegistry()
    adjusts.register(linear_scaled_lr(0.1, base_world_size=1))
    got = {}

    def factory(overrides):
        got.update(overrides)
        return optax.sgd(overrides.get("lr", 0.1))

    # world_size=4 from env, but no store/coordinator: barrier no-ops
    trainer = ElasticTrainer(
        MLP(hidden=(8,), features=1),
        factory,
        mse_loss,
        sample_input=jnp.zeros((8, 8)),
        batch_size=8,
        adjusts=adjusts,
        log=False,
    )
    trainer.fit(lambda e: _records(e, n=32), epochs=1)
    assert got == {"lr": pytest.approx(0.4)}


def test_fit_ready_batches_no_batch_size():
    def data(epoch):
        rs = np.random.RandomState(epoch)
        for _ in range(8):
            x = rs.randn(8, 8).astype(np.float32)
            yield x, x.sum(axis=1, keepdims=True).astype(np.float32)

    trainer = ElasticTrainer(
        MLP(hidden=(16,), features=1),
        optax.sgd(0.01),
        mse_loss,
        sample_input=jnp.zeros((8, 8)),
        log=False,
    )
    state = trainer.fit(data, epochs=2)
    assert int(state.step) == 16


class TestSchedulesAndProfiler:
    def test_piecewise_decay_boundaries(self):
        from edl_tpu.train import piecewise_decay

        sched = piecewise_decay(0.8, steps_per_epoch=10, boundaries_epochs=(2, 4))
        assert float(sched(0)) == pytest.approx(0.8)
        assert float(sched(19)) == pytest.approx(0.8)
        assert float(sched(20)) == pytest.approx(0.08)
        assert float(sched(40)) == pytest.approx(0.008)

    def test_warmup_cosine_shape(self):
        from edl_tpu.train import warmup_cosine

        sched = warmup_cosine(1.0, steps_per_epoch=10, total_epochs=10,
                              warmup_epochs=2)
        assert float(sched(0)) == pytest.approx(0.0)
        assert float(sched(20)) == pytest.approx(1.0)
        assert float(sched(100)) == pytest.approx(0.0, abs=1e-6)
        assert 0.0 < float(sched(60)) < 1.0

    def test_scaled_schedule_factory_in_trainer(self, monkeypatch):
        from edl_tpu.checkpoint import AdjustRegistry, linear_scaled_lr
        from edl_tpu.train import scaled_schedule_factory, warmup_cosine

        monkeypatch.setenv("EDL_NUM_WORKERS", "2")
        adjusts = AdjustRegistry()
        adjusts.register(linear_scaled_lr(0.1, base_world_size=1))
        peaks = []

        def make_sched(lr):
            peaks.append(lr)
            return warmup_cosine(lr, steps_per_epoch=4, total_epochs=2)

        trainer = ElasticTrainer(
            MLP(hidden=(8,), features=1),
            scaled_schedule_factory(make_sched),
            mse_loss,
            sample_input=jnp.zeros((8, 8)),
            batch_size=8,
            adjusts=adjusts,
            log=False,
        )
        trainer.fit(lambda e: _records(e, n=32), epochs=1)
        assert peaks == [pytest.approx(0.2)]  # 0.1 x world 2

    def test_scaled_factory_requires_lr_override(self):
        from edl_tpu.train import scaled_schedule_factory, warmup_cosine

        factory = scaled_schedule_factory(
            lambda lr: warmup_cosine(lr, 1, 1)
        )
        with pytest.raises(ValueError, match="lr"):
            factory({})

    def test_profile_window_writes_trace(self, tmp_path, monkeypatch):
        monkeypatch.setenv("EDL_PROFILE_DIR", str(tmp_path / "trace"))
        trainer = ElasticTrainer(
            MLP(hidden=(8,), features=1),
            optax.sgd(0.01),
            mse_loss,
            sample_input=jnp.zeros((8, 8)),
            batch_size=8,
            log=False,
        )
        # 20 steps/epoch crosses the (10, 15) profile window
        trainer.fit(lambda e: _records(e, n=160), epochs=1)
        import glob

        files = glob.glob(str(tmp_path / "trace" / "**" / "*"), recursive=True)
        assert files, "no trace output written"


class TestShuffled:
    def test_deterministic_and_complete(self):
        from edl_tpu.data import shuffled

        src = list(range(100))
        a = list(shuffled(iter(src), buffer_size=16, seed=3))
        b = list(shuffled(iter(src), buffer_size=16, seed=3))
        c = list(shuffled(iter(src), buffer_size=16, seed=4))
        assert a == b
        assert sorted(a) == src
        assert a != src  # actually shuffles
        assert a != c

    def test_small_stream_fits_in_buffer(self):
        from edl_tpu.data import shuffled

        out = list(shuffled(iter([1, 2, 3]), buffer_size=100, seed=0))
        assert sorted(out) == [1, 2, 3]


class TestEvaluate:
    def test_eval_covers_every_record_once(self):
        """37 records at batch 8: 4 full batches + 1 ragged(5); the
        weighted mean must equal the exact per-record mean."""
        trainer = ElasticTrainer(
            MLP(hidden=(16,), features=1),
            optax.sgd(0.05),
            mse_loss,
            sample_input=jnp.zeros((8, 8)),
            batch_size=8,
            log=False,
        )
        state = trainer.fit(lambda e: _records(e, n=64), epochs=1)

        recs = list(_records(0, n=37))
        got = trainer.evaluate(state, lambda: iter(recs))
        # exact reference: mean over all 37 records in one device call
        x = jnp.asarray(np.stack([r[0] for r in recs]))
        y = jnp.asarray(np.stack([r[1] for r in recs]))
        preds = state.apply_fn({"params": state.params}, x)
        want = float(jnp.mean((preds - y) ** 2))
        assert got["loss"] == pytest.approx(want, rel=1e-4), (got, want)

    def test_eval_ready_batches(self):
        trainer = ElasticTrainer(
            MLP(hidden=(8,), features=1),
            optax.sgd(0.05),
            mse_loss,
            sample_input=jnp.zeros((8, 8)),
            log=False,
        )
        state = trainer.fit(
            lambda e: iter(
                [(np.ones((8, 8), np.float32), np.ones((8, 1), np.float32))]
            ),
            epochs=1,
        )
        out = trainer.evaluate(
            state,
            lambda: iter(
                [(np.ones((8, 8), np.float32), np.ones((8, 1), np.float32))] * 3
            ),
        )
        assert "loss" in out and np.isfinite(out["loss"])


# -- what _fit_stage does around the steps --------------------------------------


def _trainer(**kwargs):
    kwargs.setdefault("log", False)
    return ElasticTrainer(
        MLP(hidden=(16,), features=1), optax.sgd(0.05), mse_loss,
        sample_input=np.zeros((8, 8), np.float32), batch_size=8, **kwargs
    )


def _steps_as_counts(names):
    """``names`` with each run of the per-step triple (``data_wait``,
    ``step_dispatch``, ``train_step``) as one ``("steps", n)``."""
    triple = ["data_wait", "step_dispatch", "train_step"]
    out, i = [], 0
    while i < len(names):
        if names[i:i + 3] != triple:
            out.append(names[i])
            i += 1
            continue
        if out and isinstance(out[-1], tuple):
            out[-1] = ("steps", out[-1][1] + 1)
        else:
            out.append(("steps", 1))
        i += 3
    return out


def test_the_ring_reads_the_same_names_in_the_same_order(tmp_path, monkeypatch):
    """Pins (taken from PR 66's loop, before PR 67 cut it into parts): what
    the loop's own thread leaves in the ring over two epochs of three steps
    with a save after each, name by name in the order it is recorded. jax's
    own events and what the traced program notes of itself are not the
    loop's; a step that leaves nothing but its triple is counted."""
    import threading

    from edl_tpu.obs import trace as obs_trace

    tracer = obs_trace.SpanTracer("test")
    monkeypatch.setattr(obs_trace, "_tracer", tracer)
    _trainer(ckpt_dir=str(tmp_path / "ckpt")).fit(
        lambda epoch: _records(epoch, n=24), epochs=2,
        on_epoch_end=lambda e, m: None,
    )
    tid = threading.get_ident() & 0x7FFFFFFF
    noted = {name for name, _ in tracer.notes()}
    names = [
        e["name"] for e in tracer.to_events()
        if e.get("ph") in ("X", "i") and e.get("tid") == tid
        and not e["name"].startswith("jit_") and e["name"] not in noted
        and e["name"] not in ("cache_load", "backend_init")  # once a process
    ]
    epoch_end = [
        "data_wait",  # the pull that found the end
        "epoch_sync", "step_retired", "train_epoch", "epoch_end_hook",
        "ckpt_stamp", "ckpt_save",
    ]
    assert _steps_as_counts(names) == [
        "trainer_init", "state_init", "train_setup",
        # the first step, which the probe fetches at once
        "data_wait", "step_dispatch", "numerics_fetch", "step_retired",
        "step_launch", "first_step", "train_step", "step_relower",
        ("steps", 2), *epoch_end,
        ("steps", 3), *epoch_end,
        "numerics_fetch",  # the probe's closing flush
    ]


#: what a stage of ``_a_stage_with_every_plane`` builds and must close
_EVERY_PLANE = [
    "AotLadder", "CaptureController", "CheckpointManager", "HealthMonitor",
    "MemoryPlane", "NumericsProbe", "StepTelemetry",
]


def _a_stage_with_every_plane(tmp_path, monkeypatch, store):
    """``(trainer, closed)``: a trainer whose stage builds all seven things
    it closes (a health monitor on ``store``, a stub ladder, the five the
    toy always has), and the list their closes append ``(name, whether the
    census thread had been told to drop)`` to."""
    from edl_tpu.obs import memory, numerics, profile
    from edl_tpu.train import context, loop

    for key, value in (
        ("EDL_JOB_ID", "pins"), ("EDL_POD_ID", "pod-0"),
        ("EDL_STORE_ENDPOINT", store.endpoint),
    ):
        monkeypatch.setenv(key, value)
    closed, census = [], {}

    def note(name):
        closed.append((name, census["dropped"].is_set()))

    for owner in (
        loop.CheckpointManager, memory.MemoryPlane, numerics.NumericsProbe,
        profile.CaptureController, profile.StepTelemetry,
        context.HealthMonitor,
    ):
        def close(self, _real=owner.close, _name=owner.__name__):
            note(_name)
            _real(self)

        monkeypatch.setattr(owner, "close", close)

    def publish(tracer, notes, plan, env, dropped, _real=loop._publish_step_census):
        census["dropped"] = dropped
        _real(tracer, notes, plan, env, dropped)

    monkeypatch.setattr(loop, "_publish_step_census", publish)

    def init_with_a_cache_dir(_real=loop.init):
        # the ladder's gate, without arming jax's cache for this process
        env = _real()
        env.compile_cache_dir = str(tmp_path / "xla")
        return env

    monkeypatch.setattr(loop, "init", init_with_a_cache_dir)
    monkeypatch.setattr(
        ElasticTrainer, "_start_ladder",
        lambda self, *args, **kwargs: types.SimpleNamespace(
            close=lambda: note("AotLadder")
        ),
    )
    return _trainer(ckpt_dir=str(tmp_path / "ckpt")), closed


class _RestageAfter:
    """A stage monitor whose ``restage_pending`` turns true once the loop
    has asked ``steps`` times (it asks once before each step)."""

    def __init__(self, steps):
        self._left = steps

    @property
    def restage_pending(self):
        self._left -= 1
        return self._left < 0


def _torn(epoch):
    yield from _records(epoch, n=20)
    raise RuntimeError("the loader broke")


@pytest.mark.parametrize(
    "way_out", ["trained_to_the_end", "the_loader_raised", "a_restage_was_asked_for"]
)
def test_the_stage_closes_what_it_built_in_an_order_its_holders_allow(
    way_out, tmp_path, monkeypatch, store
):
    """Pins: whichever way a stage ends (its epochs done, an exception out
    of ``data_fn``'s iterator that reaches ``fit``'s caller, a restage asked
    for between steps) it closes each plane it built exactly once: the
    census thread told to drop before the first close, the numerics probe
    and the memory plane before the health monitor whose store client they
    hold, the ladder before the memory plane its rungs harvest into, the
    checkpoint manager last."""
    from edl_tpu.train import loop

    trainer, closed = _a_stage_with_every_plane(tmp_path, monkeypatch, store)
    if way_out == "trained_to_the_end":
        state = trainer.fit(lambda epoch: _records(epoch, n=24), epochs=1)
        assert int(state.step) == 3
    elif way_out == "the_loader_raised":
        with pytest.raises(RuntimeError, match="the loader broke"):
            trainer.fit(_torn, epochs=1)
    else:
        with pytest.raises(loop._RestageRequested):
            trainer._fit_stage(
                lambda epoch: _records(epoch, n=64), 1, None, _RestageAfter(2)
            )
    names = [name for name, _ in closed]
    assert sorted(names) == _EVERY_PLANE
    assert all(dropped for _, dropped in closed), closed
    at = names.index
    assert at("NumericsProbe") < at("HealthMonitor")
    assert at("MemoryPlane") < at("HealthMonitor")
    assert at("AotLadder") < at("MemoryPlane")
    assert names[-1] == "CheckpointManager"


def test_a_close_that_raises_does_not_skip_the_closes_after_it(
    tmp_path, monkeypatch, store
):
    """New with PR 67's lifetime stack; fails on PR 66's loop, whose
    ``finally`` closed plane after plane and stopped at the first close that
    raised. Every plane is still closed once, in the same order, and the
    exception reaches ``fit``'s caller."""
    from edl_tpu.obs import profile

    trainer, closed = _a_stage_with_every_plane(tmp_path, monkeypatch, store)

    def close(self, _noting=profile.CaptureController.close):
        _noting(self)
        raise RuntimeError("the capture plane would not close")

    monkeypatch.setattr(profile.CaptureController, "close", close)
    with pytest.raises(RuntimeError, match="would not close"):
        trainer.fit(lambda epoch: _records(epoch, n=24), epochs=1)
    names = [name for name, _ in closed]
    assert sorted(names) == _EVERY_PLANE
    assert names[-1] == "CheckpointManager"


def test_an_epoch_short_of_one_batch_trains_nothing_and_says_so(capsys):
    """Pins: with fewer records than ``batch_size`` the epoch runs no step,
    prints why, and still calls ``on_epoch_end`` with empty metrics."""
    ended = []
    state = _trainer(log=True).fit(
        lambda epoch: _records(epoch, n=5), epochs=2,
        on_epoch_end=lambda e, m: ended.append((e, dict(m))),
    )
    assert int(state.step) == 0
    assert ended == [(0, {}), (1, {})]
    out = capsys.readouterr().out
    assert out.count("produced no full batches") == 2


def test_epoch_end_metrics_never_carry_the_numerics_bundle():
    """Pins: the step's fused numerics bundle (device arrays under
    ``METRICS_KEY``) is taken out for the probe before ``on_epoch_end``
    sees the metrics, while the plane is on."""
    from edl_tpu.obs import numerics

    assert numerics.enabled()
    seen = []
    _trainer().fit(
        lambda epoch: _records(epoch, n=32), epochs=2,
        on_epoch_end=lambda e, m: seen.append(set(m)),
    )
    assert len(seen) == 2
    assert all("loss" in keys for keys in seen)
    assert all(numerics.METRICS_KEY not in keys for keys in seen)


def test_without_store_or_ckpt_dir_fit_builds_no_monitor_and_no_manager(
    monkeypatch,
):
    """Pins: with no store endpoint and no ``ckpt_dir`` the stage constructs
    neither a health monitor nor a checkpoint manager, and ``fit`` still
    trains and returns the state."""
    from edl_tpu.train import context, loop

    monkeypatch.delenv("EDL_STORE_ENDPOINT", raising=False)

    built = []
    monkeypatch.setattr(
        context, "HealthMonitor", lambda *a, **k: built.append("monitor")
    )
    monkeypatch.setattr(
        loop, "CheckpointManager", lambda *a, **k: built.append("manager")
    )
    state = _trainer().fit(lambda epoch: _records(epoch, n=32), epochs=1)
    assert built == []
    assert int(state.step) == 4
