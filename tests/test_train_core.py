"""Train-core tests on the virtual 8-device CPU mesh.

Covers what the reference delegates to Paddle fleet and therefore never
tests itself (SURVEY §2 L5): mesh construction, dp-sharded train steps with
XLA-inserted gradient all-reduce, single-device vs 8-way-DP numerical
equivalence, batch-norm models, and fsdp parameter sharding.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn

from edl_tpu.models import MLP, LinearRegression, ResNet
from edl_tpu.models.resnet import BasicBlockVd
from edl_tpu.parallel import (
    batch_sharding,
    make_mesh,
    replicated,
    shard_batch,
    shard_params_fsdp,
)
from edl_tpu.train import (
    create_state,
    cross_entropy_loss,
    make_eval_step,
    make_train_step,
    mse_loss,
)


def test_cpu_mesh_has_8_devices():
    assert len(jax.devices()) == 8


def test_make_mesh_axes():
    mesh = make_mesh()
    assert mesh.shape == {"dp": 8}
    mesh = make_mesh({"dp": -1, "tp": 2})
    assert mesh.shape == {"dp": 4, "tp": 2}
    with pytest.raises(ValueError):
        make_mesh({"dp": 3})
    with pytest.raises(ValueError):
        make_mesh({"dp": -1, "tp": -1})


def _regression_data(n=512, d=13, seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(d, 1)
    x = rng.randn(n, d).astype(np.float32)
    y = (x @ w + 0.01 * rng.randn(n, 1)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y)


def test_linear_regression_converges_dp():
    """fit_a_line: the reference's minimum end-to-end slice (SURVEY §7.3)."""
    mesh = make_mesh()
    x, y = _regression_data()
    model = LinearRegression()
    state = create_state(model, jax.random.key(0), x[:1], optax.sgd(0.1))
    state = jax.device_put(state, replicated(mesh))
    step = make_train_step(mse_loss)
    batch = shard_batch(mesh, (x, y))
    first_loss = None
    for _ in range(60):
        state, metrics = step(state, batch)
        # serialize steps: this 1-core host deadlocks XLA:CPU's collective
        # rendezvous if async dispatch queues many 8-replica executions
        jax.block_until_ready(metrics)
        if first_loss is None:
            first_loss = float(metrics["loss"])
    final_loss = float(metrics["loss"])
    assert final_loss < first_loss * 0.05, (first_loss, final_loss)
    assert final_loss < 0.05


def test_dp_matches_single_device():
    """8-way DP must be numerically equivalent to one device (fp32 CPU)."""
    x, y = _regression_data(n=64)
    model = MLP(hidden=(16,), features=1)
    tx = optax.sgd(0.05)

    def run(sharded):
        state = create_state(model, jax.random.key(1), x[:1], tx)
        step = make_train_step(mse_loss, donate=False)
        if sharded:
            mesh = make_mesh()
            state = jax.device_put(state, replicated(mesh))
            batch = shard_batch(mesh, (x, y))
        else:
            batch = (x, y)
        for _ in range(5):
            state, metrics = step(state, batch)
            jax.block_until_ready(metrics)
        return state.params

    single = run(sharded=False)
    multi = run(sharded=True)
    flat_s = jax.tree.leaves(single)
    flat_m = jax.tree.leaves(multi)
    for a, b in zip(flat_s, flat_m):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6)


def _tiny_resnet():
    return ResNet(
        stage_sizes=(1, 1),
        block=BasicBlockVd,
        num_classes=10,
        width=8,
        dtype=jnp.float32,
    )


def test_resnet_train_step_updates_batch_stats():
    mesh = make_mesh()
    model = _tiny_resnet()
    x = jnp.ones((16, 32, 32, 3), jnp.float32)
    y = jnp.zeros((16,), jnp.int32)
    state = create_state(
        model, jax.random.key(0), x[:1], optax.sgd(0.01, momentum=0.9), train=True
    )
    state = jax.device_put(state, replicated(mesh))
    batch = shard_batch(mesh, (x, y))
    step = make_train_step(cross_entropy_loss, apply_kwargs={"train": True})
    # materialize before the step: the donated input state's buffers die
    old_stats = [np.asarray(l) for l in jax.tree.leaves(state.batch_stats)]
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert 0.0 <= float(metrics["accuracy"]) <= 1.0
    new_stats = [np.asarray(l) for l in jax.tree.leaves(state.batch_stats)]
    assert any(
        not np.allclose(a, b) for a, b in zip(old_stats, new_stats)
    ), "batch stats must move"
    assert int(state.step) == 1

    eval_step = make_eval_step(cross_entropy_loss, apply_kwargs={"train": False})
    metrics = eval_step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_resnet50_vd_output_shape():
    from edl_tpu.models import ResNet50_vd

    model = ResNet50_vd(num_classes=1000, dtype=jnp.float32)
    x = jnp.ones((2, 64, 64, 3), jnp.float32)
    variables = jax.eval_shape(lambda: model.init(jax.random.key(0), x, train=False))
    n_params = sum(
        np.prod(l.shape) for l in jax.tree.leaves(variables["params"])
    )
    # ResNet50_vd ~25.6M params (classifier 1000): sanity window
    assert 24e6 < n_params < 27e6, n_params


def test_fsdp_sharding_places_shards():
    mesh = make_mesh({"dp": 2, "fsdp": 4})
    model = MLP(hidden=(64, 64), features=8)
    x = jnp.ones((4, 16), jnp.float32)
    state = create_state(model, jax.random.key(0), x, optax.adam(1e-3))
    params = shard_params_fsdp(mesh, state.params)
    kernel = params["Dense_0"]["kernel"]  # (16, 64): 64 divisible by 4
    spec = kernel.sharding.spec
    assert "fsdp" in str(spec), spec
    # a scalar-ish tensor stays replicated
    bias = params["Dense_0"]["bias"]  # (64,) divisible -> may shard; check small
    tiny = jnp.ones((3,))
    placed = shard_params_fsdp(mesh, {"t": tiny})
    assert placed["t"].sharding.spec == ()


class TestWorkerBarrier:
    """Store-backed stage barrier (reference pod_server.py:63): push-based
    watch wakeup, reusable names via round counters, timeout on absentees."""

    def _spawn(self, store_endpoint, rank, world, script, extra_env=None):
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(
            os.environ,
            PYTHONPATH=repo,
            EDL_JOB_ID="jbarrier",
            EDL_STORE_ENDPOINT=store_endpoint,
            EDL_WORKER_RANK=str(rank),
            EDL_NUM_WORKERS=str(world),
            EDL_STAGE="stg1",
            JAX_PLATFORMS="cpu",
        )
        env.update(extra_env or {})
        return subprocess.Popen(
            [sys.executable, "-c", script],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )

    SCRIPT = (
        "from edl_tpu.train import worker_barrier\n"
        "worker_barrier('a', timeout=20)\n"
        "worker_barrier('a', timeout=20)\n"  # round counter: reusable name
        "print('BARRIER_OK')\n"
    )

    def test_three_workers_meet_twice(self, store):
        procs = [
            self._spawn(store.endpoint, r, 3, self.SCRIPT) for r in range(3)
        ]
        for p in procs:
            out, err = p.communicate(timeout=60)
            assert p.returncode == 0, err[-500:]
            assert "BARRIER_OK" in out

    def test_lone_worker_times_out(self, store):
        script = (
            "from edl_tpu.train import worker_barrier\n"
            "from edl_tpu.utils.exceptions import EdlBarrierError\n"
            "try:\n"
            "    worker_barrier('b', timeout=1.5)\n"
            "except EdlBarrierError as e:\n"
            "    print('TIMED_OUT', e)\n"
        )
        p = self._spawn(store.endpoint, 0, 2, script)
        out, err = p.communicate(timeout=60)
        assert p.returncode == 0, err[-500:]
        assert "TIMED_OUT" in out and "1/2" in out


class TestResNeXtAndKD:
    """Teacher model family + distillation loss (reference README.md:71:
    ResNeXt101_32x16d_wsl -> ResNet50_vd co-located distill)."""

    def test_resnext101_32x16d_param_count(self):
        # torchvision's resnext101_32x16d_wsl has ~194M params; the vd
        # stem swaps the 7x7 for three 3x3s but stays within ~1%
        from edl_tpu.models import ResNeXt101_32x16d

        model = ResNeXt101_32x16d()
        shapes = jax.eval_shape(
            model.init,
            jax.random.PRNGKey(0),
            jnp.zeros((1, 224, 224, 3), jnp.float32),
        )
        n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes["params"]))
        assert 190e6 < n < 200e6, n

    def test_resnext_tiny_train_step(self):
        from edl_tpu.models.resnet import ResNeXt

        model = ResNeXt(
            stage_sizes=(1, 1), cardinality=4, base_width=4, num_classes=10
        )
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (2, 32, 32, 3))
        y = jnp.array([1, 3])
        state = create_state(
            model, rng, x, optax.sgd(0.1), train=True
        )
        from edl_tpu.train import make_kd_loss

        teacher_logits = jax.random.normal(rng, (2, 10))
        step = make_train_step(make_kd_loss(alpha=0.5, temperature=2.0),
                               {"train": True})
        # the step donates its input state: snapshot params to host first
        leaves0 = [np.asarray(l) for l in jax.tree.leaves(state.params)]
        state2, metrics = step(state, (x, (y, teacher_logits)))
        assert np.isfinite(float(metrics["loss"]))
        leaves2 = jax.tree.leaves(state2.params)
        assert any(
            not np.allclose(a, b) for a, b in zip(leaves0, leaves2)
        )

    def test_kd_loss_zero_kl_when_teacher_equals_student(self):
        from edl_tpu.train import make_kd_loss

        logits = jax.random.normal(jax.random.PRNGKey(1), (4, 7))
        labels = jnp.array([0, 1, 2, 3])
        loss_a, m_a = make_kd_loss(alpha=1.0, temperature=3.0)(
            logits, (labels, logits)
        )
        assert abs(float(m_a["kd_kl"])) < 1e-6
        assert abs(float(loss_a)) < 1e-5
        # alpha=0 reduces to plain CE
        loss_b, m_b = make_kd_loss(alpha=0.0)(logits, (labels, logits))
        assert np.isclose(float(loss_b), float(m_b["hard_ce"]))


class TestHybridMesh:
    """Multi-slice DCN x ICI mesh construction (2 virtual slices of 4)."""

    def test_shape_and_axis_order(self):
        from edl_tpu.parallel import make_hybrid_mesh

        mesh = make_hybrid_mesh({"dp": 2}, {"fsdp": 4}, slice_count=2)
        assert mesh.axis_names == ("dp", "fsdp")
        assert mesh.shape == {"dp": 2, "fsdp": 4}

    def test_ici_groups_stay_within_slice(self):
        from edl_tpu.parallel import make_hybrid_mesh

        devs = jax.devices()
        mesh = make_hybrid_mesh({"dp": 2}, {"tp": 2, "sp": 2}, slice_count=2)
        arr = np.asarray(mesh.devices)
        assert arr.shape == (2, 2, 2)
        # virtual slice 0 = devices[0:4]: every ici coordinate of dp row 0
        first = {d.id for d in arr[0].flat}
        assert first == {d.id for d in devs[:4]}

    def test_dp_training_on_hybrid_mesh_matches_flat(self):
        from edl_tpu.parallel import make_hybrid_mesh, shard_batch

        mesh = make_hybrid_mesh({"dp": 2}, {"fsdp": 4}, slice_count=2)
        model = MLP(hidden=(16,), features=4)
        rng = jax.random.PRNGKey(0)
        x = jax.random.normal(rng, (8, 8))
        y = jax.random.normal(rng, (8, 4))
        state = create_state(model, rng, x, optax.sgd(0.1))
        step = make_train_step(mse_loss)
        with mesh:
            batch = shard_batch(mesh, (x, y))
            _, m_mesh = step(state, batch)
        state2 = create_state(model, rng, x, optax.sgd(0.1))
        _, m_flat = step(state2, (x, y))
        np.testing.assert_allclose(
            float(m_mesh["loss"]), float(m_flat["loss"]), rtol=1e-5
        )

    def test_errors(self):
        from edl_tpu.parallel import make_hybrid_mesh

        with pytest.raises(ValueError):
            make_hybrid_mesh({"dp": 3}, {"fsdp": 4}, slice_count=2)
        with pytest.raises(ValueError):
            make_hybrid_mesh({"dp": 2}, {"fsdp": 4}, slice_count=3)


def test_make_cross_entropy_reports_top5():
    """Opt-in acc1/acc5 like the reference benchmark tables
    (README.md:68-72); plain cross_entropy_loss stays top-1-only."""
    from edl_tpu.train import make_cross_entropy_loss

    head = make_cross_entropy_loss(report_top_k=5)
    logits = jnp.asarray([
        [9.0, 5.0, 4.0, 3.0, 2.0, 1.0, 0.0, -1.0],  # label 1: top5 yes, top1 no
        [0.0, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 9.0],   # label 0: not in top5
        [9.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0],   # label 0: top1 yes
    ])
    labels = jnp.asarray([1, 0, 0])
    _, m = head(logits, labels)
    assert float(m["accuracy"]) == pytest.approx(1 / 3)
    assert float(m["top5"]) == pytest.approx(2 / 3)
    # exactly-k-class heads skip it (top-5 of 5 classes is constant 1.0)
    _, m5 = head(jnp.zeros((2, 5)), jnp.asarray([0, 1]))
    assert "top5" not in m5
    # the shared head never pays for it
    _, m_plain = cross_entropy_loss(logits, labels)
    assert "top5" not in m_plain


class TestCompilationCache:
    """Persistent XLA compilation cache across worker restarts — the
    resize-downtime lever (stop-resume restarts every JAX process per
    stage; without a cache each incarnation recompiles from scratch)."""

    SCRIPT = (
        "import os, sys; sys.path.insert(0, %(root)r); "
        "from edl_tpu.train import init; init(); "
        "import jax, jax.numpy as jnp; "
        "f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum()); "
        "print(float(f(jnp.ones((64, 64)))))"
    )

    def _run(self, cache_dir, tmp_path):
        import subprocess, sys, os as _os

        env = dict(_os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "EDL_JOB_ID": "cctest",
            "EDL_COMPILE_CACHE_DIR": str(cache_dir),
        })
        root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
        out = subprocess.run(
            [sys.executable, "-c", self.SCRIPT % {"root": root}],
            env=env, capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]

    @staticmethod
    def _snapshot(cache):
        """{name: (mtime, sha)} of the EXECUTABLE cache entries only.

        XLA writes an 8-byte ``-atime`` metadata sidecar next to every
        ``-cache`` entry and rewrites it on every HIT (it is literally an
        access-time record), so sidecars churn by design and must not
        count as a cache miss.
        """
        import hashlib

        return {
            p.name: (p.stat().st_mtime, hashlib.sha256(p.read_bytes()).hexdigest())
            for p in cache.iterdir()
            if not p.name.endswith("-atime")
        }

    def test_worker_init_populates_and_reuses_cache(self, tmp_path):
        cache = tmp_path / "xla"
        self._run(cache, tmp_path)
        entries = self._snapshot(cache)
        assert entries, "first run must write cache entries"
        self._run(cache, tmp_path)
        after = self._snapshot(cache)
        # a HIT loads the executable without rewriting: same entries,
        # untouched mtimes and content. A miss would re-serialize over
        # the same keys.
        assert after == entries

    def test_job_env_default_and_disable(self, monkeypatch, tmp_path):
        import os

        from edl_tpu.cluster.job_env import JobEnv

        from edl_tpu.cluster.job_env import default_compile_cache_dir

        monkeypatch.delenv("EDL_COMPILE_CACHE_DIR", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        je = JobEnv(job_id="jobx", store_endpoint="h:1")
        assert je.compile_cache_dir == default_compile_cache_dir()
        assert JobEnv(job_id="jobx", compile_cache_dir="none").compile_cache_dir == ""
        assert (
            JobEnv(job_id="jobx", compile_cache_dir=str(tmp_path)).compile_cache_dir
            == str(tmp_path)
        )


class TestMaskedTrainStep:
    def _setup(self):
        import numpy as np
        import optax

        from edl_tpu.models import MLP
        from edl_tpu.train import create_state, cross_entropy_loss

        model = MLP(hidden=(16,), features=4)
        rs = np.random.RandomState(0)
        x = rs.randn(8, 8).astype(np.float32)
        y = rs.randint(0, 4, (8,))
        state = create_state(
            model, jax.random.PRNGKey(0), x, optax.sgd(0.1)
        )
        return state, x, y, cross_entropy_loss

    def test_all_valid_matches_plain_step(self):
        import numpy as np

        from edl_tpu.train import make_masked_train_step, make_train_step

        state, x, y, loss = self._setup()
        plain = make_train_step(loss, donate=False)
        masked = make_masked_train_step(loss, donate=False)
        s1, m1 = plain(state, (x, y))
        s2, m2, n_valid = masked(state, (x, y), np.ones(8, bool))
        assert float(n_valid) == 8.0
        np.testing.assert_allclose(
            float(m1["loss"]), float(m2["loss"]), rtol=1e-6
        )
        for a, b in zip(
            jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-6
            )

    def test_padded_rows_equal_small_batch(self):
        """A padded 8-row batch with 5 valid rows must produce the SAME
        update as a plain step over just those 5 rows."""
        import numpy as np

        from edl_tpu.train import make_masked_train_step, make_train_step

        state, x, y, loss = self._setup()
        plain = make_train_step(loss, donate=False)
        masked = make_masked_train_step(loss, donate=False)
        mask = np.array([1, 1, 1, 1, 1, 0, 0, 0], bool)
        # garbage in the pad rows must not matter
        xp = x.copy()
        xp[5:] = 1e3
        s_ref, m_ref = plain(state, (x[:5], y[:5]))
        s_got, m_got, n_valid = masked(state, (xp, y), mask)
        assert float(n_valid) == 5.0
        np.testing.assert_allclose(
            float(m_ref["loss"]), float(m_got["loss"]), rtol=1e-5
        )
        for a, b in zip(
            jax.tree.leaves(s_ref.params), jax.tree.leaves(s_got.params)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5
            )

    def test_batch_stats_models_rejected(self):
        import numpy as np
        import optax
        import pytest as _pytest

        from edl_tpu.models import ResNet
        from edl_tpu.train import create_state, cross_entropy_loss
        from edl_tpu.train import make_masked_train_step

        model = ResNet(stage_sizes=(1,), num_classes=4, width=8)
        x = np.zeros((4, 32, 32, 3), np.float32)
        state = create_state(
            model, jax.random.PRNGKey(0), x, optax.sgd(0.1)
        )
        masked = make_masked_train_step(
            cross_entropy_loss, {"train": True}, donate=False
        )
        with _pytest.raises(ValueError, match="batch_stats"):
            masked(state, (x, np.zeros(4, np.int64)), np.ones(4, bool))


# -- large weight gradients leave the optimizer's fusion ----------------------


class _Sower(nn.Module):
    """Two Dense layers; with ``sows`` a term of the objective through
    ``"losses"`` and a gauge through ``"metrics"``."""

    sows: bool = False

    @nn.compact
    def __call__(self, x):
        h = jnp.tanh(nn.Dense(16, name="inner")(x))
        if self.sows:
            self.sow("losses", "activation_l2", 1e-2 * jnp.mean(h * h))
            self.sow("metrics", "activation_absmax", jnp.max(jnp.abs(h)))
        return nn.Dense(1, name="outer")(h)


def _apart_state_and_batch(sows, tx=None):
    rs = np.random.RandomState(3)
    x = rs.randn(8, 8).astype(np.float32)
    y = rs.randn(8, 1).astype(np.float32)
    state = create_state(
        _Sower(sows=sows), jax.random.PRNGKey(0), x, tx or optax.adamw(1e-2)
    )
    return state, (x, y)


@pytest.mark.parametrize("sows", [False, True], ids=["no_sown", "sown_losses"])
@pytest.mark.parametrize("split", [True, False], ids=["split", "unsplit"])
def test_the_step_is_bit_equal_with_and_without_leaves_taken_apart(
    monkeypatch, split, sows
):
    """The barrier is the identity: parameters, optimizer state, loss and
    bundle to the bit, whether the rule takes every matrix or none."""
    from edl_tpu.train import step as step_module

    monkeypatch.setenv("EDL_NUMERICS_GNS", "1" if split else "0")
    state, batch = _apart_state_and_batch(sows)
    results, barriers = [], []
    for min_width in (1, 2**20):
        monkeypatch.setattr(step_module, "GRAD_APART_MIN_WIDTH", min_width)
        step = make_train_step(mse_loss, numerics=True, donate=False)
        barriers.append(
            step.lower(state, batch).as_text().count("optimization_barrier")
        )
        results.append(step(state, batch))
    halves = 2 if split and not sows else 1  # a model that sows is never split
    assert barriers == [2 * halves, 0]  # the two Dense kernels; no bias
    (new_a, metrics_a), (new_b, metrics_b) = results
    assert ("half_sq" in metrics_a["_numerics"]) == (halves == 2)
    assert ("aux_loss" in metrics_a) == sows
    leaves_a, tree_a = jax.tree.flatten((new_a, metrics_a))
    leaves_b, tree_b = jax.tree.flatten((new_b, metrics_b))
    assert tree_a == tree_b
    for a, b in zip(leaves_a, leaves_b):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # and the step moved something
    assert not np.array_equal(
        np.asarray(new_a.params["inner"]["kernel"]),
        np.asarray(state.params["inner"]["kernel"]),
    )


def _shapes(*leaves):
    return {
        "leaf%d" % i: jax.ShapeDtypeStruct(shape, dtype)
        for i, (shape, dtype) in enumerate(leaves)
    }


def _resnet50_vd_params():
    from edl_tpu.models import ResNet50_vd

    variables = jax.eval_shape(
        lambda: ResNet50_vd(num_classes=1000).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)), train=False
        )
    )
    return variables["params"]


@pytest.mark.parametrize("tree,taken", [
    (_resnet50_vd_params, 0),
    (lambda: _shapes(((3840, 11008), jnp.float32)), 1),
    (lambda: _shapes(((11008, 3840), jnp.float32), ((4096, 14336), jnp.bfloat16),
                     ((3840, 8670), jnp.float32), ((2880, 3840), jnp.float32),
                     ((3840,), jnp.float32)), 4),
    (lambda: _shapes(((2048, 8192), jnp.float32),                # Granite's SwiGLU
                     ((2048, 11776), jnp.float32),               # LFM2's
                     ((2048, 25024), jnp.float32)), 0),          # Trinity's head
    (lambda: _shapes(((4096, 32000), jnp.float32),               # Mistral's head
                     ((32000, 4096), jnp.float32)), 0),
    (lambda: _shapes(((16, 4096, 4096), jnp.float32),            # an expert bank
                     ((4096, 32, 128), jnp.float32)), 0),        # a DenseGeneral
    (lambda: _shapes(((2**30,), jnp.float32), ((40 << 20,), jnp.float32)), 0),
    (lambda: _shapes(((4096, 14336), jnp.int32)), 0),            # never an integer
    (lambda: _shapes(((4096, 14336), jnp.bool_)), 0),
    (lambda: _shapes(((), jnp.float32)), 0),
], ids=["resnet50_vd", "olmo_swiglu", "matrices_and_a_norm", "hidden_2048",
        "vocabulary_matrices", "rank_3", "one_d", "integer", "boolean", "scalar"])
def test_the_rule_on_which_gradient_leaves_are_taken_apart(tree, taken):
    from edl_tpu.train import step as step_module

    leaves = jax.tree.leaves(tree())
    assert leaves
    assert sum(step_module.taken_apart(leaf) for leaf in leaves) == taken


@pytest.mark.parametrize("qualifying", [0, 1, 3])
@pytest.mark.parametrize("split", [True, False], ids=["split", "unsplit"])
def test_the_lowered_step_holds_one_barrier_a_leaf_taken(
    monkeypatch, split, qualifying
):
    """k leaves taken: k ``optimization_barrier``s in the lowered text, 2k
    under the half-batch split, none for a tree with none to take."""
    from edl_tpu.train import step as step_module

    monkeypatch.setenv("EDL_NUMERICS_GNS", "1" if split else "0")
    # kernels of [8, 16], [16, 16] (one or three of them) and [16, 1], biases
    monkeypatch.setattr(step_module, "GRAD_APART_MIN_WIDTH", 16)
    hidden = {0: (), 1: (16, 16), 3: (16, 16, 16, 16)}[qualifying]
    model = MLP(hidden=hidden, features=1)
    x = np.zeros((8, 8), np.float32)
    state = create_state(model, jax.random.PRNGKey(0), x, optax.adamw(1e-3))
    kernels = [
        leaf for leaf in jax.tree.leaves(state.params)
        if step_module.taken_apart(leaf)
    ]
    assert len(kernels) == qualifying
    step = make_train_step(mse_loss, numerics=True)
    text = step.lower(state, (x, np.zeros((8, 1), np.float32))).as_text()
    assert text.count("optimization_barrier") == qualifying * (2 if split else 1)


def test_tracing_a_step_leaves_one_grad_apart_instant(monkeypatch):
    from edl_tpu.obs import trace as obs_trace
    from edl_tpu.train import step as step_module

    monkeypatch.setattr(step_module, "GRAD_APART_MIN_WIDTH", 8)
    obs_trace.get_tracer().reset_notes()
    tracer = obs_trace.get_tracer()
    before = len([e for e in tracer.to_events() if e["name"] == "grad_apart"])
    state, batch = _apart_state_and_batch(False)
    for _ in range(2):  # the second trace at the same shapes adds nothing
        # split: ``grads_apart`` runs once a half, over one and the same tree
        make_train_step(mse_loss, numerics=True).lower(state, batch)
    noted = [e for e in tracer.to_events() if e["name"] == "grad_apart"][before:]
    assert len(noted) == 1
    # of inner's [8, 16] kernel and bias and outer's [16, 1] and bias: the first
    assert noted[0]["args"] == {
        "leaves": 1, "of": 4, "bytes": 4 * 8 * 16, "largest_bytes": 4 * 8 * 16,
        "min_width": 8, "max_elements": step_module.GRAD_APART_MAX_ELEMENTS,
    }
    # a tree with nothing to take says so
    monkeypatch.setattr(step_module, "GRAD_APART_MIN_WIDTH", 2**20)
    make_train_step(mse_loss, numerics=True).lower(state, batch)
    noted = [e for e in tracer.to_events() if e["name"] == "grad_apart"][before:]
    assert [e["args"]["leaves"] for e in noted] == [1, 0]
    assert noted[1]["args"] == {
        "leaves": 0, "of": 4, "bytes": 0, "largest_bytes": 0, "min_width": 2**20,
        "max_elements": step_module.GRAD_APART_MAX_ELEMENTS,
    }


# -- the attention layer's projections fence their own dW ---------------------


def _toy_lm(gate, dtype=jnp.float32, **over):
    from edl_tpu.models.transformer import ArchSpec, TransformerLM

    return TransformerLM(**{
        "vocab_size": 64, "d_model": 32, "num_heads": 4, "num_kv_heads": 2,
        "num_layers": 2, "d_ff": 64, "dtype": dtype, "remat": True,
        "arch": ArchSpec(attn_gate=gate, head_dim=16), **over,
    })


def _toy_lm_state_and_batch(gate, dtype=jnp.float32, **over):
    tokens = np.random.RandomState(0).randint(0, 64, (4, 16)).astype(np.int32)
    state = create_state(
        _toy_lm(gate, dtype, **over), jax.random.PRNGKey(0), tokens,
        optax.adamw(1e-2),
    )
    return state, (tokens, np.roll(tokens, -1, axis=1))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("gate", [False, True], ids=["plain", "gated"])
@pytest.mark.parametrize("split", [True, False], ids=["split", "unsplit"])
def test_the_step_is_bit_equal_with_and_without_the_head_projections_fence(
    monkeypatch, unfence, split, gate, dtype
):
    """The fence is the identity: parameters, optimizer state, loss and
    metrics to the bit. The bundle's norms are sums over the whole tree whose
    order XLA picks fusion by fusion, so they agree to a float32's rounding."""
    monkeypatch.setenv("EDL_NUMERICS_GNS", "1" if split else "0")
    state, batch = _toy_lm_state_and_batch(gate, dtype)
    results, barriers = [], []
    for fenced in (True, False):
        if not fenced:
            unfence()
        step = make_train_step(cross_entropy_loss, numerics=True, donate=False)
        barriers.append(
            step.lower(state, batch).as_text().count("optimization_barrier")
        )
        results.append(step(state, batch))
    # q, k, v, o and the gate's g in each of two layers, each half's under the split
    assert barriers[0] - barriers[1] == (5 if gate else 4) * 2 * (2 if split else 1)
    (new_a, metrics_a), (new_b, metrics_b) = results
    assert ("half_sq" in metrics_a["_numerics"]) == split
    bundle_a, bundle_b = metrics_a.pop("_numerics"), metrics_b.pop("_numerics")
    leaves_a, tree_a = jax.tree.flatten((new_a, metrics_a))
    leaves_b, tree_b = jax.tree.flatten((new_b, metrics_b))
    assert tree_a == tree_b
    for a, b in zip(leaves_a, leaves_b):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert jax.tree.structure(bundle_a) == jax.tree.structure(bundle_b)
    for a, b in zip(jax.tree.leaves(bundle_a), jax.tree.leaves(bundle_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-6)
    # and the step moved the fenced kernels
    for name in ("q", "k", "v", "o") + (("g",) if gate else ()):
        assert not np.array_equal(
            np.asarray(new_a.params["layer_0"]["attn"][name]["kernel"]),
            np.asarray(state.params["layer_0"]["attn"][name]["kernel"]),
        )


class _PlainHeads(nn.Module):
    """The attention layer's parameters as plain ``nn.DenseGeneral`` makes
    them: the tree the fence must leave as it was."""

    gate: bool

    @nn.compact
    def __call__(self, x):
        q = nn.DenseGeneral(features=(4, 16), use_bias=False, name="q")(x)
        for name in ("k", "v"):
            nn.DenseGeneral(features=(2, 16), use_bias=False, name=name)(x)
        if self.gate:
            nn.DenseGeneral(features=(4, 16), use_bias=False, name="g")(x)
        return nn.DenseGeneral(
            features=32, axis=(-2, -1), use_bias=False, name="o"
        )(q)


@pytest.mark.parametrize("gate", [False, True], ids=["plain", "gated"])
def test_the_fenced_projections_keep_dense_generals_tree_and_initial_values(gate):
    from edl_tpu.models.transformer import Attention

    x = jnp.zeros((2, 8, 32), jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(8)[None, :], (2, 8))
    got = Attention(
        num_heads=4, num_kv_heads=2, head_dim=16, gate=gate
    ).init(jax.random.PRNGKey(7), x, positions)["params"]
    want = _PlainHeads(gate).init(jax.random.PRNGKey(7), x)["params"]
    shapes = {"q": (32, 4, 16), "k": (32, 2, 16), "v": (32, 2, 16), "o": (4, 16, 32)}
    if gate:
        shapes["g"] = (32, 4, 16)
    assert {k: v["kernel"].shape for k, v in got.items()} == shapes
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype == jnp.float32
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("gate", [False, True], ids=["plain", "gated"])
def test_the_lowered_gradient_holds_one_barrier_a_head_projection(unfence, gate):
    """q, k, v, o (and g) each one; the forward and the decode programs none
    at all."""
    from edl_tpu.models.transformer import Attention

    x = jnp.ones((2, 8, 32), jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(8)[None, :], (2, 8))
    layer = Attention(num_heads=4, num_kv_heads=2, head_dim=16, gate=gate)
    params = layer.init(jax.random.PRNGKey(0), x, positions)["params"]
    decoder = layer.clone(decode=True, max_decode_len=8)
    cache = decoder.init(jax.random.PRNGKey(0), x, positions)["cache"]

    def barriers(fn, *args):
        return jax.jit(fn).lower(*args).as_text().count("optimization_barrier")

    def counted():  # fresh functions: ``jax.checkpoint`` keeps a traced one
        def forward(p):
            return layer.apply({"params": p}, x, positions)

        def decode(p, c):
            return decoder.apply(
                {"params": p, "cache": c}, x[:, :1], positions[:, :1],
                mutable=["cache"],
            )

        return {
            "forward": barriers(forward, params),
            "decode": barriers(decode, params, cache),
            "gradient": barriers(
                jax.grad(lambda p: forward(p).astype(jnp.float32).sum()), params
            ),
            "under_remat": barriers(
                jax.grad(
                    lambda p: jax.checkpoint(forward)(p).astype(jnp.float32).sum()
                ),
                params,
            ),
        }

    fenced = counted()
    unfence()
    plain = counted()  # ``jax.checkpoint`` brings barriers of its own
    assert plain["forward"] == plain["decode"] == plain["gradient"] == 0
    assert {k: fenced[k] - plain[k] for k in fenced} == {
        "forward": 0, "decode": 0,
        "gradient": 5 if gate else 4, "under_remat": 5 if gate else 4,
    }


def test_the_gradient_through_the_fence_is_the_plain_gradient_to_the_bit(unfence):
    """Under ``jax.checkpoint`` and without: every kernel's gradient equals
    the unfenced layer's."""
    from edl_tpu.models.transformer import Attention

    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(2, 8, 32), jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(8)[None, :], (2, 8))
    layer = Attention(num_heads=4, num_kv_heads=2, head_dim=16, gate=True)
    params = layer.init(jax.random.PRNGKey(0), x, positions)["params"]

    def grads(remat):
        def loss(p):
            fn = lambda p: layer.apply({"params": p}, x, positions)  # noqa: E731
            out = (jax.checkpoint(fn) if remat else fn)(p)
            return (out.astype(jnp.float32) ** 2).sum()

        return jax.jit(jax.grad(loss))(params)

    fenced = [grads(False), grads(True)]
    unfence()
    plain = [grads(False), grads(True)]
    for got, want in zip(fenced, plain):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
