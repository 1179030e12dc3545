"""``create_state``: the train state is the output of ONE jitted program
whose outputs are born under the shardings they are to live under.

The op-by-op init the program used to run lives on here, as the reference
the jitted one is held to (``_op_by_op``).
"""

import functools
from unittest import mock

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding

from edl_tpu.models import MLP, ResNet, TransformerLM
from edl_tpu.obs import trace as obs_trace
from edl_tpu.parallel import make_mesh, replicated
from edl_tpu.parallel.mesh import _fsdp_spec
from edl_tpu.train import ElasticTrainer, TrainState, create_state, mse_loss
from edl_tpu.train.loop import _state_shardings

_COMPILE = "/jax/core/compile/backend_compile_duration"


def _resnet():
    rs = np.random.RandomState(0)
    return (
        ResNet(stage_sizes=(1,), num_classes=10, width=8),
        rs.rand(4, 16, 16, 3).astype(np.float32),
        optax.sgd(0.1, momentum=0.9),
        {"train": False},
    )


def _lm():
    rs = np.random.RandomState(0)
    return (
        TransformerLM(
            vocab_size=64, d_model=32, num_heads=4, num_layers=1, d_ff=64,
            dtype=jnp.float32,
        ),
        rs.randint(0, 64, (2, 16)).astype(np.int32),
        optax.adamw(1e-3),
        {},
    )


def _mlp():
    rs = np.random.RandomState(0)
    return (
        MLP(hidden=(16, 16), features=4),
        rs.rand(4, 8).astype(np.float32),
        optax.adam(1e-3),
        {},
    )


JOBS = {"resnet": _resnet, "lm": _lm, "mlp": _mlp}


def _op_by_op(model, rng, sample_input, tx, **init_kwargs):
    """The reference: what ``create_state`` was before it was jitted.
    ``model.init`` and ``tx.init`` run eagerly, one program an operation,
    the forward pass included, on the default device."""
    variables = model.init(rng, sample_input, **init_kwargs)
    params = variables["params"]
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        apply_fn=model.apply,
        params=params,
        tx=tx,
        opt_state=tx.init(params),
        batch_stats=variables.get("batch_stats"),
    )


class _Compiles:
    """Backend compiles while the block runs (the persistent cache is off
    in the tests, so every new program is one)."""

    def __enter__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, duration, **kwargs):
        self.n += event == _COMPILE

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


class _Built:
    """One job's states, each built once for the module's tests (a build
    is an XLA compile; the suite's timing-sensitive neighbours share the
    host's cores)."""

    def __init__(self, model, sample, tx, kwargs):
        self.model, self.sample, self.tx, self.kwargs = model, sample, tx, kwargs
        self.rng = jax.random.PRNGKey(7)  # the caller's own two programs
        self.mesh = make_mesh({"dp": 2, "fsdp": 4})

    def create(self, rng=None, **extra):
        return create_state(
            self.model, self.rng if rng is None else rng, self.sample,
            self.tx, **extra, **self.kwargs,
        )

    @functools.cached_property
    def plain(self):
        with _Compiles() as compiles:
            state = jax.block_until_ready(self.create())
        return state, compiles.n

    @functools.cached_property
    def split(self):
        # no placement after the program: no leaf can have been whole on
        # device 0 (or anywhere) before it was split
        with _Compiles() as compiles, \
                mock.patch("jax.device_put", side_effect=AssertionError), \
                mock.patch("jax.make_array_from_callback",
                           side_effect=AssertionError):
            state = jax.block_until_ready(
                self.create(shardings=_state_shardings(self.mesh, True))
            )
        return state, compiles.n


@pytest.fixture(scope="module", params=sorted(JOBS))
def job(request):
    return _Built(*JOBS[request.param]())


def _avals(tree):
    return jax.tree.map(
        lambda x: (x.shape, x.dtype, getattr(x, "weak_type", False)), tree
    )


def _assert_equal(got, want):
    assert _avals(got) == _avals(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_equals_the_op_by_op_reference(job):
    state, _ = job.plain
    want = _op_by_op(job.model, job.rng, job.sample, job.tx, **job.kwargs)
    assert state.apply_fn == want.apply_fn and state.tx is want.tx
    assert (state.batch_stats is None) == (want.batch_stats is None)
    assert jax.tree.structure(state) == jax.tree.structure(want)
    assert _avals(state) == _avals(want)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(state), jax.tree.leaves(want)
    ):
        # same key, same initialisers, same dtypes: XLA may fuse the
        # scaling of a normal draw differently from the op-by-op run,
        # which moves the last place of a float32 (the LM's embedding:
        # 1.2e-7 relative; the ResNet and the MLP are bit-equal)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7,
            err_msg=jax.tree_util.keystr(path),
        )


def test_one_backend_compile(job):
    assert job.plain[1] == 1


def test_fsdp_leaves_are_born_sharded(job):
    state, compiles = job.split
    assert compiles == 1
    split = 0
    for leaf in jax.tree.leaves((state.params, state.opt_state)):
        want = NamedSharding(job.mesh, _fsdp_spec(leaf.shape, 4, "fsdp"))
        assert leaf.sharding == want, (leaf.shape, leaf.sharding)
        if any(want.spec):
            split += 1
            assert leaf.addressable_shards[0].data.size == leaf.size // 4
    assert split > 0
    for leaf in jax.tree.leaves((state.step, state.batch_stats)):
        assert leaf.sharding == replicated(job.mesh)


def test_fsdp_values_equal_replicated(job):
    """The draw must not depend on the sharding it is born under."""
    one = job.create(shardings=_state_shardings(make_mesh({"dp": 8}), False))
    for leaf in jax.tree.leaves(one):
        assert leaf.sharding.is_fully_replicated
        assert len(leaf.sharding.device_set) == 8
    _assert_equal(job.split[0], one)
    _assert_equal(job.plain[0], one)


def test_eval_shape_still_returns_the_tree(job):
    with _Compiles() as compiles:
        shapes = jax.eval_shape(job.create)
    assert compiles.n == 0
    state, _ = job.plain
    assert jax.tree.structure(shapes) == jax.tree.structure(state)
    assert all(
        isinstance(s, jax.ShapeDtypeStruct) for s in jax.tree.leaves(shapes)
    )
    assert jax.tree.map(lambda s: (s.shape, s.dtype), shapes) == jax.tree.map(
        lambda x: (x.shape, x.dtype), state
    )


def test_typed_key(job):
    _assert_equal(job.create(rng=jax.random.key(7)), job.plain[0])


def test_the_sample_enters_as_shapes(job):
    """No literal of the sample in the program (77 MB of zeros at the
    benchmark's size, hashed into the cache key), and no tensor of its
    shape either: the forward pass ``model.init`` traces is dead code."""
    text = jax.jit(lambda rng: job.create(rng=rng)).lower(job.rng).as_text()
    kind = {"float32": "f32", "int32": "i32"}[str(job.sample.dtype)]
    shaped = "x".join(map(str, job.sample.shape)) + "x" + kind
    assert "tensor<2xui32>" in text  # the key is an argument
    assert "tensor<%s>" % shaped not in text
    assert 'dense<"0x' not in text  # no large constant of any kind


@pytest.mark.parametrize("fsdp", [False, True], ids=["replicated", "fsdp"])
def test_fit_builds_its_state_under_a_state_init_span(fsdp):
    """``ElasticTrainer.fit``: one ``state_init`` span inside
    ``train_setup``, and the state it trains lives under the stage's
    shardings with no placement after the init."""
    tracer = obs_trace.get_tracer()
    tracer.clear()

    def data(epoch):
        rs = np.random.RandomState(epoch)
        for _ in range(2):
            x = rs.randn(8, 8).astype(np.float32)
            yield x, x.sum(axis=1, keepdims=True).astype(np.float32)

    trainer = ElasticTrainer(
        MLP(hidden=(16,), features=1), optax.adam(1e-3), mse_loss,
        sample_input=np.zeros((8, 8), np.float32), log=False,
        fsdp=fsdp, mesh_axes={"dp": 2, "fsdp": 4},
    )
    state = trainer.fit(data, epochs=1)
    events = tracer.to_events()
    (init,) = [e for e in events if e["name"] == "state_init"]
    (setup,) = [e for e in events if e["name"] == "train_setup"]
    leaves = jax.tree.leaves(state)
    assert init["args"]["leaves"] == len(leaves)
    assert init["args"]["bytes"] == sum(x.nbytes for x in leaves)
    assert setup["ts"] <= init["ts"]
    assert init["ts"] + init["dur"] <= setup["ts"] + setup["dur"]
    kernel = state.params["Dense_0"]["kernel"]
    assert kernel.sharding.spec == (
        _fsdp_spec(kernel.shape, 4, "fsdp") if fsdp else ()
    )
