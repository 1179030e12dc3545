"""SmallThinker's mechanisms at toy widths on the CPU: ``DroplessMoE`` routed
from a second operand (the block's input) and gated by a ReLU against the plain
reference of ``benchmark/reference/smallthinker_lm.py`` (choice, output, both
losses, every gradient; the route's gradient reaches the block's input and not
the stream the experts read), the gauge of the gate's dead share against a
count by hand, the whole model of two periods against the reference leaf by
leaf, what ``save_flash`` leaves of the route in a block's recomputation, the
refusals, and the share tied to the model (the eight shares of a 64-expert
top-6 layer add up to the uncut layer)."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.families import smallthinker_lm as family
from benchmark.reference import smallthinker_lm as reference
from edl_tpu.models import ArchSpec, MoESpec, TransformerLM
from edl_tpu.models.moe import DroplessMoE
from edl_tpu.obs import trace as obs_trace
from edl_tpu.train.step import create_state, make_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(
    ROOT, "benchmark", "rehearsal", "configs", "smallthinker_21b_a3b.json"
)) as f:
    TOY = json.load(f)

E, K, D, F, HELD = 64, 6, 32, 16, 8
COEFS = {"load_balance_coef": 0.01, "router_z_coef": 0.001}


def layer_config(first, count):
    """The reference's keys for one layer of ``E`` experts of which ``count``
    from ``first`` are held."""
    return {
        "moe_num_active_primary_experts": K, "moe_num_primary_experts": count,
        "moe_ffn_hidden_size": F, "moe_primary_router_apply_softmax": True,
        "norm_topk_prob": True, "train": COEFS,
        "share": {"router_experts": E, "experts_first": first},
    }


def layer(held, **over):
    spec = dict(
        num_experts=E, top_k=K, d_ff=F, norm_topk_prob=True,
        aux_weight=COEFS["load_balance_coef"], z_weight=COEFS["router_z_coef"],
        score_func="softmax", activation="relu", route_from="block_input",
        held=held, dtype=jnp.float32,
    )
    spec.update(over)
    return DroplessMoE(**spec)


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    scale = max(np.max(np.abs(want)), 1e-12)
    assert np.max(np.abs(got - want)) / scale <= tol


@pytest.fixture(scope="module")
def operands():
    """The block's input ``route_x``, the stream the experts read ``x`` (another
    tensor), a cotangent for the output, and the whole layer's parameters."""
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    route_x = jax.random.normal(keys[0], (2, 24, D), jnp.float32)
    x = jax.random.normal(keys[1], (2, 24, D), jnp.float32)
    cot = jax.random.normal(keys[2], (2 * 24, D), jnp.float32)
    params = jax.jit(layer(None).init)(keys[3], x, route_x)["params"]
    return route_x, x, cot, params


def held_banks(params, first, count):
    return dict(params, **{
        bank: params[bank][first:first + count] for bank in ("gate", "up", "down")
    })


@pytest.fixture(scope="module")
def one_share(operands):
    """Experts 8-15 of 64: the program's layer and the reference's, each as one
    jitted program of ``[output | both losses | what it sowed | gradients]``."""
    route_x, x, cot, params = operands
    first = 8
    here = held_banks(params, first, HELD)
    config = layer_config(first, HELD)

    def program(p, x, route_x, aux_only=False):
        y, sown = layer((first, HELD)).apply(
            {"params": p}, x, route_x, mutable=["losses", "metrics", "intermediates"]
        )
        aux = sum(jnp.sum(leaf) for leaf in jax.tree.leaves(sown["losses"]))
        main = 0.0 if aux_only else jnp.sum(y.reshape(-1, D) * cot)
        return main + aux, (y, sown)

    def plain(p, x, route_x):
        y, info = reference.mixture(config, p, route_x.reshape(-1, D), x.reshape(-1, D))
        return jnp.sum(y * cot) + info["load_balance"] + info["router_z"], (y, info)

    with jax.default_matmul_precision("highest"):
        (_, (y, sown)), grads = jax.jit(
            jax.value_and_grad(program, argnums=(0, 1, 2), has_aux=True)
        )(here, x, route_x)
        aux_grads = jax.jit(jax.grad(
            lambda p, x, r: program(p, x, r, aux_only=True)[0], argnums=(1, 2)
        ))(here, x, route_x)
        (_, (want_y, info)), want_grads = jax.jit(
            jax.value_and_grad(plain, argnums=(0, 1, 2), has_aux=True)
        )(here, x, route_x)
    return {
        "y": y, "sown": sown, "grads": grads, "aux_grads": aux_grads,
        "want_y": want_y, "info": info, "want_grads": want_grads,
    }


@pytest.mark.parametrize("what", [
    "choice", "router_logits", "output", "load_balance", "router_z", "rows_held",
    "grad_route_x", "grad_x", "grad_router", "grad_gate", "grad_up", "grad_down",
])
def test_a_layer_routed_from_a_second_operand_matches_the_reference(one_share, what):
    got = want = one_share
    sown, info = got["sown"], want["info"]
    seen = sown["intermediates"]
    if what == "choice":
        np.testing.assert_array_equal(
            np.sort(seen["top_idx"][0], axis=-1), np.sort(info["experts"], axis=-1)
        )
    elif what == "router_logits":
        _close(seen["router_logits"][0], info["router_logits"], tol=1e-5)
    elif what == "output":
        assert float(jnp.max(jnp.abs(want["want_y"]))) > 0
        _close(got["y"].reshape(-1, D), want["want_y"], tol=1e-5)
    elif what in ("load_balance", "router_z"):
        _close(sown["losses"][what][0], info[what], tol=1e-5)
    elif what == "rows_held":
        assert float(sown["metrics"]["moe_rows_held"][0]) == pytest.approx(
            float(info["rows_held"])
        )
        assert float(sown["metrics"]["moe_rows_dropped"][0]) == 0
    elif what in ("grad_route_x", "grad_x"):
        i = 2 if what == "grad_route_x" else 1
        assert float(jnp.max(jnp.abs(want["want_grads"][i]))) > 0
        _close(got["grads"][i], want["want_grads"][i], tol=1e-4)
    else:
        bank = what[len("grad_"):]
        pick = (lambda t: t["router"]["kernel"]) if bank == "router" else (lambda t: t[bank])
        assert float(jnp.max(jnp.abs(pick(want["want_grads"][0])))) > 0
        _close(pick(got["grads"][0]), pick(want["want_grads"][0]), tol=1e-4)


def test_the_routes_gradient_reaches_the_blocks_input_and_not_the_experts_stream(
    one_share, operands
):
    """The auxiliary losses are functions of the route alone: their gradient
    lands on ``route_x`` and is exactly zero on ``x``, which no router read."""
    d_x, d_route_x = one_share["aux_grads"]
    assert float(jnp.max(jnp.abs(d_x))) == 0.0
    assert float(jnp.max(jnp.abs(d_route_x))) > 0.0
    # and what the router was handed is what it sowed
    np.testing.assert_array_equal(
        np.asarray(one_share["sown"]["intermediates"]["router_in"][0]),
        np.asarray(operands[0]).reshape(-1, D),
    )


@pytest.mark.parametrize("held", [(8, HELD), None], ids=["held", "whole"])
def test_the_gauge_counts_the_dead_share_of_the_held_rows_gate(operands, held):
    """``moe_gate_dead`` against a count by hand: for each held expert, over the
    tokens that chose it, the gate's pre-activations at or below zero; rows of
    the buffer that are nobody's are not counted (they would read as dead)."""
    route_x, x, _, params = operands
    first, count = held or (0, E)
    here = held_banks(params, first, count)
    with jax.default_matmul_precision("highest"):
        _, sown = jax.jit(lambda p: layer(held).apply(
            {"params": p}, x, route_x, mutable=["metrics", "intermediates"]
        ))(here)
    chosen = np.asarray(sown["intermediates"]["top_idx"][0])
    tokens = np.asarray(x, np.float64).reshape(-1, D)
    dead = rows = 0
    for e in range(first, first + count):
        mine = tokens[(chosen == e).any(axis=-1)]
        opened = mine @ np.asarray(here["gate"][e - first], np.float64)
        dead += int((opened <= 0).sum())
        rows += len(mine)
    got = float(sown["metrics"]["moe_gate_dead"][0])
    assert got == pytest.approx(dead / (rows * F), abs=1e-6)
    assert 0.3 < got < 0.7
    if held is not None:  # the buffer holds more rows than fell on held experts
        assert rows < 2 * 48 * K * count // E


def test_a_silu_gate_sows_no_dead_share(operands):
    route_x, x, _, params = operands
    _, sown = layer(None, activation="silu").apply(
        {"params": params}, x, route_x, mutable=["metrics"]
    )
    assert "moe_gate_dead" not in sown["metrics"]


@pytest.mark.parametrize("held", [(8, HELD), None], ids=["held", "whole"])
def test_the_second_operand_changes_no_parameter_and_ff_input_is_the_layer_it_was(held):
    """``route_from`` adds no parameter: the tree of a layer routed from the
    block's input is the tree of one routed from its own input, and the default
    written out (``"ff_input"``) lowers to the text the default gives
    (``tests/test_nemotron_h.py``'s digests tie that text to the parent's)."""
    x = jnp.zeros((1, 16, D), jnp.float32)
    early = jax.eval_shape(layer(held).init, jax.random.PRNGKey(0), x, x)["params"]
    usual = jax.eval_shape(
        layer(held, route_from="ff_input").init, jax.random.PRNGKey(0), x
    )["params"]
    assert jax.tree.map(lambda a: (a.shape, a.dtype), early) == jax.tree.map(
        lambda a: (a.shape, a.dtype), usual
    )
    kind = dict(held=held, activation="silu", dtype=jnp.bfloat16)

    def lowered(module):
        params = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)["params"]
        return jax.jit(
            jax.grad(lambda p, x: jnp.sum(module.apply({"params": p}, x)), argnums=(0, 1))
        ).lower(params, x).as_text()

    spec = dict(num_experts=E, top_k=K, d_ff=F, norm_topk_prob=True)
    assert lowered(DroplessMoE(**spec, **kind)) == lowered(
        DroplessMoE(**spec, route_from="ff_input", **kind)
    )


def test_a_traced_layer_notes_where_its_route_comes_from():
    tracer = obs_trace.get_tracer()
    tracer.reset_notes()
    before = len([e for e in tracer.to_events() if e["name"] == "moe_shape"])
    x = jnp.zeros((1, 40, D))
    jax.eval_shape(lambda x: layer((0, HELD)).init(jax.random.PRNGKey(0), x, x), x)
    found = [e["args"] for e in tracer.to_events() if e["name"] == "moe_shape"][before:]
    assert [(a["route_from"], a["activation"], a["gated"]) for a in found] == [
        ("block_input", "relu", True)
    ]


# -- the share, tied to the model ---------------------------------------------


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_reference(operands):
    """Every chip routes over all 64 experts from the block's input and computes
    what its own eight give on the stream after attention; nothing is computed
    on every chip alike (no shared expert). The eight parts are the uncut layer
    as the reference, given all 64 experts as one share, computes it."""
    route_x, x, _, params = operands
    with jax.default_matmul_precision("highest"):
        uncut, info = jax.jit(lambda p: reference.mixture(
            layer_config(0, E), p, route_x.reshape(-1, D), x.reshape(-1, D)
        ))(params)

        @jax.jit
        def parts(params):
            out = []
            for first in range(0, E, HELD):
                y, sown = layer((first, HELD)).apply(
                    {"params": held_banks(params, first, HELD)}, x, route_x,
                    mutable=["metrics"],
                )
                out.append((y.reshape(-1, D), sown["metrics"]))
            return out

        total, held_rows = jnp.zeros_like(uncut), 0.0
        for first, (part, gauges) in zip(range(0, E, HELD), parts(params)):
            assert float(gauges["moe_rows_dropped"][0]) == 0
            held_rows += float(gauges["moe_rows_held"][0])
            want, _ = reference.mixture(  # the reference given the same share
                layer_config(first, HELD), held_banks(params, first, HELD),
                route_x.reshape(-1, D), x.reshape(-1, D),
            )
            _close(part, want, tol=1e-5)
            total = total + part
    assert held_rows == pytest.approx(1.0)
    assert float(info["rows_held"]) == pytest.approx(1.0)
    _close(total, uncut, tol=1e-5)


# -- the whole model ----------------------------------------------------------

TWO_PERIODS = dict(
    family.as_drawn(TOY), num_hidden_layers=8, rope_layout=[0, 1, 1, 1] * 2,
    sliding_window_layout=[0, 1, 1, 1] * 2,
    train=dict(family.as_drawn(TOY)["train"], compute_dtype="float32"),
)


def _paths(tree):
    return [
        "/".join(str(k.key) for k in path)
        for path, _ in jax.tree_util.tree_leaves_with_path(tree)
    ]


PARAM_PATHS = _paths(jax.eval_shape(
    lambda: family.build(TWO_PERIODS, 1, 0)["model"].init(
        jax.random.PRNGKey(0), np.zeros((1, TOY["train"]["seq_len"]), np.int32)
    )["params"]
))


@pytest.fixture(scope="module")
def two_periods():
    """The toy twin at depth 8 in float32 (the CPU's attention is the dense
    route, the grouped matmul ``ragged_dot``: what is compared is the model's
    mathematics), three steps at a rate that moves every scale off 1, then the
    program's and the reference's logits, losses and gradients on a fourth
    batch, each side one jitted program."""
    job = family.build(TWO_PERIODS, 1, 0)
    state = create_state(
        job["model"], jax.random.PRNGKey(0), job["sample_input"], optax.adamw(1e-2)
    )
    step = make_train_step(job["loss"], donate=False)
    pool = family.host_batches(TWO_PERIODS, 1, 0, n_batches=4)
    for batch in pool[:3]:
        state, metrics = step(state, batch)
    tokens, targets = pool[3]

    def program_loss(params):
        logits, sown = state.apply_fn(
            {"params": params}, tokens, mutable=["losses", "intermediates"]
        )
        aux = sum(jnp.sum(leaf) for leaf in jax.tree.leaves(sown["losses"]))
        routers = jnp.stack([
            sown["intermediates"]["layer_%d" % i]["moe"]["router_logits"][0]
            for i in range(8)
        ])
        return job["loss"](logits, targets)[0] + aux, (logits, routers)

    def plain_loss(params):
        logits, info = reference.forward(TWO_PERIODS, params, tokens)
        loss = reference.cross_entropy(logits, targets) + info["load_balance"] + info["router_z"]
        return loss, (logits, info["router_logits"])

    with jax.default_matmul_precision("highest"):
        (got_loss, (got_logits, got_routers)), got_grads = jax.jit(
            jax.value_and_grad(program_loss, has_aux=True)
        )(state.params)
        (want_loss, (want_logits, want_routers)), want_grads = jax.jit(
            jax.value_and_grad(plain_loss, has_aux=True)
        )(state.params)
        also = jax.jit(
            lambda p: reference.loss(TWO_PERIODS, p, tokens, targets)
        )(state.params)
    return {
        "metrics": metrics, "sown": state.sown,
        "got": {"logits": got_logits, "loss": got_loss, "routers": got_routers,
                "grads": got_grads},
        "want": {"logits": want_logits, "loss": want_loss, "routers": want_routers,
                 "grads": want_grads, "loss_fn": also},
    }


@pytest.mark.parametrize("what", ["logits", "loss", "routers"])
def test_two_periods_match_the_reference(two_periods, what):
    """``routers``: every layer's router logits against ``x W_r`` on the
    reference's BLOCK INPUT (a router handed the normed input or the stream
    after attention is off by the whole: ``benchmark/tests``)."""
    _close(two_periods["got"][what], two_periods["want"][what])
    if what == "loss":
        _close(two_periods["want"]["loss_fn"], two_periods["want"]["loss"], tol=1e-6)


@pytest.mark.parametrize("path", PARAM_PATHS)
def test_every_parameters_gradient_matches_the_reference(two_periods, path):
    def leaf(tree):
        for key in path.split("/"):
            tree = tree[key]
        return tree

    want = leaf(two_periods["want"]["grads"])
    assert float(jnp.max(jnp.abs(want))) > 0
    _close(leaf(two_periods["got"]["grads"]), want, tol=1e-3)


def test_the_step_reports_the_gates_dead_share(two_periods):
    assert "moe_gate_dead" in two_periods["sown"]
    assert 0.2 < float(two_periods["metrics"]["moe_gate_dead"]) < 0.8
    from edl_tpu.obs import metrics as obs_metrics
    from edl_tpu.obs import numerics as obs_numerics

    obs_numerics.publish_sown({
        k: np.asarray(two_periods["metrics"][k]) for k in two_periods["sown"]
    })
    assert "edl_train_moe_gate_dead " in obs_metrics.default_registry().render()


# -- what save_flash leaves of the route --------------------------------------


def _primitives(jaxpr, found):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _primitives(inner, found)
    return found


def _route_work(policy):
    """In one block's value-and-gradient under ``policy``: how many float32
    ``[N, 64]`` router matmuls, ``top_k``s and sorts of integers alone (the
    ``argsort``s: the weights ride a sort of their own, which is made again)."""
    lm = TransformerLM(
        vocab_size=48, d_model=D, num_heads=7, num_kv_heads=1, num_layers=1, d_ff=F,
        dtype=jnp.float32, remat=True, remat_policy=policy,
        moe=MoESpec(
            num_experts=E, top_k=K, d_ff=F, norm_topk_prob=True, activation="relu",
            route_from="block_input", held=(0, HELD),
        ),
        arch=ArchSpec(head_dim=8),
    )
    tokens = jnp.zeros((1, 32), jnp.int32)
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0), tokens)["params"]

    def loss(params):
        logits, sown = lm.apply({"params": params}, tokens, mutable=["losses"])
        return jnp.sum(logits) + sum(jnp.sum(a) for a in jax.tree.leaves(sown["losses"]))

    eqns = _primitives(jax.make_jaxpr(jax.value_and_grad(loss))(params).jaxpr, [])
    counts = {"router": 0, "top_k": 0, "argsort": 0}
    for eqn in eqns:
        name = eqn.primitive.name
        if name == "top_k":
            counts["top_k"] += 1
        elif name == "sort" and all(
            jnp.issubdtype(v.aval.dtype, jnp.integer) for v in eqn.invars
        ):
            counts["argsort"] += 1
        elif name == "dot_general" and eqn.outvars[0].aval.shape == (32, E):
            counts["router"] += 1
    return counts


def test_a_blocks_recomputation_runs_no_router_matmul_top_k_or_argsort_again():
    """The route's operand is the block's input, which the block's checkpoint
    keeps anyway; under ``save_flash`` the recomputation is handed
    ``moe_route``'s names and makes none of them again, where a policy that
    keeps nothing runs each a second time."""
    once = _route_work("save_flash")
    assert once == {"router": 1, "top_k": 1, "argsort": 2}
    twice = _route_work("full")
    assert twice == {"router": 2, "top_k": 2, "argsort": 4}


# -- the refusals -------------------------------------------------------------


@pytest.mark.parametrize("case", [
    "one_branch", "post_norms_only", "relu_shared", "gated_relu2", "unknown_route",
    "operand_without_the_field", "field_without_the_operand",
])
def test_what_no_model_asks_for_is_refused(case):
    x = jnp.zeros((1, 16, D))
    tokens = jnp.zeros((1, 16), jnp.int32)
    spec = MoESpec(
        num_experts=E, top_k=K, d_ff=F, activation="relu", route_from="block_input"
    )

    def lm(arch):
        return TransformerLM(
            vocab_size=32, d_model=D, num_heads=2, num_layers=1, d_ff=F, moe=spec, arch=arch
        )

    key = jax.random.PRNGKey(0)
    if case == "one_branch":
        with pytest.raises(ValueError, match="block of two branches"):
            jax.eval_shape(lm(ArchSpec(one_branch=True, layer_types=("moe",))).init, key, tokens)
    elif case == "post_norms_only":
        with pytest.raises(ValueError, match="block of two branches"):
            jax.eval_shape(lm(ArchSpec(post_norms="only")).init, key, tokens)
    elif case == "relu_shared":
        with pytest.raises(ValueError, match="shared expert under a ReLU gate"):
            jax.eval_shape(layer(None, shared_d_ff=F).init, key, x, x)
    elif case == "gated_relu2":
        with pytest.raises(ValueError, match="a gated expert's gate is one of silu, relu"):
            jax.eval_shape(layer(None, activation="relu2").init, key, x, x)
    elif case == "unknown_route":
        with pytest.raises(ValueError, match="route_from 'attention'"):
            jax.eval_shape(layer(None, route_from="attention").init, key, x, x)
    elif case == "operand_without_the_field":
        with pytest.raises(ValueError, match="route_from 'ff_input' and a second operand"):
            jax.eval_shape(layer(None, route_from="ff_input").init, key, x, x)
    else:
        with pytest.raises(ValueError, match="route_from 'block_input' and no second operand"):
            jax.eval_shape(layer(None).init, key, x)
    assert dataclasses.asdict(spec)["route_from"] == "block_input"
