"""What a recurrent mixer's in projections hand on, kept by name under
``save_flash`` (PR 54): ``models/mamba.py:projected`` puts ``mixer_in`` on the
in projection's output of ``Mamba2Mixer`` and ``GatedDeltaMixer`` and on the
six of ``KimiDeltaMixer``; ``models/transformer.py:_remat_policy`` saves it,
so a block's recomputation runs no in projection again and the numbers are
those of a recomputation that does. ``ShortConvMixer`` bears no name (the chip
read its cell no faster for it: ``models/short_conv.py``) and is here as the
mixer whose recomputation multiplies again."""

import collections
import functools
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from edl_tpu.models import (
    ArchSpec,
    GatedDeltaSpec,
    KimiDeltaSpec,
    MambaSpec,
    ShortConvSpec,
    TransformerLM,
    transformer,
)
from edl_tpu.models import mamba as mamba_module
from edl_tpu.obs import trace as obs_trace
from edl_tpu.train import cross_entropy_loss

B, T, D, VOCAB = 2, 32, 32, 64

# a mixer: the ``mixer_saved`` note's name for it, its ArchSpec fields, the
# widths of what bears the name, the leaves of the in projections whose matmul
# the name spares, the leaves of those that run again whatever the policy keeps
Case = collections.namedtuple("Case", "note arch widths spared again")
MIXERS = {
    "mamba2": Case(
        "mamba2",
        dict(layer_types=("mamba",),
             mamba=MambaSpec(num_heads=4, head_dim=8, d_state=8, n_groups=2, chunk=8)),
        [2 * 32 + 2 * 16 + 4], ("in_proj",), (),
    ),
    "gdn": Case(
        "gdn",
        dict(layer_types=("linear_attention",),
             gated_delta=GatedDeltaSpec(num_heads=2, key_dim=8, value_dim=16, chunk=8)),
        [2 * 16 + 32 + 32 + 4], ("in_proj",), (),
    ),
    "kda": Case(
        "kda",
        dict(layer_types=("kda",),
             kda=KimiDeltaSpec(num_heads=2, key_dim=8, value_dim=12, chunk=8)),
        [16, 16, 24, 16, 24, 2],
        ("q_proj", "k_proj", "v_proj", "f_proj", "g_proj", "b_proj"), (),
    ),
    # Kimi Linear's own: of a low-rank pair the second matrix's output bears
    # the name, and the first's few columns are multiplied again
    "kda_pairs": Case(
        "kda",
        dict(layer_types=("kda",),
             kda=KimiDeltaSpec(num_heads=2, key_dim=8, value_dim=12, chunk=8,
                               lower_bound=None, neg_eigval=True, gate_rank=4)),
        [16, 16, 24, 16, 24, 2],
        ("q_proj", "k_proj", "v_proj", "f_up", "g_up", "b_proj"), ("f_down", "g_down"),
    ),
    "sconv": Case(
        None, dict(layer_types=("conv",), short_conv=ShortConvSpec(taps=3)),
        [], (), ("in_proj",),
    ),
}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _loss(mixer, dtype, policy, tokens, layers=1):
    arch = MIXERS[mixer].arch
    arch = dict(arch, layer_types=arch["layer_types"] * layers)
    model = TransformerLM(
        vocab_size=VOCAB, d_model=D, num_heads=2, num_kv_heads=1, num_layers=layers, d_ff=48,
        dtype=DTYPES[dtype], remat=True, remat_policy=policy,
        arch=ArchSpec(head_dim=16, **arch),
    )

    def loss(params):
        logits = model.apply({"params": params}, tokens)
        return cross_entropy_loss(logits.reshape(-1, VOCAB), tokens.reshape(-1))[0]

    loss.init = lambda key: model.init(key, tokens)["params"]
    return loss


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.RandomState(54).randint(0, VOCAB, (B, T)), jnp.int32)


@pytest.fixture(scope="module")
def params(tokens):
    @functools.cache
    def of(mixer, dtype, layers=1):
        init = jax.jit(_loss(mixer, dtype, "save_flash", tokens, layers).init)
        return init(jax.random.PRNGKey(54))

    return of


@pytest.fixture(scope="module")
def compiled(tokens, params):
    """The one-layer model's loss and gradients as a compiled program, once a
    module: under a policy, with ``MIXER_NAMES`` in it or taken out. XLA may
    skip a rounding to bfloat16 between two operations it fuses
    (``xla_allow_excess_precision``), and a tensor that was kept was rounded:
    the programs here round where the model says."""
    @functools.cache
    def of(mixer, dtype, policy, names=True):
        with mock.patch.object(
            transformer, "MIXER_NAMES", transformer.MIXER_NAMES if names else ()
        ):
            lowered = jax.jit(
                jax.value_and_grad(_loss(mixer, dtype, policy, tokens))
            ).lower(params(mixer, dtype))
        return lowered.compile(compiler_options={"xla_allow_excess_precision": False})

    return of


def _recomputed_matmuls(text):
    """``{leaf: count}`` of a compiled program's projections (the matmuls of a
    ``nn.Dense``, by its name) under a block's recomputation; the rules' own
    products, which XLA's CPU pipeline splits as it likes, left out."""
    found = collections.Counter()
    for line in text.splitlines():
        named = re.search(r'op_name="([^"]*)"', line)
        if not re.search(r" (dot|convolution)\(", line) or not named:
            continue
        if "/rematted_computation/" not in named.group(1):
            continue
        leaf = re.search(r"/(\w+)/dot_general$", named.group(1))
        if leaf:
            found[leaf.group(1)] += 1
    return found


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("mixer", list(MIXERS))
def test_a_block_handed_its_in_projections_gives_the_same_loss_and_gradients(
    mixer, dtype, params, compiled
):
    """A saved tensor is the tensor the recomputation would have made: the
    loss and every gradient under ``save_flash`` are those under ``"full"`` to
    the bit in float32; in bfloat16 (where KDA's ``f`` stays float32) to the
    order in which a float32 reduction that XLA fuses otherwise adds up."""
    p = params(mixer, dtype)
    got, want = (compiled(mixer, dtype, policy)(p) for policy in ("save_flash", "full"))
    rtol = 0 if dtype == "float32" else 2e-6
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=rtol, atol=rtol / 100
        )
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in jax.tree.leaves(got))
    assert any(np.abs(np.asarray(g, np.float32)).max() > 0 for g in jax.tree.leaves(got[1]))


@pytest.mark.parametrize("mixer", list(MIXERS))
def test_save_flash_keeps_the_named_arrays_of_each_layer_and_full_keeps_none(
    mixer, tokens, params
):
    """Among what two blocks keep under ``save_flash`` are the arrays that bear
    ``mixer_in``, once a layer, in the dtype they have (KDA's ``f`` float32);
    without ``MIXER_NAMES`` in the policy, and under ``"full"``, they are not."""
    layers = 2
    p = params(mixer, "bfloat16", layers)

    def kept(policy):
        """The shapes and dtypes of what the gradient's forward keeps."""
        loss = _loss(mixer, "bfloat16", policy, tokens, layers)
        return collections.Counter(
            (aval.shape, str(aval.dtype)) for aval, _ in saved_residuals(loss, p)
        )

    named = collections.Counter(
        ((B, T, w), "float32" if (mixer.startswith("kda") and i == 3) else "bfloat16")
        for i, w in enumerate(MIXERS[mixer].widths)
    )
    with_names, full = kept("save_flash"), kept("full")
    with mock.patch.object(transformer, "MIXER_NAMES", ()):
        without, full_without = kept("save_flash"), kept("full")
    assert with_names - without == collections.Counter(
        {key: layers * n for key, n in named.items()}
    )
    # (what a layer no longer keeps for them: a bias the recomputation added)
    assert not [key for key in without - with_names if key[0][:2] == (B, T)]
    assert transformer._remat_policy("full") is None is transformer._remat_policy(None)
    assert not set(named) & set(full) and full == full_without


@pytest.mark.parametrize("mixer", list(MIXERS))
def test_the_recomputation_runs_no_in_projection_again(mixer, compiled):
    """The compiled gradient, matmul by matmul under
    ``/rematted_computation/``: with the name kept none is an in projection's,
    without it each in projection has one, and nothing else moves (the out
    projection's forward runs again either way: the block's feed-forward
    reads what it adds to)."""
    leaves, again = MIXERS[mixer].spared, MIXERS[mixer].again
    with_names, without = (
        _recomputed_matmuls(compiled(mixer, "bfloat16", "save_flash", names).as_text())
        for names in (True, False)
    )
    assert not [leaf for leaf in leaves if with_names[leaf]]
    assert without - with_names == collections.Counter(dict.fromkeys(leaves, 1))
    assert not with_names - without and sum(with_names.values()) > 0
    assert [with_names[leaf] for leaf in again] == [1] * len(again)


@pytest.mark.parametrize("mixer", list(MIXERS))
def test_a_traced_mixer_notes_what_a_layer_leaves_under_the_name(mixer, tokens, params):
    """One ``mixer_saved`` instant a traced (mixer, shape): the mixer, how many
    arrays bear the name and their bytes a layer."""
    name, widths = MIXERS[mixer].note, MIXERS[mixer].widths
    tracer = obs_trace.get_tracer()
    tracer.reset_notes()
    jax.eval_shape(
        _loss(mixer, "bfloat16", "save_flash", tokens, 2), params(mixer, "bfloat16", 2)
    )
    noted = [args for noted, args in tracer.notes() if noted == "mixer_saved"]
    wide = widths[3] if mixer.startswith("kda") else 0   # f: float32
    assert noted == [dict(
        mixer=name, arrays=len(widths), bytes=B * T * (2 * sum(widths) + 2 * wide),
    )] * bool(widths)


def test_the_name_is_defined_once_and_both_saving_policies_list_it():
    assert transformer.MIXER_NAMES == mamba_module.REMAT_NAMES == ("mixer_in",)
    x = jnp.ones((4, 4))

    def kept(policy):
        f = jax.checkpoint(
            lambda x: jnp.sum(jnp.sin(mamba_module.projected("probe", jnp.sin(x))[0])),
            policy=transformer._remat_policy(policy),
        )
        return len(saved_residuals(f, x))

    assert kept("save_flash") == kept("save_flash_qkv") == kept("full") + 1
