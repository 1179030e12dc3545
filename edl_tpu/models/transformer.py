"""Decoder-only Transformer LM — the long-context flagship.

Net-new model family versus the reference (its largest workload is
ResNet50/ERNIE fine-tune; SURVEY §5 notes long-context is absent), built
TPU-first:

- blocks with RMSNorm (before each branch, before and after it, or after
  it only: ``ArchSpec.post_norms``), RoPE positions (a choice:
  ``ArchSpec.rope``), SwiGLU MLP — all large-matmul-dominated so the MXU
  stays busy; bf16 compute, fp32 params;
- a block's sequence mixer is, by ``ArchSpec.layer_types``, full causal
  attention, attention over a sliding window, a Mamba-2 state-space
  layer (``models/mamba.py``), a gated-delta-rule linear-attention layer
  (``models/gated_delta.py``; with a decay a key channel, Kimi delta
  attention), a gated short convolution (``models/short_conv.py``),
  attention over the keys a learned indexer selects
  (``ops/sparse_attention.py``) or latent attention (keys and values out of
  one low-rank latent a token: :class:`LatentAttention`); its feed-forward a
  SwiGLU or an expert layer (``models/moe.py``), the leading
  ``ArchSpec.dense_layers`` blocks of an expert model dense; or, with
  ``ArchSpec.one_branch``, blocks of one branch each (Nemotron-H's: a mixer, an
  expert layer or a dense feed-forward alone under one norm): one
  ``TransformerLM`` runs dense, expert, hybrid (any of the four cheap mixers
  beside attention), mixed-window and one-branch configurations;
- attention is pluggable: the Pallas flash kernel locally, or ring
  attention over the ``sp`` mesh axis for sequences longer than one
  device's HBM (``edl_tpu.parallel.ring``);
- ``remat=True`` wraps each block in ``jax.checkpoint``
  (``nn.remat``) — activation recompute, the TPU equivalent of the
  reference's recompute flag (train_with_fleet.py:104, 323-325);
- tensor-parallel sharding rules for the weights live in
  ``edl_tpu.parallel.sharding_rules`` (Megatron-style column/row splits
  expressed as PartitionSpecs; XLA inserts the tp collectives).
"""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Any, Callable, Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from edl_tpu.models.gated_delta import (
    GatedDeltaMixer,
    GatedDeltaSpec,
    KimiDeltaMixer,
    KimiDeltaSpec,
)
from edl_tpu.models.mamba import Mamba2Mixer, MambaSpec
from edl_tpu.models.mamba import REMAT_NAMES as MIXER_NAMES
from edl_tpu.models.moe import DroplessMoE, MoESpec, SwitchMoE
from edl_tpu.models.moe import REMAT_NAMES as MOE_NAMES
from edl_tpu.models.short_conv import ShortConvMixer, ShortConvSpec
from edl_tpu.obs import trace as obs_trace
from edl_tpu.ops.attention import _flash2_blocks, attention
from edl_tpu.ops.cross_entropy import rows_cross_entropy
from edl_tpu.ops.gated_delta import REMAT_NAMES as GDN_NAMES
from edl_tpu.ops.sparse_attention import REMAT_NAMES as DSA_NAMES
from edl_tpu.ops.sparse_attention import sparse_attention

AttentionFn = Callable[..., jax.Array]  # (q, k, v, causal=...) -> out


def _supports_gqa(fn) -> bool:
    """True when ``fn`` (possibly wrapped in functools.partial layers —
    the repo's standard wiring for ring attention) declares it accepts
    grouped k/v via a ``supports_gqa`` attribute."""
    while isinstance(fn, partial):
        if getattr(fn, "supports_gqa", False):
            return True
        fn = fn.func
    return getattr(fn, "supports_gqa", False)

NEG_INF_DECODE = -1e30  # mask value for cache positions past the index


@dataclasses.dataclass(frozen=True)
class SparseAttentionSpec:
    """The indexer of a ``"sparse_attention"`` layer (DeepSeek sparse
    attention, arXiv:2512.02556 section 2): ``index_heads`` query heads of
    ``index_dim`` against one shared key head score every causal pair, each
    query attends to its ``topk`` best keys, and the indexer's KL towards the
    main attention's head-mean probabilities joins the objective at
    ``loss_weight`` (``ops/sparse_attention.py`` has the equations)."""

    index_heads: int = 16
    index_dim: int = 64
    topk: int = 2048
    loss_weight: float = 1.0


DSA_SCOPES = ("dsa_index", "dsa_select", "attn_sparse", "dsa_target")


@dataclasses.dataclass(frozen=True)
class LatentAttentionSpec:
    """The shape of a ``"latent_attention"`` layer (multi-head latent
    attention, DeepSeek-V2, arXiv:2405.04434 section 2.1): keys and values
    come out of one ``kv_lora_rank``-wide latent a token, a head's query and
    key are ``qk_nope_head_dim`` values without a position and
    ``qk_rope_head_dim`` rotated ones (the keys' rotated part one vector a
    token, shared by the heads), its value ``v_head_dim``. With a
    ``q_lora_rank`` the queries come out of a latent of that width too, under
    a norm of its own (``None``: one full-rank projection). ``head_gate``
    multiplies each head's output by one ``sigmoid(x W_g)``."""

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    head_gate: bool = False
    q_lora_rank: Optional[int] = None


MLA_SCOPES = ("mla_proj", "attn_mla")


@dataclasses.dataclass(frozen=True)
class MTPSpec:
    """A multi-token-prediction module after the last norm (DeepSeek-V3,
    arXiv:2412.19437 section 2.2, equations 21 to 25): ``depth`` modules (one
    is what runs; another value raises), each predicting one token further
    than the main head, its cross-entropy joining the objective at
    ``loss_weight`` (the paper's lambda). ``TransformerLM`` has the module."""

    depth: int = 1
    loss_weight: float = 0.3


# ``mtp`` holds the whole module, the other two lie inside it
MTP_SCOPES = ("mtp", "mtp_join", "mtp_head")


@dataclasses.dataclass(frozen=True)
class BlockDiffusionSpec:
    """The block-diffusion training step (BD3-LM, arXiv:2503.09573; SDAR,
    arXiv:2510.06303): ``TransformerLM`` then takes ``[B, 2 L]`` ids, a clean
    sequence ``x_0`` and beside it its noised copy ``x_t`` (positions of
    ``x_0`` replaced by ``mask_id``, block by block of ``block`` positions:
    ``data/block_diffusion.py`` makes the pair on the host), runs both through
    every layer under the block-diffusion mask (``ops.attention``: a noised
    position sees the clean text of strictly earlier blocks and its own
    block's noised positions) at positions that repeat across the halves, and
    scores the noised half alone. ``train/step.py:make_block_diffusion_loss``
    is its loss head. ``mask_id`` is a row of the embedding like any other;
    the model reads only ``block``."""

    block: int = 4
    mask_id: int = 0


# the device scope of the attention call under ``ArchSpec.block_diffusion``
BLOCK_DIFFUSION_SCOPE = "attn_block_diffusion"


@dataclasses.dataclass(frozen=True)
class ArchSpec:
    """What a ``TransformerLM`` does differently from the dense default,
    as one hashable field; every default is the dense model's.

    ``layer_types`` names each block's sequence mixer: ``"attention"``
    (causal over the whole sequence), ``"sliding_attention"`` (causal over
    the ``sliding_window`` newest keys, the query's own among them),
    ``"mamba"`` (then ``mamba`` gives the layer's shape),
    ``"linear_attention"`` (the gated delta rule; then ``gated_delta`` gives
    the layer's shape), ``"conv"`` (the gated short convolution; then
    ``short_conv`` gives its taps), ``"sparse_attention"`` (causal attention
    over the keys a learned indexer selects for each query; then
    ``sparse_attention`` gives the indexer's shape), ``"kda"`` (Kimi delta
    attention, the delta rule with a decay for every key channel; then
    ``kda`` gives the layer's shape) or ``"latent_attention"`` (causal
    attention whose keys and values come out of a low-rank latent; then
    ``latent_attention`` gives its ranks and head sizes, and ``rope_theta``
    the base of its rotated part whatever ``rope`` says); its length is the
    model's depth. ``one_branch`` makes every block ``x + Branch(N(x))`` with
    ONE branch under one norm (Nemotron-H, arXiv:2504.03624): a mixer's block
    then carries no feed-forward, and ``layer_types`` may also name a block
    whose branch is the feed-forward alone, ``"moe"`` (the expert layer of
    ``TransformerLM.moe``) or ``"mlp"`` (the dense SwiGLU of ``d_ff``);
    ``dense_layers`` then says nothing, and there is no decode path. ``rope``
    rotates q and k in every attention layer (``True``), in none (``False``:
    no position term at all) or in the windowed layers only (``"sliding"``:
    the full layers then see order through the causal mask alone); the
    rotation's base is ``rope_theta``, a configuration's own key, and 10,000
    where none is given. ``dense_layers`` leading blocks of a
    model with an expert layer (``TransformerLM.moe``) keep the dense
    SwiGLU of ``d_ff``. ``post_norms`` is one field with three forms for
    where a block's RMSNorms sit: ``False`` before each branch (``x +
    branch(N(x))``), ``True`` before and after it (``x + N(branch(N(x)))``),
    ``"only"`` after it and not before (``x + N(branch(x))``: the branch
    reads the residual stream as it is); ``attn_gate`` multiplies
    the heads' outputs by ``sigmoid(x W_g)``, elementwise, before the out
    projection. The three multipliers are Granite's: the embedding's
    output times ``embedding_multiplier``, each residual branch (mixer and
    feed-forward) times ``residual_multiplier``, the logits divided by
    ``logits_scaling``. ``tie_embeddings`` projects onto the vocabulary
    with the embedding's own matrix, whose gradient is then the sum of
    both uses. ``block_diffusion`` makes the model the denoiser of a
    block-diffusion step (``BlockDiffusionSpec``): every layer is then
    ``"attention"`` over a clean and a noised copy side by side, and there is
    no decode path, window, selection or multi-token module with it."""

    layer_types: Optional[Tuple[str, ...]] = None
    mamba: Optional[MambaSpec] = None
    gated_delta: Optional[GatedDeltaSpec] = None
    short_conv: Optional[ShortConvSpec] = None
    sparse_attention: Optional[SparseAttentionSpec] = None
    kda: Optional[KimiDeltaSpec] = None
    latent_attention: Optional[LatentAttentionSpec] = None
    head_dim: Optional[int] = None      # None: d_model / num_heads
    rope: Union[bool, str] = True       # True, False or "sliding"
    rope_theta: float = 10000.0         # the rotation's base
    attn_scale: Optional[float] = None  # None: head_dim ** -0.5
    tie_embeddings: bool = False
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    sliding_window: Optional[int] = None
    dense_layers: int = 0
    post_norms: Union[bool, str] = False  # False, True or "only"
    attn_gate: bool = False
    one_branch: bool = False            # True: a block is one branch, no second
    mtp: Optional[MTPSpec] = None       # a multi-token-prediction module
    block_diffusion: Optional[BlockDiffusionSpec] = None  # the training step's


LAYER_TYPES = ("attention", "sliding_attention", "mamba", "linear_attention",
               "conv", "sparse_attention", "kda", "latent_attention")
# under ``ArchSpec.one_branch`` also: a block whose branch is the feed-forward
FEED_FORWARD_TYPES = ("moe", "mlp")
# the module a layer type's mixer is built under, where it is not ``attn``
_MIXER_MODULES = {"mamba": "mamba", "linear_attention": "gdn", "kda": "kda",
                  "conv": "sconv"}


def _scope(name: Optional[str]):
    """``jax.named_scope(name)``, and nothing at all without a name."""
    return jax.named_scope(name) if name else contextlib.nullcontext()


def _times(x, factor: float):
    """``x * factor``, and ``x`` itself at 1: the dense model's program
    holds no multiplication it never asked for."""
    return x if factor == 1.0 else x * factor


class RMSNorm(nn.Module):
    epsilon: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.epsilon
        )
        return (norm * scale).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, base: float = 10000.0) -> jax.Array:
    """Rotary position embedding; x: [B, T, H, D]."""
    d = x.shape[-1]
    half = d // 2
    freq = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[:, :, None, None].astype(jnp.float32) * freq  # B T 1 half
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dw_apart(name: str, kernel):
    """``kernel``, and a fence behind its gradient: forward the identity
    (it lowers to nothing), backward one ``optimization_barrier`` on the
    cotangent. ``name`` is the projection's, for the instant."""
    return kernel


def _dw_apart_fwd(name, kernel):
    return kernel, None


def _dw_apart_bwd(name, _, ct):
    # once a shape a projection's weight gradient is fenced at, and stage
    obs_trace.get_tracer().note_once(
        "dw_apart", kernel=name, shape=list(ct.shape), dtype=str(ct.dtype),
        bytes=ct.size * ct.dtype.itemsize,
    )
    return (jax.lax.optimization_barrier(ct),)


_dw_apart.defvjp(_dw_apart_fwd, _dw_apart_bwd)


def _heads_dot_general(name: str, x, kernel, dimension_numbers, precision=None):
    """``nn.DenseGeneral``'s ``dot_general`` for a projection into heads
    (``[d_model, heads, head_dim]``) or out of them (``[heads, head_dim,
    d_model]``): ``lax.dot_general`` whose weight gradient is taken out of
    the optimizer's fusion. Left to itself XLA fuses the update and the
    numerics bundle's norms into the rank-3 ``dot_general`` that produces
    this dW, and that fused form ran at 26-51% of peak wherever a cell held
    a wide one (PERF.md section 6, PRs 38 and 39; the narrow ones are a wash
    either way). Behind the fence dW is a plain matmul, written once in the
    compute dtype it is rounded to anyway, and its readers one pass. The
    layer decides, not ``train/step.py:taken_apart``: no shape tells these
    leaves from an expert bank ``[E, d, w]``, whose gradient leaves a custom
    call and is apart already."""
    return jax.lax.dot_general(
        x, _dw_apart(name, kernel), dimension_numbers, precision=precision
    )


class Attention(nn.Module):
    """Multi-head / grouped-query attention.

    ``num_kv_heads`` < ``num_heads`` is GQA (Ainslie et al. 2023): K/V
    project to fewer heads, cutting KV projection params and FLOPs by
    ``num_heads/num_kv_heads``; ``num_kv_heads=1`` is MQA; ``None``
    (default) is classic MHA. ``head_dim`` defaults to ``d_model /
    num_heads`` and may be given for a model whose heads do not tile
    its width; ``rope=False`` applies no rotation (a position-free
    layer), and ``rope_theta`` is the rotation's base (a block hands over
    ``ArchSpec.rope_theta``: the configuration's own, 10,000 by default);
    ``scale`` replaces the scores' ``head_dim ** -0.5``.
    The default dispatch's Pallas kernels are
    GQA-AWARE (ops/attention.py: grouped k/v read via index mapping, no
    materialized repeat, dk/dv folded back to the grouped width), so on
    the flash/flash2 routes training keeps the grouped activation bytes
    too; the dense "ref" route (below the measured flash crossover) and
    ragged fallbacks still broadcast in-graph. A custom ``attention_fn``
    sees broadcast MHA shapes UNLESS it (or the function under its
    functools.partial wrapping) declares ``supports_gqa = True`` — ring
    and ulysses attention both do, and then receive grouped k/v (the
    ring's rotating shards and ulysses' kv collectives shrink by the
    group factor).
    With tensor parallelism the grouped projections replicate when
    ``num_kv_heads`` doesn't divide ``tp`` (see ``shard_params_by_rules``)
    while q/o keep their Megatron split.

    ``qk_norm`` is one field with two forms, both an RMSNorm with a learned
    scale on q and one on k, before RoPE: ``True`` (OLMoE's) normalises the
    WHOLE projected vector, all heads together, with a scale as wide;
    ``"head"`` normalises each head's ``head_dim`` values with one scale of
    ``head_dim`` shared by the heads. ``window`` restricts a query to its
    ``window`` newest keys, itself included (``ops.attention.attention``'s
    argument; the cached decode path has none and refuses it). ``gate``
    adds the projection ``g`` of q's width and returns
    ``(heads' outputs * sigmoid(g)) W_o``. ``kernel_scope`` names the device
    scope of the attention call alone (a mixed-window model tells its two
    kinds of layer apart by it); with it, or with a gate, the per-head QK
    norms and the gate sit under ``attn_gate``. Every projection's weight
    gradient is written by a matmul of its own, behind a fence
    (``_heads_dot_general``); the parameters are plain ``nn.DenseGeneral``'s.

    ``sparse`` makes the layer attend over a learned selection: an indexer on
    ``stop_gradient(x)`` (``index_q``: ``index_heads`` heads of ``index_dim``;
    ``index_k``: one head through a LayerNorm with scale and bias; both
    rotated like q and k; ``index_w``: a float32 weight a head a token, times
    ``index_heads ** -0.5 * index_dim ** -0.5``) scores every causal pair,
    ``ops.sparse_attention`` keeps each query's ``topk`` best keys and
    attends over them, and the indexer's KL towards the heads' mean
    probabilities is sown into ``"losses"`` (``dsa_index_kl``, at
    ``loss_weight``): it is the only gradient the indexer's three matrices
    and the LayerNorm receive, and it reaches nothing else. Sown into
    ``"metrics"``: ``dsa_index_kl`` (unweighted), ``dsa_selected_share``
    (selected over causal pairs) and ``dsa_tile_live`` (of the forward
    kernel's tiles under the causal line, the share that holds a selected
    pair); into ``"intermediates"``, for a check that asks for the
    collection: ``selection`` ``[B, T, T]`` (int8), ``index_scores`` ``[B, T,
    T]`` and ``index_operands`` (the indexer's q ``[B, T, J, Di]``, k ``[B, T,
    Di]`` and weights ``[B, T, J]``). Device scopes ``dsa_index`` / ``dsa_select`` / ``attn_sparse`` /
    ``dsa_target``. The decode cache has no selection and refuses it.

    ``block_diffusion`` (a block length ``B``) makes the call's mask the third
    kind ``ops.attention.attention`` knows: ``x`` is then ``2 L`` positions, a
    clean sequence and its noised copy, and the mask ``block_diffusion=(L,
    B)``. No decode cache, window, selection or custom ``attention_fn`` goes
    with it; each raises by name.
    """

    num_heads: int
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[AttentionFn] = None
    num_kv_heads: Optional[int] = None
    decode: bool = False       # autoregressive mode: KV cache in "cache"
    max_decode_len: int = 2048
    qk_norm: Union[bool, str] = False  # True: whole width; "head": per head
    norm_eps: float = 1e-6
    head_dim: Optional[int] = None
    rope: bool = True
    rope_theta: float = 10000.0
    scale: Optional[float] = None
    window: Optional[int] = None
    gate: bool = False
    kernel_scope: Optional[str] = None
    sparse: Optional[SparseAttentionSpec] = None
    block_diffusion: Optional[int] = None

    @nn.compact
    def __call__(self, x, positions):
        d_model = x.shape[-1]
        if self.block_diffusion is not None:
            for name, given in (
                ("decode", self.decode), ("window", self.window is not None),
                ("sparse", self.sparse is not None),
                ("attention_fn", self.attention_fn is not None),
            ):
                if given:
                    raise NotImplementedError(
                        "Attention: block_diffusion with %s: the block-diffusion "
                        "mask is a training step's, over a clean and a noised "
                        "copy in one call of ops.attention.attention" % name
                    )
            if x.shape[1] % 2:
                raise ValueError(
                    "Attention: block_diffusion over %d positions: a clean and "
                    "a noised copy are 2 L" % x.shape[1]
                )
        head_dim = self.head_dim or d_model // self.num_heads
        kv_heads = (
            self.num_kv_heads if self.num_kv_heads is not None
            else self.num_heads
        )
        if kv_heads < 1 or self.num_heads % kv_heads:
            raise ValueError(
                "num_kv_heads (%d) must be a positive divisor of "
                "num_heads (%d)" % (kv_heads, self.num_heads)
            )

        def dense(name: str, **shape):
            return nn.DenseGeneral(
                use_bias=False, dtype=self.dtype, name=name,
                dot_general=partial(_heads_dot_general, name), **shape,
            )

        q = dense("q", features=(self.num_heads, head_dim))(x)
        k = dense("k", features=(kv_heads, head_dim))(x)
        v = dense("v", features=(kv_heads, head_dim))(x)
        # the device scope of what stands beside the kernel: by name in a layer
        # with a gate, or of a model that tells two kinds of layer apart
        beside = "attn_gate" if self.gate or self.kernel_scope else None
        if self.qk_norm == "head":
            with _scope(beside):
                q = RMSNorm(self.norm_eps, name="q_norm")(q)
                k = RMSNorm(self.norm_eps, name="k_norm")(k)
        elif self.qk_norm is True:
            flat = q.shape[:2] + (-1,)
            q = RMSNorm(self.norm_eps, name="q_norm")(q.reshape(flat)).reshape(q.shape)
            k = RMSNorm(self.norm_eps, name="k_norm")(k.reshape(flat)).reshape(k.shape)
        elif self.qk_norm:
            raise ValueError("unknown qk_norm %r" % (self.qk_norm,))
        if self.rope:
            q = rope(q, positions, self.rope_theta)
            k = rope(k, positions, self.rope_theta)
        if self.decode:
            if self.window is not None or self.sparse is not None:
                raise NotImplementedError(
                    "the decode cache takes no window and no selection"
                )
            out = self._decode_step(q, k, v, kv_heads, head_dim)
        elif self.sparse is not None:
            out = self._selected(x, positions, q, k, v)
        else:
            # [B, T, H, D] -> [B, H, T, D]
            q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
            if (
                kv_heads != self.num_heads
                and self.attention_fn is not None
                and not _supports_gqa(self.attention_fn)
            ):
                # custom attention fns see plain MHA shapes unless they
                # declare supports_gqa (ring attention does: grouped k/v
                # cut its ppermute volume by the group factor). The
                # DEFAULT dispatch accepts grouped k/v natively.
                group = self.num_heads // kv_heads
                k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
            attn = self.attention_fn or attention
            extra = {} if self.scale is None else {"scale": self.scale}
            if self.window is not None:
                extra["window"] = self.window
            if self.block_diffusion is not None:
                extra["block_diffusion"] = (x.shape[1] // 2, self.block_diffusion)
            with _scope(self.kernel_scope):
                out = attn(q, k, v, causal=True, **extra)
            out = jnp.swapaxes(out, 1, 2)
        if self.gate:
            with _scope(beside):
                g = dense("g", features=(self.num_heads, head_dim))(x)
                out = out * nn.sigmoid(g)
        return dense("o", features=x.shape[-1], axis=(-2, -1))(out)

    def _selected(self, x, positions, q, k, v):
        """Attention over the keys the layer's indexer selects (``sparse``);
        q, k, v ``[B, T, heads, head_dim]`` as projected, normed and rotated;
        returns ``[B, T, H, head_dim]``."""
        spec = self.sparse
        if self.window is not None or self.attention_fn is not None:
            raise ValueError("a selection takes no window and no attention_fn")
        with jax.named_scope("dsa_index"):
            seen = jax.lax.stop_gradient(x)
            fp32_out = partial(jax.lax.dot_general, preferred_element_type=jnp.float32)
            index_q = nn.DenseGeneral(
                (spec.index_heads, spec.index_dim), use_bias=False,
                dtype=self.dtype, name="index_q",
            )(seen)
            index_k = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype, name="index_k_norm")(
                nn.Dense(spec.index_dim, use_bias=False, dtype=self.dtype, name="index_k")(seen)
            )[:, :, None, :]
            if self.rope:
                index_q = rope(index_q, positions, self.rope_theta)
                index_k = rope(index_k, positions, self.rope_theta)
            index_w = nn.Dense(
                spec.index_heads, use_bias=False, dtype=self.dtype,
                dot_general=fp32_out, name="index_w",
            )(seen).astype(jnp.float32) * (spec.index_heads ** -0.5 * spec.index_dim ** -0.5)
        out, kl, stats, detail = sparse_attention(
            *(jnp.swapaxes(t, 1, 2) for t in (q, k, v, index_q)), index_k[:, :, 0],
            index_w, spec.topk, self.scale,
        )
        if spec.loss_weight:
            self.sow("losses", "dsa_index_kl", spec.loss_weight * kl)
        self.sow("metrics", "dsa_index_kl", kl)
        self.sow("metrics", "dsa_selected_share", stats["selected_share"])
        self.sow("metrics", "dsa_tile_live", stats["tile_live"])
        # only when a caller asks for the collection: a check against a reference
        self.sow("intermediates", "selection", detail["selection"])
        self.sow("intermediates", "index_scores", detail["scores"])
        self.sow("intermediates", "index_operands", (index_q, index_k[:, :, 0], index_w))
        return jnp.swapaxes(out, 1, 2)

    def _decode_step(self, q, k, v, kv_heads: int, head_dim: int):
        """Cached autoregressive attention for T >= 1 new tokens: insert
        their K/V into the cache at the running index (GROUPED width —
        the num_heads/num_kv_heads cache-byte saving is real here, and
        the cache is stored in the model dtype, bf16 for the default
        config) and attend each query against its causal prefix. T > 1
        is the PREFILL path: the whole prompt lands in one MXU-friendly
        pass. Static shapes throughout: the cache is ``max_decode_len``
        long and masked by index + offset, so generate() compiles one
        prefill program and one single-token step."""
        b, t = q.shape[0], q.shape[1]
        cache_k = self.variable(
            "cache", "cached_key",
            jnp.zeros, (b, self.max_decode_len, kv_heads, head_dim),
            self.dtype,
        )
        cache_v = self.variable(
            "cache", "cached_value",
            jnp.zeros, (b, self.max_decode_len, kv_heads, head_dim),
            self.dtype,
        )
        index = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        i = index.value
        cache_k.value = jax.lax.dynamic_update_slice(
            cache_k.value, k.astype(self.dtype), (0, i, 0, 0)
        )
        cache_v.value = jax.lax.dynamic_update_slice(
            cache_v.value, v.astype(self.dtype), (0, i, 0, 0)
        )
        index.value = i + t

        group = self.num_heads // kv_heads
        # [B, T, H, D] -> [B, T, KV, G, D]; score math in fp32
        qg = q.astype(jnp.float32).reshape(b, t, kv_heads, group, head_dim)
        scores = jnp.einsum(
            "btkgd,blkd->bkgtl",
            qg * (self.scale or head_dim ** -0.5),
            cache_k.value.astype(jnp.float32),
        )
        # query at offset o (position i+o) sees cache slots l <= i+o
        valid = (
            jnp.arange(self.max_decode_len)[None, :]
            <= i + jnp.arange(t)[:, None]
        )  # [T, L]
        scores = jnp.where(valid[None, None, None], scores, NEG_INF_DECODE)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum(
            "bkgtl,blkd->btkgd", probs, cache_v.value.astype(jnp.float32)
        )
        return out.reshape(b, t, self.num_heads, head_dim).astype(self.dtype)


def _note_mla_shape(tq: int, heads: int, spec: LatentAttentionSpec):
    """One ``mla_shape`` instant in the span ring for each shape a latent
    attention layer is traced at in a stage (``note_once``), with the blocks
    the grid-pipelined kernels take at the two widths."""
    d_qk = spec.qk_nope_head_dim + spec.qk_rope_head_dim
    blocks = {
        kind: list(_flash2_blocks(kind, tq, tq, None))
        for kind in ("fwd", "bwd")
    }
    obs_trace.get_tracer().note_once(
        "mla_shape", tq=tq, heads=heads, d_qk=d_qk, d_v=spec.v_head_dim,
        latent=spec.kv_lora_rank, rope_dim=spec.qk_rope_head_dim,
        q_rank=spec.q_lora_rank,
        fwd_blocks=blocks["fwd"], bwd_blocks=blocks["bwd"],
    )


class LatentAttention(nn.Module):
    """Multi-head latent attention as it trains (DeepSeek-V2,
    arXiv:2405.04434, equations 9 to 19), for the block's input ``x``
    ``[B, T, d_model]`` and ``H`` heads::

        q = x W_q                       [T, H, nope + rope], cut into q_n | q_r;
            with ``q_lora_rank``: q = RMSNorm(x W_qa) W_qb
        [c | k_r] = x W_a               kv_lora_rank + rope: the latent and ONE rotated key a token
        [k_n | v] = RMSNorm(c) W_b      [T, H, nope + v_head_dim]
        q_r, k_r rotated at rope_theta; k = [k_n | k_r] with k_r shared by the heads
        o = softmax(q k^T (nope + rope)^-1/2 + causal mask) v         [T, H, v_head_dim]
        out = W_o (o * sigmoid(x W_g))  one gate a head (``head_gate``)

    It trains as plain multi-head attention with keys of ``nope + rope`` and
    values of ``v_head_dim``: ``ops.attention.attention`` takes the two
    widths through the grid-pipelined kernels. Nothing is absorbed into
    ``W_q`` or ``W_o`` and no latent is cached: there is no decode path.
    Device scopes ``mla_proj`` (the latent path: the projections, the
    latent's norm, the rotation, the gate) and ``attn_mla`` (the attention
    call alone: ``%attn_mla.N`` in a trace). ``q`` (``q_b`` under a query
    rank, after ``q_a`` and ``q_norm``), ``kv_b`` and ``o`` take
    ``_heads_dot_general``'s fence behind their weight gradients. ``q`` as
    projected, before its rotation, is sown into ``"intermediates"`` as
    ``queries`` (``q`` is the full-rank projection's own name)."""

    num_heads: int
    spec: LatentAttentionSpec
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0

    @nn.compact
    def __call__(self, x, positions):
        s, h = self.spec, self.num_heads
        nope, rot, d_v = s.qk_nope_head_dim, s.qk_rope_head_dim, s.v_head_dim
        _note_mla_shape(x.shape[1], h, s)

        def heads(name: str, **shape):
            return nn.DenseGeneral(
                use_bias=False, dtype=self.dtype, name=name,
                dot_general=partial(_heads_dot_general, name), **shape,
            )

        flat = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        with jax.named_scope("mla_proj"):
            if s.q_lora_rank is None:
                q = heads("q", features=(h, nope + rot))(x)
            else:
                c_q = RMSNorm(self.norm_eps, name="q_norm")(
                    flat(s.q_lora_rank, name="q_a")(x)
                )
                q = heads("q_b", features=(h, nope + rot))(c_q)
            self.sow("intermediates", "queries", q)
            latent = flat(s.kv_lora_rank + rot, name="kv_a")(x)
            c = RMSNorm(self.norm_eps, name="kv_norm")(latent[..., :s.kv_lora_rank])
            kv = heads("kv_b", features=(h, nope + d_v))(c)
            q_r = rope(q[..., nope:], positions, self.rope_theta)
            k_r = rope(latent[..., None, s.kv_lora_rank:], positions, self.rope_theta)
            q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_r, k_r.shape[:2] + (h, rot))], axis=-1
            )
            q, k, v = (jnp.swapaxes(m, 1, 2) for m in (q, k, kv[..., nope:]))
        with jax.named_scope("attn_mla"):
            out = attention(q, k, v, causal=True, scale=(nope + rot) ** -0.5)
        with jax.named_scope("mla_proj"):
            out = jnp.swapaxes(out, 1, 2)
            if s.head_gate:
                out = out * nn.sigmoid(flat(h, name="g")(x))[..., None]
            return heads("o", features=x.shape[-1], axis=(-2, -1))(out)


class SwiGLU(nn.Module):
    d_ff: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        gate = nn.silu(dense(self.d_ff, name="gate")(x))
        up = dense(self.d_ff, name="up")(x)
        return dense(x.shape[-1], name="down")(gate * up)


class Block(nn.Module):
    num_heads: int
    d_ff: int
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[AttentionFn] = None
    num_experts: int = 0  # >0: expert-parallel MoE FFN instead of SwiGLU
    num_kv_heads: Optional[int] = None
    decode: bool = False
    max_decode_len: int = 2048
    norm_eps: float = 1e-6
    qk_norm: Union[bool, str] = False
    moe: Optional[MoESpec] = None  # dropless expert FFN instead of SwiGLU
    arch: ArchSpec = ArchSpec()
    mixer: str = "attention"       # this block's entry of arch.layer_types

    @nn.compact
    def __call__(self, x, positions):
        # host Python around the same calls, once each time a trace runs this
        # block's Python: a second span of one layer is a second run of it
        mixer, ffn = self._branch_names()
        with obs_trace.span(
            "model_trace", part="block", layer=self.name, mixer=mixer, ffn=ffn
        ):
            return self._branches(x, positions)

    def _branch_names(self):
        """``(mixer, ffn)``: the module names of this block's two branches, as
        ``obs/profile.py:STEP_PARTS`` lists them; None for the one a one-branch
        block lacks."""
        mixer = _MIXER_MODULES.get(self.mixer, "attn")
        ffn = "moe" if self.moe is not None or self.num_experts > 0 else "mlp"
        if not self.arch.one_branch:
            return mixer, ffn
        if self.mixer in FEED_FORWARD_TYPES:
            return None, self.mixer
        return mixer, None

    def _branches(self, x, positions):
        arch = self.arch
        if arch.post_norms not in (False, True, "only"):
            raise ValueError("unknown post_norms %r" % (arch.post_norms,))
        before, after = arch.post_norms != "only", bool(arch.post_norms)
        if arch.one_branch and self.decode:
            raise NotImplementedError("a one-branch block has no decode path")
        # an expert layer routed before the mixer reads the stream as it came in
        routed_early = self.moe is not None and self.moe.route_from == "block_input"
        block_input = x if routed_early else None
        if routed_early and (arch.one_branch or not before):
            raise ValueError(
                'route_from "block_input" wants a block of two branches with a norm '
                "before each: with one_branch or post_norms \"only\" the router's "
                "operand is the experts' own"
            )
        h = RMSNorm(self.norm_eps, name="ln1")(x) if before else x
        if arch.one_branch and self.mixer in FEED_FORWARD_TYPES:
            if self.mixer == "moe" and self.moe is None:
                raise ValueError('a "moe" block needs TransformerLM.moe')
            mixed = self._feed_forward(h, dense=self.mixer == "mlp")
        elif self.mixer == "mamba":
            if self.decode:
                raise NotImplementedError("a Mamba-2 block has no decode cache")
            mixed = Mamba2Mixer(
                arch.mamba, self.dtype, self.norm_eps, name="mamba"
            )(h)
        elif self.mixer == "linear_attention":
            if self.decode:
                raise NotImplementedError(
                    "a gated-delta-rule block has no decode cache"
                )
            mixed = GatedDeltaMixer(
                arch.gated_delta, self.dtype, self.norm_eps, name="gdn"
            )(h)
        elif self.mixer == "kda":
            if self.decode:
                raise NotImplementedError(
                    "a Kimi-delta-attention block has no decode state"
                )
            mixed = KimiDeltaMixer(
                arch.kda, self.dtype, self.norm_eps, name="kda"
            )(h)
        elif self.mixer == "latent_attention":
            if self.decode:
                raise NotImplementedError(
                    "a latent-attention block has no decode cache of latents"
                )
            mixed = LatentAttention(
                self.num_heads, arch.latent_attention, self.dtype, self.norm_eps,
                arch.rope_theta, name="attn",
            )(h, positions)
        elif self.mixer == "conv":
            if self.decode:
                raise NotImplementedError(
                    "a short-convolution block has no decode cache"
                )
            mixed = ShortConvMixer(
                arch.short_conv or ShortConvSpec(), self.dtype, name="sconv"
            )(h)
        elif self.mixer in ("attention", "sliding_attention", "sparse_attention"):
            sliding = self.mixer == "sliding_attention"
            selecting = self.mixer == "sparse_attention"
            diffusing = arch.block_diffusion is not None
            if sliding and arch.sliding_window is None:
                raise ValueError("a sliding_attention layer needs sliding_window")
            if selecting and self.decode:
                raise NotImplementedError(
                    "a sparse-attention block has no decode cache of indexer keys"
                )
            mixed = Attention(
                self.num_heads, self.dtype, self.attention_fn,
                num_kv_heads=self.num_kv_heads, decode=self.decode,
                max_decode_len=self.max_decode_len, qk_norm=self.qk_norm,
                norm_eps=self.norm_eps, head_dim=arch.head_dim,
                rope=sliding if arch.rope == "sliding" else arch.rope,
                rope_theta=arch.rope_theta,
                scale=arch.attn_scale,
                window=arch.sliding_window if sliding else None,
                gate=arch.attn_gate,
                # a model of both kinds tells their device time apart
                kernel_scope=BLOCK_DIFFUSION_SCOPE if diffusing
                else None if arch.sliding_window is None
                else ("attn_window" if sliding else "attn_full"),
                sparse=(arch.sparse_attention or SparseAttentionSpec())
                if selecting else None,
                block_diffusion=arch.block_diffusion.block if diffusing else None,
                name="attn",
            )(h, positions)
        else:
            raise ValueError(
                "unknown layer type %r: a block's mixer is one of %s, and under "
                "one_branch its one branch may be a feed-forward alone, one of %s"
                % (self.mixer, ", ".join(LAYER_TYPES), ", ".join(FEED_FORWARD_TYPES))
            )
        if after:
            mixed = RMSNorm(self.norm_eps, name="ln1_post")(mixed)
        x = x + _times(mixed, arch.residual_multiplier)
        if arch.one_branch:
            return x
        h = RMSNorm(self.norm_eps, name="ln2")(x) if before else x
        ff = self._feed_forward(h, route_x=block_input)
        if after:
            ff = RMSNorm(self.norm_eps, name="ln2_post")(ff)
        return x + _times(ff, arch.residual_multiplier)

    def _feed_forward(self, h, dense: bool = False, route_x=None):
        """The block's feed-forward on its normed input: the expert layer if
        the block has one and ``dense`` does not overrule it, else the SwiGLU.
        ``route_x``: the expert layer's second operand (``MoESpec.route_from``)."""
        if self.moe is not None and not dense:
            return DroplessMoE(
                **dataclasses.asdict(self.moe), dtype=self.dtype, name="moe"
            )(h, route_x)
        if self.num_experts > 0 and not dense:
            return SwitchMoE(
                num_experts=self.num_experts, d_ff=self.d_ff,
                dtype=self.dtype, name="moe",
            )(h)
        return SwiGLU(self.d_ff, self.dtype, name="mlp")(h)


def _remat_policy(name: Optional[str]):
    """Resolve a TransformerLM.remat_policy string to a jax.checkpoint
    policy. ``"save_flash"`` keeps the attention kernel's forward
    products (out + lse, tagged by ``checkpoint_name`` inside the
    custom_vjp fwd — ops/attention.py::_name_residuals) so the backward
    consumes them instead of re-running the forward kernel: O(B*T*D)
    extra HBM per layer buys back a full flash forward per layer per
    step. ``"save_flash_qkv"`` additionally skips the q/k/v projection
    recompute. Both also keep what a gated-delta-rule layer's sequential
    carry leaves (``gdn_carry``: the float32 state every chunk inherits,
    ``V_new``; ``gdn_out``: the rule's output and final state) and every
    chunk's inverse (``gdn_inverse``), so that the block's recomputation
    runs neither the carry's loop nor the solve again
    (ops/gated_delta.py), and the three values a row that a
    sparse-attention layer's select kernel found (``dsa_select``: 192 KB a
    layer; the recomputation then makes the scores and the mask again, not
    the bisection or its row sums; ops/sparse_attention.py) with the causal
    tiles of ``dL_I/dI`` that its indexer's loss made in its forward call
    (``dsa_di``: 277 MB a layer at 16,384 tokens in bfloat16; neither the
    backward nor the recomputation then runs the target kernel), and of an
    expert layer what its route decided and what its held experts'
    buffer multiplied (``models/moe.py:REMAT_NAMES``; ``moe_route``: the
    router's float32 logits ``[N, E]``, 16.8 MB a layer at 8192 tokens and
    512 experts, and the chosen experts, their scores, the sort's two
    permutations and the counts, under 2 MB; ``moe_held``: the ``gate`` / ``up`` products
    over the buffer of ``2 N k count / E`` rows, 6 MB a layer in Ling's
    cell, 18 in Solar's, 30 in Nemotron's, 50 in LFM2's, 67 in
    Trinity's, 100 in Keye's; the whole-``N k`` form bears no name), so
    that the recomputation runs no router matmul, ``top_k``, sort or
    buffer-sized grouped matmul again, and what a recurrent mixer's in
    projections hand on (``models/mamba.py:REMAT_NAMES``; ``mixer_in``:
    ``Mamba2Mixer``'s ``[z | xBC | dt]`` and ``GatedDeltaMixer``'s ``proj``,
    each whole and before its slices, and ``KimiDeltaMixer``'s ``q``,
    ``k``, ``v`` before their convolutions, its float32 ``f``, ``gate`` and
    ``b``, of a low-rank pair the second matrix's output; one sequence of
    8192 leaves 152 MB a layer in Nemotron's cell, 139 in Granite's, 142
    in OLMo-hybrid's, 201 in Ling's, 101 in Solar's; ``ShortConvMixer``'s
    bears no name: the chip read LFM2's step no faster for it), so that
    the convolution, the gate and the rule's inputs are remade from the
    kept array and the recomputation runs no in projection's matmul
    again; a model without such a layer
    bears none of the names. ``None``/"full" is classic
    recompute-everything."""
    if name in (None, "full"):
        return None
    if name == "save_flash":
        return jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse", *GDN_NAMES, *DSA_NAMES, *MOE_NAMES, *MIXER_NAMES
        )
    if name == "save_flash_qkv":
        return jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse", "flash_qkv", *GDN_NAMES, *DSA_NAMES, *MOE_NAMES,
            *MIXER_NAMES,
        )
    raise ValueError("unknown remat_policy %r" % (name,))


@jax.custom_vjp
def _head_matmul(x, w):
    """fp32 ``x @ w`` of two operands in one dtype: see ``LMHead``."""
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _head_matmul_fwd(x, w):
    return _head_matmul(x, w), (x, w)


def _head_matmul_bwd(residuals, ct):
    x, w = residuals
    # the barrier makes the logits' gradient one array that both matmuls
    # read: without it XLA clones its producer (softmax - one_hot over the
    # fp32 logits, exp and all) into each of them
    g = jax.lax.optimization_barrier(ct.astype(x.dtype))
    rows = tuple(range(x.ndim - 1))
    dx = jax.lax.dot_general(
        g, w, (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dw = jax.lax.dot_general(
        x, g, ((rows, rows), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return dx.astype(x.dtype), dw.astype(w.dtype)


_head_matmul.defvjp(_head_matmul_fwd, _head_matmul_bwd)


class LMHead(nn.Module):
    """Vocabulary projection with fp32 logits from input-dtype operands.

    The old ``nn.Dense(dtype=float32)`` upcast x AND the kernel to fp32
    before the matmul — on the v5e MXU that runs at a fraction of the
    bf16 rate, and at vocab 32k the head is one of the largest matmuls
    in the model. Here the multiply runs in the activation dtype (bf16
    in training) with fp32 ACCUMULATION via preferred_element_type, so
    the softmax still sees fp32 logits. The backward keeps the rule: the
    logits' cotangent is cast to the activation dtype ONCE, as one array,
    and dx and dW are both matmuls over it with fp32 accumulation, each
    handed back in its operand's dtype, as autodiff hands a cast operand
    its cotangent. Param path/shape match the old nn.Dense exactly
    (``lm_head/kernel``) — checkpoints stay loadable.
    """

    vocab_size: int

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (x.shape[-1], self.vocab_size),
        )
        return _head_matmul(x, kernel.astype(x.dtype))


def _scored_cross_entropy(logits, labels, scored):
    """Mean softmax cross-entropy over the positions ``scored`` marks, by the
    rows' one owner (``ops/cross_entropy.py``), as ``train/step.py``'s heads."""
    ce, _ = rows_cross_entropy(logits, labels, site="mtp_head")
    return jnp.sum(ce * scored) / jnp.maximum(jnp.sum(scored), 1)


class TransformerLM(nn.Module):
    """The decoder: an embedding, ``num_layers`` blocks, a last norm and a
    head over the vocabulary; its output is the logits ``[B, T, vocab]``.

    With ``ArchSpec.mtp`` (and not in ``decode``) a multi-token-prediction
    module of depth 1 runs behind the last norm, built from the model's own
    parts (DeepSeek-V3, arXiv:2412.19437, equations 21 to 25), for the normed
    last stream ``hbar`` (what the head reads) and the model's own ``tokens``::

        u_i = [N_e(Emb(t_{i+1})) ; N_h(hbar_i)] W_eh      the embedding half first
        g   = Block(u)                  one more block: the last layer's mixer, the
                                        model's feed-forward, positions as given
        P_i = softmax(Head(N_m(g_i)))   Emb and Head are the model's own parameters
        L   = mean_i -log P_i[t_{i+2}]  over i = 0 .. T-3

    It runs over all T positions, so that its attention call has the trunk's
    shape (the ids shifted by one and by two, padded with id 0 at the end) and
    the last two positions are not scored: neither has a target among the
    model's input, and being last under a causal mixer neither reaches a
    scored one. ``loss_weight * L`` is sown into ``"losses"`` (the step adds
    it to the objective), ``L`` into ``"metrics"`` as ``mtp_loss`` and the
    module's logits into ``"intermediates"``; the model's output stays the main
    logits. Parameters ``mtp_enorm``, ``mtp_hnorm``, ``mtp_eh_proj``,
    ``mtp_block`` and ``mtp_norm`` beside the model's; device scopes ``mtp``
    (the whole module; the block's own scopes lie inside it), ``mtp_join``
    (the two norms, the concatenation, ``W_eh``) and ``mtp_head`` (the last
    norm, the head's second use, the cross-entropy)."""

    vocab_size: int = 32000
    d_model: int = 512
    num_heads: int = 8
    num_layers: int = 6
    d_ff: int = 1408
    dtype: Any = jnp.bfloat16
    remat: bool = False
    # policy under remat=True: "save_flash" (default) saves the attention
    # forward's out+lse so the backward never re-runs the kernel;
    # "save_flash_qkv" also saves q/k/v; "full"/None recomputes everything
    remat_policy: Optional[str] = "save_flash"
    attention_fn: Optional[AttentionFn] = None
    num_experts: int = 0   # with moe_every: MoE width of the routed blocks
    moe_every: int = 2     # every Nth block is MoE when num_experts > 0
    num_kv_heads: Optional[int] = None  # < num_heads = GQA; 1 = MQA
    decode: bool = False                # KV-cached autoregressive mode
    max_decode_len: int = 2048
    norm_eps: float = 1e-6              # every RMSNorm's epsilon
    # RMSNorm over projected q and k: True over the whole width (OLMoE),
    # "head" over each head's own values (see Attention)
    qk_norm: Union[bool, str] = False
    # every block's FFN (past arch.dense_layers) as a dropless top-k expert
    # layer (models/moe.py); the older Switch pair above stays for
    # SwitchMoE until ROADMAP D6
    moe: Optional[MoESpec] = None
    # the layer pattern (mixers, windows, leading dense layers), the
    # attention's head size / positions and their base / score scale / gate, where a
    # block's norms sit, a tied head and Granite's multipliers; None: the
    # dense model
    arch: Optional[ArchSpec] = None

    @nn.compact
    def __call__(self, tokens, positions=None):
        arch = self.arch or ArchSpec()
        layer_types = arch.layer_types or ("attention",) * self.num_layers
        if len(layer_types) != self.num_layers:
            raise ValueError(
                "%d layer_types for num_layers %d"
                % (len(layer_types), self.num_layers)
            )
        with obs_trace.span("model_trace", part="embed"):
            embed = nn.Embed(
                self.vocab_size, self.d_model,
                dtype=self.dtype, name="embed",
            )
            x = _times(embed(tokens), arch.embedding_multiplier)
        if arch.block_diffusion is not None:
            length = self._block_diffusion_length(arch, layer_types, tokens)
            if positions is None:
                # position i of either copy is position i of the sequence
                positions = jnp.broadcast_to(
                    (jnp.arange(2 * length) % length)[None, :], tokens.shape
                )
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1])[None, :], tokens.shape
            )
        block = Block
        if self.remat:
            block = nn.remat(
                Block, static_argnums=(),
                policy=_remat_policy(self.remat_policy),
            )

        def switch_width(i: int) -> int:  # the older Switch pair's pattern
            routed = self.num_experts > 0 and (i + 1) % self.moe_every == 0
            return self.num_experts if routed else 0

        for i in range(self.num_layers):
            x = block(
                self.num_heads, self.d_ff, self.dtype, self.attention_fn,
                switch_width(i), self.num_kv_heads, self.decode, self.max_decode_len,
                self.norm_eps, self.qk_norm,
                None if i < arch.dense_layers else self.moe, arch,
                layer_types[i], name="layer_%d" % i,
            )(x, positions)
        if arch.block_diffusion is not None:
            # the noised half alone is scored: the clean half was keys and
            # values, and its last layer's output is never read
            x = x[:, length:]
        with obs_trace.span("model_trace", part="head"):
            x = RMSNorm(self.norm_eps, name="ln_f")(x)
            if arch.tie_embeddings:
                logits = _head_matmul(x, embed.embedding.astype(x.dtype).T)
            else:
                head = LMHead(self.vocab_size, name="lm_head")
                logits = head(x)
        if arch.mtp is not None and not self.decode:
            if arch.mtp.depth != 1:
                raise ValueError(
                    "a multi-token module of depth %r: one is what runs" % (arch.mtp.depth,)
                )
            t = tokens.shape[1]
            # position i joins token i+1 and is scored against token i+2
            ahead = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
            targets = jnp.pad(tokens[:, 2:], ((0, 0), (0, min(t, 2))))
            scored = jnp.broadcast_to(jnp.arange(t) < t - 2, tokens.shape)
            obs_trace.get_tracer().note_once(
                "mtp_shape", depth=arch.mtp.depth, tq=t, scored=max(t - 2, 0),
                loss_weight=arch.mtp.loss_weight, vocab=self.vocab_size,
                mixer=layer_types[-1], logit_bytes=4 * tokens.size * self.vocab_size,
            )
            with obs_trace.span("model_trace", part="mtp"), jax.named_scope("mtp"):
                with jax.named_scope("mtp_join"):
                    joined = jnp.concatenate([
                        RMSNorm(self.norm_eps, name="mtp_enorm")(
                            _times(embed(ahead), arch.embedding_multiplier)
                        ),
                        RMSNorm(self.norm_eps, name="mtp_hnorm")(x),
                    ], axis=-1)
                    u = nn.Dense(
                        self.d_model, use_bias=False, dtype=self.dtype, name="mtp_eh_proj"
                    )(joined)
                g = block(
                    self.num_heads, self.d_ff, self.dtype, self.attention_fn,
                    switch_width(self.num_layers), self.num_kv_heads, False,
                    self.max_decode_len,
                    self.norm_eps, self.qk_norm, self.moe, arch,
                    layer_types[-1], name="mtp_block",
                )(u, positions)
                with jax.named_scope("mtp_head"):
                    g = RMSNorm(self.norm_eps, name="mtp_norm")(g)
                    # the kernel, not the module once more: under the module's
                    # name the second use would read as the main head's
                    w = (
                        embed.embedding.T if arch.tie_embeddings
                        else head.variables["params"]["kernel"]
                    )
                    ahead_logits = _times(
                        _head_matmul(g, w.astype(g.dtype)), 1.0 / arch.logits_scaling
                    )
                    mtp_loss = _scored_cross_entropy(ahead_logits, targets, scored)
            self.sow("intermediates", "mtp_logits", ahead_logits)
            self.sow("losses", "mtp_loss", arch.mtp.loss_weight * mtp_loss)
            self.sow("metrics", "mtp_loss", mtp_loss)
        return _times(logits, 1.0 / arch.logits_scaling)

    def _block_diffusion_length(self, arch, layer_types, tokens) -> int:
        """``L`` of a block-diffusion call's ``[B, 2 L]`` ids, after refusing
        by name what does not go with the spec; one ``block_diffusion_shape``
        instant a shape and stage."""
        spec = arch.block_diffusion
        refused = [
            name for name, given in (
                ("decode=True", self.decode),
                ("a window (sliding_window)", arch.sliding_window is not None),
                ("sparse_attention", arch.sparse_attention is not None),
                ("mtp", arch.mtp is not None),
                ("an attention_fn", self.attention_fn is not None),
                ("a mixer that is not attention (layer_types %s)"
                 % sorted(set(layer_types) - {"attention"}),
                 set(layer_types) != {"attention"}),
                ("one_branch", arch.one_branch),
            ) if given
        ]
        if refused:
            raise NotImplementedError(
                "TransformerLM: block_diffusion with %s: the step runs a clean "
                "and a noised copy through attention layers under one mask and "
                "scores the noised half" % ", ".join(refused)
            )
        length, odd = divmod(tokens.shape[1], 2)
        if odd or spec.block < 1 or length % spec.block:
            raise ValueError(
                "TransformerLM: block_diffusion wants [B, 2 L] ids, x_0 then "
                "x_t, with L whole blocks of %d: got %d positions"
                % (spec.block, tokens.shape[1])
            )
        obs_trace.get_tracer().note_once(
            "block_diffusion_shape", length=length, block=spec.block,
            mask_id=spec.mask_id, positions=tokens.shape[1],
            head_rows=tokens.shape[0] * length,
            logit_bytes=4 * tokens.shape[0] * length * self.vocab_size,
        )
        return length
