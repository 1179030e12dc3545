"""PR 58's chip probe: the scalar delta rule's chunk-local stage alone at the
cell's shape (one sequence of 8192, 15 heads of 96 / 192), in two layouts, each
from the arrays as the mixer's convolution and norms leave them (the steps
minor: ``[B, H d, T]``) to the carry's operands and back to gradients of that
layout:

- ``lanes``: ``ops/gated_delta.py``'s kernels as they stand, the steps along
  the lanes, two chunks a grid step, a head ``d`` sublanes wherever it starts:
  no pass of XLA's before or after them;
- ``tiles``: every head laid on whole lane tiles of a row by the call (``[B,
  T, 15 * 128]`` and ``[B, T, 15 * 256]``: 96 on 128 lanes, 192 on 256, zero
  channels), a grid step one chunk, a head's true width read from its tile's
  first lane, the loop over pairs of heads walking the odd last one: the
  kernels PR 58 built first, kept in this file; around them the transposing
  and padding passes XLA makes of the call's ``jnp.pad`` and ``reshape``, and
  the slices back.

and the plain form's stage (``_scalar_plain``), forward and with its gradients.

    chiprun --chips 1 -- python3 bench_results/gdn_layout_probe.py

A program is timed as ``bench_results/ssd_probe.py`` times one: the difference
between one jitted function that runs it 21 times and one that runs it once,
over 20. Prints one JSON line a program and writes them to
``chiprun_out/gdn_layout_probe.jsonl``; README.md has PR 58's numbers. The two
layouts' operands are compared with the plain form's on the way.
"""

import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.ad_checkpoint import checkpoint_name  # noqa: E402

from bench_results.ssd_probe import wall  # noqa: E402
from edl_tpu.ops import gated_delta as G  # noqa: E402

T, H, D_K, D_V = 8192, 15, 96, 192


# -- layout ``tiles``: the kernels as PR 58 first built them ------------------


def _head_of(h, width, lanes=None):
    """The lanes of head ``h`` in a row of ``heads * width`` (``lanes``: its
    first so many); ``h`` the loop's index or, in an odd tail, a number."""
    from jax.experimental import pallas as pl

    start = h * width if isinstance(h, int) else pl.multiple_of(h * width, 128)
    return pl.ds(start, lanes or width)


def _inverse_at(inverse_ref, pair, half, width=2):
    """Where head ``width * pair + half``'s ``T`` lies in a block ``[1, 1, (H
    + 1) / 2, C, 2 C]``: a pair of heads side by side along the lanes."""
    size = inverse_ref.shape[3]
    side = half % 2
    return 0, 0, width // 2 * pair + half // 2, slice(None), slice(side * size, (side + 1) * size)


_HEADS_A_ROUND = 2       # heads a round of the scalar kernels' loops (even)


def _tile(ref, heads, at, width=None):
    """Head ``at``'s (``(pair, half)`` of the loop) ``[C, width]`` out of a
    block ``[1, C, H stride]`` of rows whose heads start on whole lane tiles:
    its first ``width`` lanes (None: the whole stride)."""
    stride = ref.shape[2] // heads
    return ref[0, :, _head_of(_HEADS_A_ROUND * at[0] + at[1], stride, width)]


def _put(ref, heads, at, tile):
    """``tile`` ``[C, width]`` into the first lanes of head ``at``'s stride of
    a block ``[1, C, H stride]``; the lanes after them are left as they are."""
    stride = ref.shape[2] // heads
    ref[0, :, _head_of(_HEADS_A_ROUND * at[0] + at[1], stride, tile.shape[1])] = tile


def _decay_between(gamma):
    """``[C, C]``: ``exp(gamma_i - gamma_j)`` on and below the diagonal, zeros
    above it, for a head's running sum ``gamma`` ``[C, 1]`` (its row ``[1,
    C]`` comes off the diagonal of a tile, exactly)."""
    shape = (gamma.shape[0],) * 2
    row, col = G._iota(shape, 0), G._iota(shape, 1)
    along = jnp.sum(jnp.where(row == col, gamma, 0.0), axis=0, keepdims=True)
    return jnp.exp(jnp.where(row >= col, gamma - along, -jnp.inf))


def tiles_inverse_kernel(k_ref, g_ref, beta_ref, inverse_ref):
    """Every head's ``T = (I + A)^-1`` of one chunk, float32 ``[(H + 1) / 2,
    C, 2 C]``: a pair of heads a row (of an odd last head the first half)."""
    heads = beta_ref.shape[2]
    gammas, betas = G._running_sum(g_ref[0]), beta_ref[0]                 # [C, H]

    def head(pair, half, carry):
        h = _HEADS_A_ROUND * pair + half
        k = _tile(k_ref, heads, (pair, half))  # the stride whole: its last lanes are zeros
        system = G._column_of(betas, h) * G._times_transposed(k, k) * _decay_between(
            G._column_of(gammas, h)
        )
        inverse_ref[_inverse_at(inverse_ref, pair, half, _HEADS_A_ROUND)] = G._solve(system)
        return carry

    G._over_heads(heads, head, width=_HEADS_A_ROUND)


def _decays(g_ref):
    """Every head's running sum of ``g`` over the chunk and what the operands
    take of it, ``[C, H]`` each: ``gamma``, ``exp(gamma)``, ``exp(gamma_C -
    gamma)``; and ``exp(gamma_C)`` ``[1, H]``."""
    gammas = G._running_sum(g_ref[0])
    last = gammas[gammas.shape[0] - 1:]
    return gammas, jnp.exp(gammas), jnp.exp(last - gammas), jnp.exp(last)


def tiles_operands_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, inverse_ref,
                        w_ref, u_ref, k_out_ref, whole_ref, q_in_ref, scores_ref):
    """From one chunk's inputs and ``T`` to the carry's operands (``w``, ``u``,
    ``k_out``, ``whole``) and the output stage's (``q_in``, the masked decayed
    scores), a ``[C, d]`` tile a head each, at the true widths."""
    f32, dtype = jnp.float32, q_ref.dtype
    heads = beta_ref.shape[2]
    d_k, d_v = w_ref.shape[4], u_ref.shape[4]
    gammas, growns, to_ends, wholes = _decays(g_ref)
    betas = beta_ref[0]
    whole_ref[0, 0] = wholes
    dot = functools.partial(jnp.dot, preferred_element_type=f32)

    def head(pair, half, carry):
        h, at = _HEADS_A_ROUND * pair + half, (pair, half)
        q, k = _tile(q_ref, heads, at, d_k), _tile(k_ref, heads, at, d_k)
        k32 = k.astype(f32)
        beta, grown = G._column_of(betas, h), G._column_of(growns, h)
        between = _decay_between(G._column_of(gammas, h))
        scores_ref[0, 0, h] = (G._times_transposed(q, k) * between).astype(dtype)
        inverse = inverse_ref[_inverse_at(inverse_ref, pair, half, _HEADS_A_ROUND)].astype(dtype)
        k_in = (k32 * (beta * grown)).astype(dtype)
        v_in = (_tile(v_ref, heads, at, d_v).astype(f32) * beta).astype(dtype)
        w_ref[0, 0, h] = dot(inverse, k_in).astype(dtype)
        u_ref[0, 0, h] = dot(inverse, v_in)
        k_out_ref[0, 0, h] = (k32 * G._column_of(to_ends, h)).astype(dtype)
        q_in_ref[0, 0, h] = (q.astype(f32) * grown).astype(dtype)
        return carry

    G._over_heads(heads, head, width=_HEADS_A_ROUND)


def tiles_backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, inverse_ref,
                        dw_ref, du_ref, dk_out_ref, dwhole_ref, dq_in_ref, dscores_ref,
                        dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref):
    """The cotangents of one chunk's inputs from those of the six operands:
    the tile's intermediates are made again in VMEM from the inputs and the
    saved ``T``. A head leaves its column of ``dbeta`` and of the running
    sum's cotangent; ``dg`` is every head's reverse running sum at the end."""
    f32, dtype = jnp.float32, q_ref.dtype
    heads, size = beta_ref.shape[2], q_ref.shape[1]
    d_k, d_v = dw_ref.shape[4], du_ref.shape[4]
    gammas, growns, to_ends, wholes = _decays(g_ref)
    betas = beta_ref[0]
    down = lambda a: jnp.sum(a, axis=0, keepdims=True)      # noqa: E731 — [1, d]
    across = lambda a: jnp.sum(a, axis=1, keepdims=True)    # noqa: E731 — [C, 1]
    highest = dict(precision=jax.lax.Precision.HIGHEST)

    def head(pair, half, carry):
        h, at = _HEADS_A_ROUND * pair + half, (pair, half)
        q, k = _tile(q_ref, heads, at, d_k), _tile(k_ref, heads, at, d_k)
        q32, k32, v32 = q.astype(f32), k.astype(f32), _tile(v_ref, heads, at, d_v).astype(f32)
        beta, grown, to_end = (G._column_of(a, h) for a in (betas, growns, to_ends))
        between = _decay_between(G._column_of(gammas, h))
        exact = inverse_ref[_inverse_at(inverse_ref, pair, half, _HEADS_A_ROUND)]
        inverse = exact.astype(dtype)
        k_in = (k32 * (beta * grown)).astype(dtype)
        v_in = (v32 * beta).astype(dtype)

        # through w = T k_in and u = T v_in
        dw, du = dw_ref[0, 0, h], du_ref[0, 0, h].astype(dtype)
        d_inverse = G._times_transposed(dw, k_in) + G._times_transposed(du, v_in)
        d_k_in, d_v_in = G._transposed_times(inverse, dw), G._transposed_times(inverse, du)

        # through the solve, as ``_unit_lower_inverse_bwd``: dA = -T^T dT T^T
        d_system = -G._times_transposed(
            G._transposed_times(exact, d_inverse, **highest), exact, **highest
        )
        row, col = G._iota(d_system.shape, 0), G._iota(d_system.shape, 1)
        d_system = jnp.where(row > col, d_system, 0.0)
        d_scores = jnp.where(row >= col, dscores_ref[0, 0, h].astype(f32), 0.0)

        # through A = beta (K K^T) o between and the scores (Q K^T) o between
        kk, qk = G._times_transposed(k, k), G._times_transposed(q, k)
        d_beta = across(d_system * kk * between)
        d_between = (d_system * beta * kk + d_scores * qk) * between
        both = jnp.concatenate(
            [d_system * beta * between, d_scores * between], axis=0
        ).astype(dtype)                                                  # [2 C, C]
        d_rows = jnp.dot(both, k, preferred_element_type=f32)
        d_cols = G._transposed_times(both, jnp.concatenate([k, q], axis=0))
        # gamma_i gathers a row of between's cotangent, gamma_j gives a column
        by_col = across(jnp.where(row == col, down(d_between), 0.0))

        # through the elementwise operands
        dk_out, dq_in = dk_out_ref[0, 0, h].astype(f32), dq_in_ref[0, 0, h].astype(f32)
        leaving, fed = across(dk_out * k32) * to_end, across(d_k_in * k32)
        d_gamma = (
            across(d_between) - by_col + fed * (beta * grown) - leaving
            + across(dq_in * q32) * grown
        )
        d_beta = d_beta + fed * grown + across(d_v_in * v32)
        _put(dq_ref, heads, at, (d_rows[size:] + dq_in * grown).astype(dtype))
        _put(dk_ref, heads, at, (
            d_rows[:size] + d_cols + d_k_in * (beta * grown) + dk_out * to_end
        ).astype(dtype))
        _put(dv_ref, heads, at, (d_v_in * beta).astype(dtype))
        mine = G._iota(betas.shape, 1) == h
        return tuple(
            jnp.where(mine, column, columns)
            for column, columns in zip((d_beta, d_gamma, leaving), carry)
        )

    d_betas, d_gammas, leavings = G._over_heads(
        heads, head, (jnp.zeros(betas.shape, f32),) * 3, _HEADS_A_ROUND
    )
    # the chunk's last step: what left through k_out and through whole
    d_last = down(leavings) + dwhole_ref[0, 0] * wholes
    d_gammas = d_gammas + jnp.where(G._iota(betas.shape, 0) == size - 1, d_last, 0.0)
    dg_ref[0] = G._running_sum(d_gammas, reverse=True)
    dbeta_ref[0] = d_betas


_SCALAR_INPUTS = ("keys", "keys", "values", "steps", "steps")            # q k v g beta
_SCALAR_OPERANDS = ("w", "u", "w", "whole", "q_in", "scores")            # ..., k_out, ...


def _scalar_run(kernel, ins, outs, operands, widths, interpret):
    """One of the scalar rule's three kernels on ``operands``, whose kinds
    ``ins`` names (``outs`` those of its results): a grid step a chunk, every
    block a chunk of every head; ``widths`` the true ``(d_k, d_v)`` (None
    where no operand has them: the strides then)."""
    batch, steps, h = operands[ins.index("steps")].shape
    size, nc = G._KERNEL_CHUNK, steps // G._KERNEL_CHUNK
    keys = operands[ins.index("keys")]
    values = operands[ins.index("values")] if "values" in ins else keys
    d_k, d_v = widths or (keys.shape[2] // h, values.shape[2] // h)
    f32, dtype = jnp.float32, keys.dtype
    here, first = (lambda b, n: (b, n)), (lambda b, n: (n, b))  # noqa: E731
    tiles = lambda lead, d, dt, place: (  # noqa: E731 — a [C, d] tile a head
        (*lead, h, size, d), dt, (1, 1, h, size, d), place
    )
    kinds = dict(
        # rows of every head's lane tiles; g and beta as the mixer has them
        keys=(keys.shape, dtype, (1, size, keys.shape[2]), here),
        values=(values.shape, dtype, (1, size, values.shape[2]), here),
        steps=((batch, steps, h), f32, (1, size, h), here),
        # every chunk's T, a pair of heads a row; the scores [b n h c s]
        inverse=((batch, nc, -(-h // 2), size, 2 * size), f32,
                 (1, 1, -(-h // 2), size, 2 * size), here),
        scores=tiles((batch, nc), size, dtype, here),
        q_in=tiles((batch, nc), d_k, dtype, here),
        # the carry's operands, chunks first (k_out as w)
        w=tiles((nc, batch), d_k, dtype, first),
        u=tiles((nc, batch), d_v, f32, first),
        whole=((nc, batch, 1, h), f32, (1, 1, 1, h), first),
    )
    return G._chunk_call(kernel, (batch, nc), kinds, ins, outs, operands, interpret)


# jitted, as ``ops/causal_conv.py``'s: a step traces and lowers each body once
@functools.partial(jax.jit, static_argnums=3)
def _scalar_inverse_call(k, g, beta, interpret):
    ins = ("keys", "steps", "steps")
    return _scalar_run(tiles_inverse_kernel, ins, ("inverse",), (k, g, beta), None, interpret)[0]


@functools.partial(jax.jit, static_argnums=(6, 7))
def _scalar_operands_call(q, k, v, g, beta, inverse, widths, interpret):
    ins = (*_SCALAR_INPUTS, "inverse")
    return _scalar_run(
        tiles_operands_kernel, ins, _SCALAR_OPERANDS, (q, k, v, g, beta, inverse), widths, interpret
    )


@functools.partial(jax.jit, static_argnums=(12, 13))
def _scalar_backward_call(q, k, v, g, beta, inverse, dw, du, dk_out, dwhole, dq_in, dscores,
                          widths, interpret):
    ins = (*_SCALAR_INPUTS, "inverse", *_SCALAR_OPERANDS)
    operands = (q, k, v, g, beta, inverse, dw, du, dk_out, dwhole, dq_in, dscores)
    return _scalar_run(tiles_backward_kernel, ins, _SCALAR_INPUTS, operands, widths, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scalar_kernels(q, k, v, g, beta, widths, interpret):
    """The scalar rule's chunk-local stage by the kernels, for ``q``, ``k``
    ``[B, T, H s_k]`` and ``v`` ``[B, T, H s_v]`` (a head the first ``d_k`` /
    ``d_v`` = ``widths`` lanes of a stride of whole lane tiles, zeros after
    them) and float32 ``g``, ``beta`` ``[B, T, H]``: ``(w, u, k_out, whole,
    q_in, scores)`` as ``_scalar_plain`` lays them out, but ``k_out`` ``[n b h
    c k]`` and ``q_in`` ``[b n h c k]`` a tile a head and ``whole`` ``[n b 1
    h]``."""
    inverse = _scalar_inverse_call(k, g, beta, interpret)
    return _scalar_operands_call(q, k, v, g, beta, inverse, widths, interpret)


def _scalar_kernels_fwd(q, k, v, g, beta, widths, interpret):
    # T apart from the rest and by name, as ``_local_kernels_fwd``
    inverse = checkpoint_name(_scalar_inverse_call(k, g, beta, interpret), G.INVERSE_NAME)
    operands = _scalar_operands_call(q, k, v, g, beta, inverse, widths, interpret)
    return operands, (q, k, v, g, beta, inverse)


def _scalar_kernels_bwd(widths, interpret, residuals, cotangents):
    return _scalar_backward_call(*residuals, *cotangents, widths, interpret)


_scalar_kernels.defvjp(_scalar_kernels_fwd, _scalar_kernels_bwd)




# -- the programs timed -------------------------------------------------------

G_AT = 3  # where ``g`` stands among a stage's operands


def inputs(seed):
    """As the mixer holds them: the steps minor."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda m: m / jnp.linalg.norm(m, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (1, T, H, D_K))) * D_K ** -0.5
    k = unit(jax.random.normal(keys[1], (1, T, H, D_K)))
    v = jax.random.normal(keys[2], (1, T, H, D_V))
    g = -jax.nn.softplus(jax.random.normal(keys[3], (1, T, H)) - 2.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (1, T, H)))
    minor = lambda a: jnp.swapaxes(a.reshape(1, T, -1), 1, 2)  # noqa: E731
    bf16 = lambda a: minor(a).astype(jnp.bfloat16)  # noqa: E731
    return bf16(q), bf16(k), bf16(v), minor(g), minor(beta)


def by_steps(a, d=None):
    """``[B, H d, T]`` as the ``[B, T, H, d]`` (``[B, T, H]``) the rule is handed."""
    a = jnp.swapaxes(a, 1, 2)
    return a if d is None else a.reshape(1, T, H, d)


def laid(a, d):
    """Layout ``tiles``: every head on whole lane tiles, a row's heads side by side."""
    return jnp.pad(by_steps(a, d), ((0, 0),) * 3 + ((0, -d % 128),)).reshape(1, T, -1)


def tiles_stage(q, k, v, g, beta):
    return _scalar_kernels(laid(q, D_K), laid(k, D_K), laid(v, D_V), by_steps(g), by_steps(beta),
                           (D_K, D_V), False)


def lanes_stage(q, k, v, g, beta):
    return G._scalar_kernels(q, k, v, g, beta, False)


def plain_stage(q, k, v, g, beta):
    return G._scalar_plain(by_steps(q, D_K), by_steps(k, D_K), by_steps(v, D_V),
                           by_steps(g), by_steps(beta), 64)[:6]


def with_gradients(stage, weights):
    def run(*operands):
        values, pull = jax.vjp(stage, *operands)
        return (*values, *pull(tuple(w.astype(o.dtype) for w, o in zip(weights, values))))

    return run


def a_call(program, operands, more=20):
    """Milliseconds of one run of ``program`` on the device: every output
    behind a barrier, so that none is narrowed to the element read of it."""

    def times(n):
        def run(*ops):
            total = jnp.float32(0)
            for i in range(n):
                ops_i = list(ops)
                ops_i[G_AT] = ops[G_AT] * (1.0 + 1e-6 * i)
                outs = jax.lax.optimization_barrier(tuple(program(*ops_i)))
                total += sum(o.ravel()[0].astype(jnp.float32) for o in outs)
            return total

        return jax.jit(run)

    return (wall(times(1 + more), *operands) - wall(times(1), *operands)) / more


def rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def main():
    lines = []

    def say(**line):
        line["device_kind"] = jax.devices()[0].device_kind
        lines.append(line)
        print(json.dumps(line), flush=True)

    operands = jax.block_until_ready(jax.jit(lambda *a: a)(*inputs(58)))
    q, k, v, g, beta = operands
    stages = {"lanes": lanes_stage, "tiles": tiles_stage, "plain": plain_stage}
    want = jax.jit(plain_stage)(*operands)
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    for name, stage in stages.items():
        got = jax.jit(stage)(*operands)
        if name == "lanes":       # k_out, q_in a tile a head, whole [n b h 1]
            got = (got[0], got[1], jnp.swapaxes(got[2], 2, 3), got[3][..., 0],
                   jnp.swapaxes(got[4], 2, 3), got[5])
        elif name == "tiles":     # whole [n b 1 h]
            got = (got[0], got[1], jnp.swapaxes(got[2], 2, 3), got[3][:, :, 0],
                   jnp.swapaxes(got[4], 2, 3), got[5])
        weights = [jax.random.normal(key, a.shape) for key, a in zip(keys, jax.jit(stage)(*operands))]
        say(program="stage", layout=name, ms=a_call(stage, operands),
            with_gradients_ms=a_call(with_gradients(stage, weights), operands),
            rel_err_to_plain=max(rel(a, b) for a, b in zip(got, want)))

    # the kernels alone, each layout on operands already in its own
    inverse = G._scalar_inverse_call(k, g, beta, False)
    outs = G._scalar_operands_call(q, k, v, g, beta, inverse, False)
    cots = [jax.random.normal(key, a.shape).astype(a.dtype) for key, a in zip(keys, outs)]
    say(program="kernels", layout="lanes",
        inverse_ms=a_call(lambda q, k, v, g, b: (G._scalar_inverse_call(k, g, b, False),), operands),
        operands_ms=a_call(lambda q, k, v, g, b: G._scalar_operands_call(q, k, v, g, b, inverse, False),
                           operands),
        backward_ms=a_call(lambda q, k, v, g, b: G._scalar_backward_call(
            q, k, v, g, b, inverse, *cots, False), operands))
    there = jax.block_until_ready(jax.jit(lambda q, k, v, g, b: (
        laid(q, D_K), laid(k, D_K), laid(v, D_V), by_steps(g), by_steps(b)))(*operands))
    widths = (D_K, D_V)
    inverse = _scalar_inverse_call(there[1], there[3], there[4], False)
    outs = _scalar_operands_call(*there, inverse, widths, False)
    cots = [jax.random.normal(key, a.shape).astype(a.dtype) for key, a in zip(keys, outs)]
    say(program="kernels", layout="tiles",
        inverse_ms=a_call(lambda q, k, v, g, b: (_scalar_inverse_call(k, g, b, False),), there),
        operands_ms=a_call(lambda q, k, v, g, b: _scalar_operands_call(
            q, k, v, g, b, inverse, widths, False), there),
        backward_ms=a_call(lambda q, k, v, g, b: _scalar_backward_call(
            q, k, v, g, b, inverse, *cots, widths, False), there))

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "gdn_layout_probe.jsonl"), "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
