"""The forward process of a block-diffusion training step, on the host.

Block diffusion (BD3-LM, arXiv:2503.09573; SDAR, arXiv:2510.06303) trains a
decoder to denoise a sequence block by block: a clean sequence ``x_0`` of
``L`` ids is cut into blocks of ``block`` positions, each block draws one
noise level ``t`` in ``(t_min, 1]``, each position one ``u`` in ``[0, 1)``,
and a position is replaced by ``mask_id`` where ``u < t`` of its block (the
absorbing, linear schedule: a share ``t`` of a block masked, expected). The
model (``TransformerLM`` under ``ArchSpec.block_diffusion``) reads ``[x_0 ;
x_t]``, ``2 L`` ids, and ``train/step.py:make_block_diffusion_loss`` scores
the masked positions at ``1 / t``.

The process is numpy on the host and **a function of explicit randomness**
(:func:`noised`), drawn by :func:`noise_draws` from ``(seed, index)``, a
batch's place in the data order, and from nothing else: the train step stays
a function of ``(state, batch)``, so a job that is killed and resumed, or
resized, replays the data order and with it the noise.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def noised(
    x0: np.ndarray, t: np.ndarray, u: np.ndarray, block: int, mask_id: int
) -> Tuple[np.ndarray, Tuple[np.ndarray, np.ndarray]]:
    """``(tokens [B, 2 L] int32, (labels [B, L] int32, weights [B, L]
    float32))`` from clean ids ``x0`` [B, L], one noise level a block ``t``
    [B, L / block] in (0, 1] and one draw a position ``u`` [B, L] in [0, 1):
    ``tokens`` is ``x0`` and then its noised copy (``mask_id`` where ``u <
    t`` of the position's block), ``labels`` is ``x0``, ``weights`` is ``1 /
    t`` where the position was masked and 0 where it was not."""
    x0, t, u = np.asarray(x0), np.asarray(t, np.float64), np.asarray(u)
    b, length = x0.shape
    if block < 1 or length % block or t.shape != (b, length // block) or (
        u.shape != x0.shape
    ):
        raise ValueError(
            "noised: ids %r in blocks of %d want t %r and u %r"
            % (x0.shape, block, (b, length // max(block, 1)), x0.shape)
        )
    if not (t > 0).all() or not (t <= 1).all():
        raise ValueError("noised: a block's noise level lies in (0, 1]")
    level = np.repeat(t, block, axis=1)
    masked = u < level
    tokens = np.concatenate([x0, np.where(masked, mask_id, x0)], axis=1)
    weights = np.where(masked, 1.0 / level, 0.0)
    return tokens.astype(np.int32), (x0.astype(np.int32), weights.astype(np.float32))


def noise_draws(
    seed: int, index: int, shape: Tuple[int, int], block: int, t_min: float = 0.0
) -> Tuple[np.ndarray, np.ndarray]:
    """``(t [B, L / block] uniform in (t_min, 1], u [B, L] uniform in [0,
    1))`` of batch ``index`` of the data order ``seed`` names: the same pair
    whenever it is asked for, in whatever order."""
    b, length = shape
    rs = np.random.default_rng([int(seed), int(index)])
    # 1 - [0, 1) is (0, 1]: the level is never 0, the weight never infinite
    t = t_min + (1.0 - t_min) * (1.0 - rs.random((b, length // block)))
    return t, rs.random((b, length))


def noised_batch(
    x0: np.ndarray, seed: int, index: int, block: int, mask_id: int,
    t_min: float = 0.0,
):
    """Batch ``index`` of a data order as the model and its loss head take
    it: :func:`noised` on the draws :func:`noise_draws` gives ``(seed,
    index)``."""
    t, u = noise_draws(seed, index, np.shape(x0), block, t_min)
    return noised(x0, t, u, block, mask_id)
