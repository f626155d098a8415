"""jit'd wrappers for the GreedyTL scoring kernels (pad to block multiples,
interpret off-TPU)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.greedy_scores import greedy_scores as K


def _pad_to(x, mult, axis, value=0.0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("block_n", "block_m"))
def gram(Z, *, block_n: int = 128, block_m: int = 128):
    """G = Z^T Z via the Pallas kernel (zero-padded to block multiples —
    zero rows/cols contribute nothing to the Gram)."""
    m, n = Z.shape
    Zp = _pad_to(_pad_to(Z, block_m, 0), block_n, 1)
    G = K.gram(Zp, block_n=block_n, block_m=block_m,
               interpret=not on_tpu())
    return G[:n, :n]


@functools.partial(jax.jit, static_argnames=("lam", "block_n"))
def scores_argmax(corr, diag, selected_mask, lam: float,
                  *, block_n: int = 256):
    """Fused candidate scoring + argmax (padded tail is pre-masked)."""
    n = corr.shape[0]
    cp = _pad_to(corr, block_n, 0)
    dp = _pad_to(diag, block_n, 0, value=1.0)
    sp = _pad_to(selected_mask.astype(jnp.float32), block_n, 0, value=1.0)
    scores, idx = K.scores_argmax(cp, dp, sp, lam, block_n=block_n,
                                  interpret=not on_tpu())
    return scores[:n], idx
