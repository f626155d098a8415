"""GreedyTL candidate scoring — Pallas TPU kernels.

The per-iteration hot spot of GreedyTL's forward selection (paper Section 3)
is, for every candidate column j of the design matrix:

    r_corr_j = c_j - G[j, S] @ w_S           (residual correlation)
    score_j  = r_corr_j^2 / (G_jj + lam)     (-inf on selected columns)

plus the argmax over j.  For d+L in the hundreds this is tiny, but the
paper's own scaling concern (Section 3: GreedyTL cost grows with the local
dataset/design size, hence their subsample bagging) makes the scoring sweep
the kernel-worthy layer once n reaches 10^4-10^5 (deep-model design spaces,
bagged multi-class fits).  Two kernels:

- `gram`: blocked Z^T Z with accumulation over row blocks — the one-off
  O(m n^2) statistic. Tiles are (bm, bn) x (bm, bn) -> (bn, bn) MXU matmuls.
- `scores_argmax`: fused scoring + blockwise argmax, one pass over n.

Both validated in interpret mode against ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


# ------------------------------------------------------------------- gram


def _gram_kernel(z1_ref, z2_ref, o_ref, acc_ref, *, n_m: int):
    im = pl.program_id(2)

    @pl.when(im == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = z1_ref[...].astype(jnp.float32)  # (bm, bi)
    b = z2_ref[...].astype(jnp.float32)  # (bm, bj)
    acc_ref[...] += jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())))

    @pl.when(im == n_m - 1)
    def _out():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_m",
                                             "interpret"))
def gram(Z, *, block_n: int = 128, block_m: int = 128, interpret: bool):
    """G = Z^T Z.  Z: (m, n); returns (n, n) float32.  interpret: run the
    Pallas interpreter instead of lowering to Mosaic (CPU tests)."""
    m, n = Z.shape
    bn = min(block_n, n)
    bm = min(block_m, m)
    assert n % bn == 0 and m % bm == 0, (m, n, bm, bn)
    grid = (n // bn, n // bn, m // bm)
    return pl.pallas_call(
        functools.partial(_gram_kernel, n_m=m // bm),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, t: (t, i)),
            pl.BlockSpec((bm, bn), lambda i, j, t: (t, j)),
        ],
        out_specs=pl.BlockSpec((bn, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, bn), jnp.float32)],
        interpret=interpret,
    )(Z, Z)


# --------------------------------------------------------- scores + argmax


def _scores_kernel(corr_ref, diag_ref, sel_ref, scores_ref, best_ref,
                   *, lam: float, block_n: int):
    i = pl.program_id(0)
    corr = corr_ref[...].astype(jnp.float32)       # (1, bn)
    diag = diag_ref[...].astype(jnp.float32)
    sel = sel_ref[...]
    s = (corr * corr) / (diag + lam)
    s = jnp.where(sel > 0, NEG_INF, s)
    scores_ref[...] = s
    # blockwise (max, first argmax) from two lane reductions — the
    # first index attaining the max, as jnp.argmax picks
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    best = jnp.max(s, axis=1, keepdims=True)       # (1, 1)
    j = jnp.min(jnp.where(s == best, lane, block_n), axis=1, keepdims=True)
    idx = (i * block_n + j).astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 2), 1)
    best_ref[0] = jnp.where(col == 0, best, idx)


@functools.partial(jax.jit, static_argnames=("lam", "block_n", "interpret"))
def scores_argmax(corr, diag, selected_mask, lam: float,
                  *, block_n: int = 256, interpret: bool):
    """Returns (scores (n,), best_idx scalar int32).

    corr/diag: (n,) float; selected_mask: (n,) {0,1}.  The blockwise
    (max, argmax) pairs are reduced on the host side of the op (ops.py) —
    a (n/block_n, 2) table, negligible traffic.  The vectors ride as
    (1, n) rows and the table as (n/block_n, 1, 2), so every block's last
    two dims are whole or (8, 128)-aligned.  interpret: run the Pallas
    interpreter instead of lowering to Mosaic (CPU tests)."""
    n = corr.shape[0]
    bn = min(block_n, n)
    assert n % bn == 0, (n, bn)
    n_blocks = n // bn
    row = pl.BlockSpec((1, bn), lambda i: (0, i))
    scores, best = pl.pallas_call(
        functools.partial(_scores_kernel, lam=lam, block_n=bn),
        grid=(n_blocks,),
        in_specs=[row, row, row],
        out_specs=[row, pl.BlockSpec((1, 1, 2), lambda i: (i, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, 1, 2), jnp.float32),
        ],
        interpret=interpret,
    )(corr.reshape(1, n), diag.reshape(1, n),
      selected_mask.astype(jnp.float32).reshape(1, n))
    best = best[:, 0]
    blk = jnp.argmax(best[:, 0])
    return scores[0], best[blk, 1].astype(jnp.int32)
