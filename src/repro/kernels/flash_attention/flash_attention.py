"""Flash attention — Pallas TPU kernel.

Blockwise causal attention with online softmax, GQA, and optional
sliding-window / chunked-local masking.

TPU mapping: grid (batch, q_heads, n_q_blocks, n_k_blocks) with the k-block
dimension "arbitrary" (sequential) so the running (acc, m, l) state lives in
VMEM scratch across k steps.  Block shapes are (block_q, head_dim) /
(block_k, head_dim) — head_dim is MXU-lane aligned (128 for all assigned
archs except musicgen/rwkv at 64, still sublane-friendly), and block_q/k
default to 128 so the s = q k^T tile is a 128x128 MXU matmul.  The full K/V
of one head never resides in VMEM (32k seq x 128 x 2B = 8MB would not fit
alongside double-buffering) — only (block, head_dim) tiles do.

Validated on CPU in interpret mode against ref.reference_attention; on a
real TPU the same pallas_call lowers to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            *, scale: float, block_q: int, block_k: int, n_k: int,
            causal: bool, window: int, chunk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (block_q, hd)
    k = k_ref[0, 0].astype(jnp.float32)            # (block_k, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > (q_pos - window)
    if chunk:
        mask &= (k_pos // chunk) == (q_pos // chunk)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                            # (block_q,)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    # fully-masked rows: keep everything at zero
    p = jnp.where(mask, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
    m_ref[...] = m_cur

    @pl.when(ik == n_k - 1)
    def _out():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "chunk", "block_q",
                              "block_k", "interpret"))
def flash_attention_bhsd(q, k, v, *, causal=True, window=0, chunk=0,
                         block_q=128, block_k=128, interpret: bool):
    """q: (B, H, S, hd); k/v: (B, H, S, hd) (GQA pre-expanded by ops.py).
    interpret: run the Pallas interpreter instead of lowering to Mosaic
    (CPU tests).

    Returns (B, H, S, hd)."""
    B, H, S, hd = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    n_q = S // block_q
    n_k = S // block_k
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k, n_k=n_k,
        causal=causal, window=window, chunk=chunk)

    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),   # acc
            pltpu.VMEM((block_q,), jnp.float32),      # m (running max)
            pltpu.VMEM((block_q,), jnp.float32),      # l (running sum)
        ],
        interpret=interpret,
    )(q, k, v)
