"""jit'd wrapper: model-layout (B, S, H, hd) GQA flash attention."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "chunk", "block_q",
                                    "block_k"))
def flash_attention(q, k, v, *, causal=True, window=0, chunk=0,
                    block_q=128, block_k=128):
    """q: (B, S, H, hd), k/v: (B, S, KV, hd) with H = g*KV (GQA).

    Expands KV heads to the query-head grid (an O(1)-cost broadcast under
    XLA; inside the kernel each q-head tile streams its kv-head's blocks)
    and dispatches to the Pallas kernel — interpret mode off-TPU.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    qh = jnp.transpose(q, (0, 2, 1, 3))
    kh = jnp.transpose(k, (0, 2, 1, 3))
    vh = jnp.transpose(v, (0, 2, 1, 3))
    if g > 1:
        kh = jnp.repeat(kh, g, axis=1)
        vh = jnp.repeat(vh, g, axis=1)
    out = flash_attention_bhsd(qh, kh, vh, causal=causal, window=window,
                               chunk=chunk, block_q=block_q, block_k=block_k,
                               interpret=not on_tpu())
    return jnp.transpose(out, (0, 2, 1, 3))
