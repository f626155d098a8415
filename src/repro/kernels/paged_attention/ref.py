"""Pure-jnp oracle for the paged-attention kernel.

Mirrors the XLA paged decode path in models/layers.py: gather each slot's
logical ring out of the shared page pool through its block-table row, mask
by position validity (stale / null-page entries have k_pos < 0 or fall
outside the causal window), fp32 softmax.

Three oracles: `reference_paged_attention` (the v1 single-token decode
shape), `reference_paged_attention_block` (S-token query blocks with
per-row causal masking — the v2 S>1 rung), and `reference_paged_update`
(XLA scatter of the S new K/V rows through the block table, then block
attention — the fused-scatter rung's end-to-end oracle, byte-exact on
the returned pools).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ring_positions(last, T):
    """Absolute position held by ring slot i after the newest write.

    last: (B,) absolute position of the newest token; the largest value
    congruent to i (mod T) that is <= last — negative (invalid) for ring
    entries no sequence has reached yet."""
    idx = jnp.arange(T)
    return last[:, None] - ((last[:, None] - idx[None, :]) % T)  # (B, T)


def gather_ring(pool, block_table):
    """Each slot's logical ring out of a (n_pages, KV, page_size, hd) pool:
    ring entry i lives in row i % page_size of page block_table[b, i //
    page_size].  Returns (B, T, KV, hd)."""
    psz = pool.shape[2]
    ring = jnp.arange(block_table.shape[1] * psz)
    return pool[block_table[:, ring // psz], :, ring % psz]


def reference_paged_attention(q, k_pool, v_pool, block_table, last_pos, *,
                              window: int = 0):
    """q: (B, H, hd) — ONE query token per slot, at position last_pos[b].

    k_pool/v_pool: (n_pages, KV, page_size, hd) shared pools, the new
    token's K/V already scattered in.  block_table: (B, P) int32 page ids
    (page 0 = reserved null page).  last_pos: (B,) int32.  Returns
    (B, H, hd) in q's dtype."""
    B, H, hd = q.shape
    KV = k_pool.shape[1]
    g = H // KV
    T = block_table.shape[1] * k_pool.shape[2]

    ck = gather_ring(k_pool, block_table).astype(jnp.float32)    # (B, T, KV, hd)
    cv = gather_ring(v_pool, block_table).astype(jnp.float32)

    k_pos = ring_positions(last_pos, T)
    valid = (k_pos >= 0) & (k_pos <= last_pos[:, None])
    if window:
        valid &= k_pos > (last_pos[:, None] - window)

    qh = q.reshape(B, KV, g, hd).astype(jnp.float32)
    scale = 1.0 / float(hd) ** 0.5
    s = jnp.einsum("bkgh,btkh->bkgt", qh, ck) * scale            # (B, KV, g, T)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows (idle slots)
    out = jnp.einsum("bkgt,btkh->bkgh", p, cv)
    return out.reshape(B, H, hd).astype(q.dtype)


def reference_paged_attention_block(q, k_pool, v_pool, block_table,
                                    last_pos, *, window: int = 0,
                                    q_positions=None):
    """q: (B, S, H, hd) — an S-token query block per slot; row s is the
    query at position q_positions[b, s] (default: the contiguous block
    last_pos - S + 1 .. last_pos, so intra-block causality falls out of
    the per-row k_pos <= q_pos mask).  K/V for every row must already be
    in the pool.  Returns (B, S, H, hd) in q's dtype."""
    B, S, H, hd = q.shape
    KV = k_pool.shape[1]
    g = H // KV
    T = block_table.shape[1] * k_pool.shape[2]

    ck = gather_ring(k_pool, block_table).astype(jnp.float32)    # (B, T, KV, hd)
    cv = gather_ring(v_pool, block_table).astype(jnp.float32)

    if q_positions is None:
        q_positions = last_pos[:, None] - (S - 1) + jnp.arange(S)[None, :]
    k_pos = ring_positions(last_pos, T)                          # (B, T)
    valid = (k_pos[:, None, :] >= 0) & \
        (k_pos[:, None, :] <= q_positions[..., None])            # (B, S, T)
    if window:
        valid &= k_pos[:, None, :] > (q_positions[..., None] - window)

    qh = q.reshape(B, S, KV, g, hd).astype(jnp.float32)
    scale = 1.0 / float(hd) ** 0.5
    s = jnp.einsum("bskgh,btkh->bskgt", qh, ck) * scale
    s = jnp.where(valid[:, :, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows (idle slots)
    out = jnp.einsum("bskgt,btkh->bskgh", p, cv)
    return out.reshape(B, S, H, hd).astype(q.dtype)


def reference_paged_update(q, k_new, v_new, k_pool, v_pool, block_table,
                           last_pos, *, window: int = 0, q_positions=None):
    """Scatter-then-attend oracle for ops.paged_attention_update: the S
    new K/V rows (k_new/v_new (B, S, KV, hd)) land at ring slots
    (last_pos - S + 1 .. last_pos) % T through the block table — the
    exact XLA write models/layers.py does — then block attention reads
    them back.  Returns (out, k_pool, v_pool)."""
    B, S = q.shape[:2]
    psz = k_pool.shape[2]
    T = block_table.shape[1] * psz
    abs_pos = last_pos[:, None] - (S - 1) + jnp.arange(S)[None, :]
    slots = abs_pos % T
    page = jnp.take_along_axis(block_table, slots // psz, axis=1)  # (B, S)
    row = slots % psz
    kp = k_pool.at[page, :, row].set(k_new.astype(k_pool.dtype))
    vp = v_pool.at[page, :, row].set(v_new.astype(v_pool.dtype))
    out = reference_paged_attention_block(
        q, kp, vp, block_table, last_pos, window=window,
        q_positions=q_positions)
    return out, kp, vp
