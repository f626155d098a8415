"""jit'd wrappers: model-layout (B, S, H, hd) paged attention over the
shared page pool — attention-only (`paged_attention`) and fused
scatter+attention (`paged_attention_update`, the serving decode path).

Eligibility is enforced loud: ineligible inputs raise ValueError at
trace time instead of silently falling back (a fallback-bypass bug in
models/layers.py must fail, not run the wrong path).  Rules:

- block_table / last_pos / q_positions must already be int32 — the
  engine owns them int32 at construction (serving/engine.py); the
  per-tick ``.astype(jnp.int32)`` cast copies were removed.
- q is (B, S, H, hd) with H a multiple of the pool's KV head count and
  1 <= S <= P * page_size (a block larger than the logical ring would
  overwrite its own tokens — the serving engine never produces one; it
  must take the XLA path).
- M-RoPE (3-D positions) and chunked-local attention masking are not
  expressible in the kernel, and it is a single-device program:
  `pallas_eligible` says which models and placements take it, for
  models/layers.py (which keeps the rest on XLA) and the serving engine
  (which counts the pages it streams).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.paged_attention.paged_attention import \
    paged_attention_grouped

# pages per grid step, when the caller gives none: STEP_ROWS rows of K and
# V a step (8 pages of 16 rows), with the four page buffers (K and V,
# double-buffered) within STEP_VMEM_BYTES, and at most the ring's P.  A
# grid step streams the live pages of one slot, every KV head at once,
# and copies nothing past the slot's newest position.
STEP_ROWS = 128
STEP_VMEM_BYTES = 8 * 1024 * 1024


def default_tile_k(page_size: int, page_bytes: int, P: int) -> int:
    """Pages per grid step for pages of `page_size` rows and `page_bytes`
    (one page, every KV head, one of K or V) on a ring of P pages."""
    return max(1, min(P, STEP_ROWS // page_size,
                      STEP_VMEM_BYTES // (4 * page_bytes)))


def pallas_eligible(cfg, shard=None) -> bool:
    """Whether paged attention of model `cfg` can run the kernel: not with
    M-RoPE's 3-D positions or chunked-local masking, and not on a mesh
    (`shard`, a serving.sharding.ShardingPlan)."""
    return shard is None and not (cfg.mrope or cfg.chunked_attention)


def scatter_rows(pool, rows, block_table, last_pos):
    """The XLA row write: rows (B, S, KV, hd) of positions last_pos - S + 1
    .. last_pos into their block-table-addressed pool rows, in the pool's
    dtype.  Pools narrower than 32 bits take it before the attention-only
    kernel (a copy cannot address one row of a packed word)."""
    S, psz = rows.shape[1], pool.shape[2]
    T = block_table.shape[1] * psz
    slots = (last_pos[:, None] - (S - 1)
             + jnp.arange(S, dtype=jnp.int32)[None, :]) % T
    page = jnp.take_along_axis(block_table, slots // psz, axis=1)
    return pool.at[page, :, slots % psz].set(rows.astype(pool.dtype))


def _validate(q, k_pool, v_pool, block_table, last_pos, q_positions):
    if q.ndim != 4:
        raise ValueError(
            f"paged attention takes q (B, S, H, hd); got shape {q.shape}")
    B, S, H, hd = q.shape
    if k_pool.shape != v_pool.shape or k_pool.ndim != 4:
        raise ValueError(
            f"k_pool/v_pool must be matching (n_pages, KV, page_size, hd) "
            f"pools; got {k_pool.shape} vs {v_pool.shape}")
    KV = k_pool.shape[1]
    if H % KV:
        raise ValueError(
            f"H={H} query heads must group onto KV={KV} pool heads (GQA)")
    for name, arr in (("block_table", block_table), ("last_pos", last_pos)):
        if arr.dtype != jnp.int32:
            raise ValueError(
                f"{name} must be int32 at construction (got {arr.dtype}); "
                f"the engine owns block tables and positions as int32 — "
                f"per-dispatch astype casts were removed, not hidden")
    if q_positions is not None and (
            q_positions.dtype != jnp.int32 or q_positions.shape != (B, S)):
        raise ValueError(
            f"q_positions must be (B, S) int32; got "
            f"{q_positions.shape} {q_positions.dtype}")
    T = block_table.shape[1] * k_pool.shape[2]
    if not 1 <= S <= T:
        raise ValueError(
            f"S={S} query block must satisfy 1 <= S <= ring length {T} "
            f"(P * page_size) — larger blocks would overwrite their own "
            f"tokens and are ineligible for the kernel (XLA path only)")


def _dispatch(q, k_new, v_new, k_pool, v_pool, block_table, last_pos,
              window, tile_k, q_positions):
    """Map model layout -> grouped kernel layout, pad the page grid to a
    multiple of tile_k with the null page 0, dispatch."""
    _validate(q, k_pool, v_pool, block_table, last_pos, q_positions)
    B, S, H, hd = q.shape
    KV = k_pool.shape[1]
    g = H // KV
    psz = k_pool.shape[2]
    P = block_table.shape[1]
    T = P * psz

    if k_new is not None and jnp.dtype(k_pool.dtype).itemsize != 4:
        k_pool = scatter_rows(k_pool, k_new, block_table, last_pos)
        v_pool = scatter_rows(v_pool, v_new, block_table, last_pos)
        k_new = v_new = None
    if tile_k is None:
        tile_k = default_tile_k(
            psz, KV * psz * hd * jnp.dtype(k_pool.dtype).itemsize, P)
    tk = max(1, min(tile_k, P))
    pad = -P % tk
    if pad:
        block_table = jnp.pad(block_table, ((0, 0), (0, pad)))
    if q_positions is None:
        q_positions = last_pos[:, None] - (S - 1) + \
            jnp.arange(S, dtype=jnp.int32)[None, :]

    qg = q.reshape(B, S, KV, g, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, S * g, hd)
    fused = k_new is not None
    if fused:
        # (B, S, KV, hd) -> (B, KV, S, hd), in the pool's dtype: the kernel
        # copies these rows into the pool as they are
        k_new = k_new.transpose(0, 2, 1, 3).astype(k_pool.dtype)
        v_new = v_new.transpose(0, 2, 1, 3).astype(v_pool.dtype)
    res = paged_attention_grouped(
        qg, k_new, v_new, k_pool, v_pool, block_table, q_positions,
        last_pos, ring_len=T, window=window, tile_k=tk,
        interpret=not on_tpu())
    out, kp, vp = res if fused else (res, k_pool, v_pool)
    out = out.reshape(B, KV, S, g, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, S, H, hd)
    return out, kp, vp


@functools.partial(jax.jit, static_argnames=("window", "tile_k"))
def paged_attention(q, k_pool, v_pool, block_table, last_pos, *,
                    window: int = 0, tile_k: int | None = None,
                    q_positions=None):
    """q: (B, S, H, hd) with H = g*KV (GQA) — an S-token query block per
    slot, already RoPE'd; its K/V must already be scattered into the pool
    (use `paged_attention_update` to fuse that write in).

    k_pool/v_pool: (n_pages, KV, page_size, hd) shared pools.
    block_table: (B, P) int32 page ids; last_pos: (B,) int32 absolute
    position of the newest token per slot.  q_positions: optional (B, S)
    int32 per-row query positions (defaults to last_pos - S + 1 .. last_pos,
    the contiguous decode block).  tile_k: pages per grid step (None:
    `default_tile_k` of the page's rows and bytes and P).  Returns
    (B, S, H, hd)."""
    out, _, _ = _dispatch(q, None, None, k_pool, v_pool, block_table,
                          last_pos, window, tile_k, q_positions)
    return out


@functools.partial(jax.jit, static_argnames=("window", "tile_k"))
def paged_attention_update(q, k_new, v_new, k_pool, v_pool, block_table,
                           last_pos, *, window: int = 0,
                           tile_k: int | None = None, q_positions=None):
    """Fused scatter + attention: the serving decode/prefill path.

    k_new/v_new: (B, S, KV, hd) just-projected K/V rows for positions
    last_pos - S + 1 .. last_pos; the kernel copies them (cast to the
    pool dtype) into their block-table-addressed page rows before it
    reads the pool — no separate XLA pool scatter, and no other pool
    byte written.  A pool narrower than 32 bits takes `scatter_rows`
    and the attention-only kernel instead.  Returns
    (out, k_pool, v_pool): out (B, S, H, hd) plus the updated pools
    (aliased in-place onto the inputs)."""
    B, S = q.shape[:2]
    want = (B, S, k_pool.shape[1], k_pool.shape[3])
    if k_new.shape != want or k_new.shape != v_new.shape:
        raise ValueError(
            f"k_new/v_new must be (B, S, KV, hd) = {want}; got "
            f"{k_new.shape} / {v_new.shape}")
    return _dispatch(q, k_new, v_new, k_pool, v_pool, block_table,
                     last_pos, window, tile_k, q_positions)
