"""Paged attention v2 — Pallas TPU kernel: fused K/V scatter, multi-page
tiles, S>1 query blocks.

A block of S query tokens per slot attends over the slot's logical KV
ring, which lives scattered across a shared page pool and is addressed
through a per-slot block table riding in as a scalar-prefetch operand —
each grid step's BlockSpec index_map picks the page tile to DMA straight
out of the pool, so no (B, T, KV, hd) gather ever materializes in HBM
(the XLA path in models/layers.py pays that copy every tick).  The pool
is laid out (n_pages, KV, page_size, hd), so the tile of one (page, KV
head) is a contiguous (page_size, hd) block: its last two dims are whole,
as the TPU lowering requires of a block.

Three rungs over the v1 decode-only kernel:

- FUSED K/V SCATTER.  The kernel also receives the just-projected
  (B, KV, S, hd) k_new/v_new rows and writes them into their
  block-table-addressed page rows in the same grid pass that reads the
  page (`input_output_aliases` pins the pool outputs onto the pool
  inputs, so the write is in-place).  Each new row lands by an S-step
  loop of row selects on the (page_size, hd) tile — exact for every pool
  dtype, with no gather and no MXU round trip.  This deletes the
  separate XLA pool scatter that v1 paid as a second HBM traversal of
  the pool every tick.
- MULTI-PAGE TILES.  The page grid dimension stays one page per step
  (pages are scattered in the pool, so one BlockSpec can only DMA one),
  but K/V tiles accumulate into a (tile_k * page_size, hd) VMEM scratch
  and the flash inner product fires every tile_k-th step on the whole
  buffer — the MXU sees tile_k*page_size-row contractions instead of
  16-row slivers.  ops.py pads the block table with the null page 0 to
  a multiple of tile_k; padded rows are cut by the `ring < T` mask.
- S>1 QUERY BLOCKS.  q is a (B, KV, S*g, hd) block (g = H / KV query
  heads per KV head, GQA); row r is query token r // g at position
  q_pos[b, r // g], masked causally per row — so chunked prefill,
  preemption resume-recompute, and speculative verify run through the
  kernel instead of falling back to the XLA gather.  The row positions
  ride in as an (S*g, 1) VMEM column: vector reads of the
  scalar-prefetched operands do not lower to Mosaic.

Masking: a ring entry is admitted only when the absolute position it
holds (the largest value congruent to its ring index mod T that is
<= the slot's newest position `last`) is >= 0, <= the row's query
position, inside the sliding window, and its ring index is < T (cuts
the null-page padding rows).  Stale pages, idle lanes parked on the
null page, and unreached ring tail entries all mask out.

Write/read ordering contract (why in-kernel scatter is safe): the CoW
allocator guarantees every page written this tick is private to exactly
one slot's block table (serving/scheduler.py `ensure_private`), each
(slot, kv_head, page_step) grid cell runs once, and a slot's own write
lands in the same k_tile its attention reads — so no grid step ever
reads a page another step wrote (the shared null page 0 collects idle
lanes' dead writes exactly as the XLA scatter path does, and stays
masked).  Interpret mode reads pool inputs functionally; a real-TPU
in-place alias sees the same values for every unmasked read.

Validated on CPU in interpret mode against ref.py; on a real TPU the
same pallas_call lowers to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _kernel(bt_ref, last_ref, *refs, scale: float, page_size: int,
            n_steps: int, tile_k: int, window: int, S: int, T: int,
            fuse: bool):
    if fuse:
        (rpos_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref, o_ref, ko_ref,
         vo_ref, acc_ref, m_ref, l_ref, kbuf_ref, vbuf_ref) = refs
    else:
        (rpos_ref, q_ref, k_ref, v_ref, o_ref,
         acc_ref, m_ref, l_ref, kbuf_ref, vbuf_ref) = refs
    b = pl.program_id(0)
    ip = pl.program_id(2)
    psz = page_size

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    last = last_ref[b]
    k_tile = k_ref[0, 0]                           # (psz, hd) pool dtype
    v_tile = v_ref[0, 0]

    if fuse:
        # scatter the S new rows into this page tile: ring slot
        # (first + s) % T holds new token s, first = last - S + 1.  Row r
        # of the page takes new token rel[r] = (r - first) mod T when
        # rel < S; rows outside [first..last] (mod T) or past the real
        # ring (the null-page padding) keep the pool's bytes.  The modulus
        # is taken on the scalar unit; the vector side only selects.
        first_mod = jnp.mod(last - (S - 1), T)
        rows = ip * psz + jax.lax.broadcasted_iota(jnp.int32, (psz, 1), 0)
        rel = rows - first_mod + jnp.where(rows < first_mod, T, 0)
        live = (rel < S) & (rows < T)              # (psz, 1)

        def put(s, tiles):
            kt, vt = tiles
            hit = live & (rel == s)
            # cast BEFORE the attention read: the pool may store narrower
            # kv_cache_dtype and the XLA path round-trips through it too
            kt = jnp.where(hit, kn_ref[0, 0, pl.ds(s, 1), :].astype(
                kt.dtype), kt)
            vt = jnp.where(hit, vn_ref[0, 0, pl.ds(s, 1), :].astype(
                vt.dtype), vt)
            return kt, vt

        k_tile, v_tile = jax.lax.fori_loop(0, S, put, (k_tile, v_tile),
                                           unroll=S <= 16)
        ko_ref[0, 0] = k_tile
        vo_ref[0, 0] = v_tile

    # accumulate this page into the multi-page tile buffer; the flash
    # update fires once per tile_k pages on the whole buffer
    j = jax.lax.rem(ip, tile_k)
    off = pl.multiple_of(j * psz, psz)
    kbuf_ref[pl.ds(off, psz), :] = k_tile.astype(jnp.float32)
    vbuf_ref[pl.ds(off, psz), :] = v_tile.astype(jnp.float32)

    @pl.when(j == tile_k - 1)
    def _flash():
        L = tile_k * psz
        q = q_ref[0, 0].astype(jnp.float32)        # (S*g, hd)
        s = jax.lax.dot_general(
            q, kbuf_ref[...], (((1,), (1,)), ((), ()))) * scale  # (S*g, L)

        # absolute position held by each ring entry of the tile: the
        # largest value congruent to its ring index (mod T) <= `last`,
        # i.e. last - ((last - ring) mod T) with the modulus on the scalar
        # unit: ring entries past last % T belong to the previous lap
        last_mod = jnp.mod(last, T)
        base = (ip - (tile_k - 1)) * psz
        ring = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        k_pos = (last - last_mod) + ring - jnp.where(ring > last_mod, T, 0)
        row_pos = rpos_ref[0]                      # (S*g, 1) query position
        mask = (k_pos >= 0) & (ring < T) & (k_pos <= row_pos)
        if window:
            mask &= k_pos > (row_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                        # (S*g, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        # fully-masked tiles (idle slot parked on the null page, padding
        # past the ring, tail tiles past `last`): stay at zero
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        # HIGHEST: at its default precision Mosaic rounds fp32 operands
        # to bf16, so the chip would drop low bits of p that interpret
        # mode keeps
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, vbuf_ref[...], precision=jax.lax.Precision.HIGHEST)
        m_ref[...] = m_cur

    @pl.when(ip == n_steps - 1)
    def _out():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("ring_len", "window", "tile_k", "interpret"))
def paged_attention_grouped(q, k_new, v_new, k_pool, v_pool, block_table,
                            q_pos, last_pos, *, ring_len: int,
                            window: int = 0, tile_k: int = 1,
                            interpret: bool):
    """q: (B, KV, S*g, hd) — GQA-grouped S-token query blocks (ops.py maps
    the model layout).  k_new/v_new: (B, KV, S, hd) just-projected rows to
    scatter in-kernel, or both None for attention-only (pool already holds
    them).  k_pool/v_pool: (n_pages, KV, page_size, hd), so one grid step
    streams one head's page as a contiguous (page_size, hd) block.
    block_table: (B, P_pad) int32 page ids, P_pad a multiple of tile_k
    (ops.py pads with the null page 0).  q_pos: (B, S) int32 per-row query
    positions.  last_pos: (B,) int32 newest WRITE position per slot
    (masking modulus anchor — and the write window [last-S+1 .. last]
    when fusing).  ring_len: the real (unpadded) logical ring length
    P * page_size.  interpret: run the Pallas interpreter instead of
    lowering to Mosaic (CPU tests).
    Returns (out, k_pool, v_pool) when fusing, else out, out being
    (B, KV, S*g, hd)."""
    fuse = k_new is not None
    B, KV, Sg, hd = q.shape
    S = q_pos.shape[1]
    g = Sg // S
    psz = k_pool.shape[2]
    n_steps = block_table.shape[1]
    scale = 1.0 / (hd ** 0.5)
    # row r of the query block is token r // g: its position, as a column
    # the mask broadcasts against (vector reads of the scalar-prefetched
    # operands are not allowed, so this rides in as a VMEM block)
    row_pos = jnp.broadcast_to(q_pos[:, :, None], (B, S, g)).reshape(
        B, Sg, 1)

    kernel = functools.partial(
        _kernel, scale=scale, page_size=psz, n_steps=n_steps, tile_k=tile_k,
        window=window, S=S, T=ring_len, fuse=fuse)

    rpos_spec = pl.BlockSpec((1, Sg, 1), lambda b, kv, ip, bt, lp: (b, 0, 0))
    q_spec = pl.BlockSpec((1, 1, Sg, hd),
                          lambda b, kv, ip, bt, lp: (b, kv, 0, 0))
    new_spec = pl.BlockSpec((1, 1, S, hd),
                            lambda b, kv, ip, bt, lp: (b, kv, 0, 0))
    # the dynamic gather (and scatter, when fusing): the page tile this
    # grid step streams is chosen by the prefetched block table
    pool_spec = pl.BlockSpec(
        (1, 1, psz, hd), lambda b, kv, ip, bt, lp: (bt[b, ip], kv, 0, 0))
    o_spec = pl.BlockSpec((1, 1, Sg, hd),
                          lambda b, kv, ip, bt, lp: (b, kv, 0, 0))

    in_specs = [rpos_spec, q_spec] + ([new_spec, new_spec] if fuse else []) \
        + [pool_spec, pool_spec]
    out_specs = o_spec
    out_shape = jax.ShapeDtypeStruct((B, KV, Sg, hd), q.dtype)
    kwargs = {}
    if fuse:
        out_specs = [o_spec, pool_spec, pool_spec]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                     jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)]
        # alias the pool inputs onto the pool outputs (in-place update;
        # indices count ALL flat inputs including the 2 scalar-prefetch
        # operands: bt=0, last=1, row_pos=2, q=3, k_new=4, v_new=5, pools)
        kwargs["input_output_aliases"] = {6: 1, 7: 2}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, n_steps),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((Sg, hd), jnp.float32),          # acc
            pltpu.VMEM((Sg, 1), jnp.float32),           # m (running max)
            pltpu.VMEM((Sg, 1), jnp.float32),           # l (running sum)
            pltpu.VMEM((tile_k * psz, hd), jnp.float32),  # K tile buffer
            pltpu.VMEM((tile_k * psz, hd), jnp.float32),  # V tile buffer
        ],
    )

    args = (block_table, last_pos, row_pos, q) + \
        ((k_new, v_new) if fuse else ()) + (k_pool, v_pool)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        **kwargs,
    )(*args)
