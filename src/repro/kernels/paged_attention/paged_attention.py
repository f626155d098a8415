"""Paged attention v2 — Pallas TPU kernel: whole pages of every KV head
per DMA, only each slot's live pages, S>1 query blocks, and a fused K/V
write of just the new rows.

A block of S query tokens per slot attends over the slot's logical KV
ring, which lives scattered across a shared page pool and is addressed
through a per-slot block table riding in as a scalar-prefetch operand.
The pools are pinned to HBM, where a deployment's pool lives: left to
XLA, a layer's pool slice small enough for VMEM is staged there whole,
live pages or not, outside the kernel.  The kernel copies only the
pages it reads into its own buffers, so no (B, T, KV, hd) gather ever
materializes (the XLA path in models/layers.py pays that copy every
tick).  On a TPU the fused kernel runs where the pools are a loop's
state, as in the serving step's layer loop: pinned outputs returned
straight from a program fail XLA's memory-space assignment at some
shapes (tests/test_chip_compile.py compiles the served form).

- WHOLE PAGES, ALL HEADS.  The pool is laid out (n_pages, KV, page_size,
  hd), so one page of every KV head is one contiguous block.  The grid
  is (B, ceil(P / tile_k)): each step streams tile_k pages of one slot,
  one async copy per page for K and one for V, into a
  (KV, tile_k * page_size, hd) VMEM buffer (the copy lays each page's
  heads apart, so no in-kernel transpose), and scores every KV head at
  once: a dot batched over heads, (KV, S*g, hd) against
  (KV, tile_k * page_size, hd).  The buffers are double-buffered: while
  a step computes, the copies of the next step that has work (this
  slot's next block, else the next slot's first) are in flight, their
  page ids read from the prefetched block table.
- ONLY LIVE PAGES.  Slot b reads live_b = min(P, ceil((last_b + 1) /
  page_size)) pages, clamped to at least 1, with `last` its newest
  position: pages past it hold no position the mask admits.  A wrapped
  ring keeps all P pages live.  Steps wholly past live_b issue no copy
  and no compute; in a block that is only partly live the dead pages
  are not copied, and their V rows are zeroed in VMEM so that a stale
  buffer cannot turn p = 0 into NaN.
- FUSED K/V WRITE OF THE NEW ROWS.  The kernel also receives the
  just-projected (B, KV, S, hd) k_new/v_new rows, in the pool's dtype,
  and at the grid's first step copies each (KV, 1, hd) row to its
  (page, :, row) address through the block table, and waits for every
  such copy before the first page is read — so each slot reads its own
  new rows back, and no other pool byte is written.  Only a 32-bit pool
  fuses the write: a narrower one (bf16 `kv_cache_dtype`) packs
  neighbouring rows into one word, which a copy cannot address one row
  of, so ops.py scatters its rows with XLA and runs the attention-only
  kernel.  `input_output_aliases` pins the pool outputs onto the pool
  inputs, so the write is in place; the reads go through the output
  refs, which interpret mode keeps apart from the inputs.
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
null page, and unreached ring tail entries all mask out; the pages the
kernel does not read are exactly pages whose every entry the mask cuts.

Numerics: scores at the dot's default precision, the online softmax in
fp32, and `p @ V` at HIGHEST; the pool is read in the dtype the engine
holds and widened to fp32 in VMEM.

Write/read ordering contract (why the in-kernel write is safe): the CoW
allocator guarantees every page written this tick is private to exactly
one slot's block table (serving/scheduler.py `ensure_private`); every
row is written, and its copy waited for, before any page is read; so
no page a slot reads changes under it.  The shared null page 0 collects
idle lanes' dead writes exactly as the XLA scatter path does, and stays
masked.

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


def live_pages(last, page_size: int, pages: int, xp=jnp):
    """Pages of the ring a slot whose newest position is `last` reads:
    ceil((last + 1) / page_size), at least 1 and at most `pages` (a
    wrapped ring is live throughout).  `xp=np` counts them on the host
    (serving/engine.py) without a device op."""
    return xp.clip((last + page_size) // page_size, 1, pages)


def _kernel(bt_ref, last_ref, *refs, scale: float, page_size: int,
            pages: int, n_pad: int, tile_k: int, window: int, S: int,
            T: int, n_slots: int, fuse: bool):
    if fuse:
        rpos_ref, q_ref, kn_ref, vn_ref, _k_in, _v_in, o_ref, k_hbm, \
            v_hbm, *scratch = refs
    else:
        rpos_ref, q_ref, k_hbm, v_hbm, o_ref, *scratch = refs
    acc_ref, m_ref, l_ref, kbuf, vbuf, ksem, vsem, wsem, slot_ref = scratch
    b = pl.program_id(0)
    j = pl.program_id(1)
    psz = page_size
    L = tile_k * psz

    def page_copies(bb, jj, slot):
        """(live, K copy, V copy) for each page of block jj of slot bb:
        the page's every KV head in one copy, into rows [i * psz, (i+1)
        * psz) of each head's buffer."""
        live = live_pages(last_ref[bb], psz, pages)
        out = []
        for i in range(tile_k):
            p = jj * tile_k + i
            page = bt_ref[bb * n_pad + p]
            rows = pl.ds(i * psz, psz)
            out.append((
                p < live,
                pltpu.make_async_copy(k_hbm.at[page],
                                      kbuf.at[slot, :, rows, :],
                                      ksem.at[slot]),
                pltpu.make_async_copy(v_hbm.at[page],
                                      vbuf.at[slot, :, rows, :],
                                      vsem.at[slot])))
        return out

    def fetch(bb, jj, slot):
        for live, kc, vc in page_copies(bb, jj, slot):
            @pl.when(live)
            def _():
                kc.start()
                vc.start()

    def wait(bb, jj, slot):
        for live, kc, vc in page_copies(bb, jj, slot):
            @pl.when(live)
            def _():
                kc.wait()
                vc.wait()

    def row_copies(i):
        """Copies of new row s of slot bb (i = bb * S + s) to ring slot
        (last - S + 1 + s) mod T: row (slot mod psz) of page
        bt[bb, slot // psz], every KV head."""
        bb = i // S
        s = i - bb * S
        ring = jnp.mod(last_ref[bb] - (S - 1) + s, T)
        page = bt_ref[bb * n_pad + ring // psz]
        row = pl.ds(ring % psz, 1)
        return (pltpu.make_async_copy(kn_ref.at[bb, :, pl.ds(s, 1), :],
                                      k_hbm.at[page, :, row, :],
                                      wsem.at[0]),
                pltpu.make_async_copy(vn_ref.at[bb, :, pl.ds(s, 1), :],
                                      v_hbm.at[page, :, row, :],
                                      wsem.at[0]))

    def write_rows():
        def start(i, c):
            for cp in row_copies(i):
                cp.start()
            return c

        def finish(i, c):
            for cp in row_copies(i):
                cp.wait()
            return c

        jax.lax.fori_loop(0, n_slots * S, start, 0)
        jax.lax.fori_loop(0, n_slots * S, finish, 0)

    nblk = (live_pages(last_ref[b], psz, pages) + tile_k - 1) // tile_k

    @pl.when(j < nblk)
    def _step():
        @pl.when((b == 0) & (j == 0))
        def _first():
            slot_ref[0] = 0
            if fuse:
                write_rows()
            fetch(0, 0, 0)

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        slot = slot_ref[0]
        more = j + 1 < nblk

        # the next step with work: this slot's next block, else the next
        # slot's first (every slot has at least one)
        @pl.when(more)
        def _next_block():
            fetch(b, j + 1, 1 - slot)

        @pl.when(jnp.logical_not(more) & (b + 1 < n_slots))
        def _next_slot():
            fetch(b + 1, 0, 1 - slot)

        wait(b, j, slot)

        last = last_ref[b]
        base = j * L
        q = q_ref[0].astype(jnp.float32)              # (KV, S*g, hd)
        k = kbuf[slot].astype(jnp.float32)            # (KV, L, hd)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # (KV, S*g, L)

        # absolute position held by each ring entry of the block: the
        # largest value congruent to its ring index (mod T) <= `last`,
        # i.e. last - ((last - ring) mod T) with the modulus on the scalar
        # unit: ring entries past last % T belong to the previous lap
        last_mod = jnp.mod(last, T)
        ring = base + jax.lax.broadcasted_iota(jnp.int32, s.shape[1:], 1)
        k_pos = (last - last_mod) + ring - jnp.where(ring > last_mod, T, 0)
        row_pos = rpos_ref[0]                         # (S*g, 1)
        mask = (k_pos >= 0) & (ring < T) & (k_pos <= row_pos)
        if window:
            mask &= k_pos > (row_pos - window)
        mask = mask[None]                             # (1, S*g, L)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                           # (KV, S*g, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        # fully-masked rows (idle slot parked on the null page, padding
        # past the ring, tail entries past `last`): stay at zero
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        # rows of pages this block did not copy hold whatever the buffer
        # held before; zero them, or 0 * NaN would reach the sum
        copied = (base + jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)
                  < live_pages(last, psz, pages) * psz)
        v = jnp.where(copied[None], vbuf[slot].astype(jnp.float32), 0.0)
        # HIGHEST: at its default precision Mosaic rounds fp32 operands
        # to bf16, so the chip would drop low bits of p that interpret
        # mode keeps
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur
        slot_ref[0] = 1 - slot

        @pl.when(j == nblk - 1)
        def _out():
            l = jnp.maximum(l_ref[...], 1e-30)
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("ring_len", "window", "tile_k", "interpret"))
def paged_attention_grouped(q, k_new, v_new, k_pool, v_pool, block_table,
                            q_pos, last_pos, *, ring_len: int,
                            window: int = 0, tile_k: int = 1,
                            interpret: bool):
    """q: (B, KV, S*g, hd) — GQA-grouped S-token query blocks (ops.py maps
    the model layout).  k_new/v_new: (B, KV, S, hd) just-projected rows in
    the pool's dtype, to write in-kernel (32-bit pools only), or both None
    for attention-only (pool already holds them).  k_pool/v_pool: (n_pages, KV, page_size,
    hd), so one copy moves one page of every head.  block_table:
    (B, P_pad) int32 page ids, P_pad a multiple of tile_k (ops.py pads
    with the null page 0).  q_pos: (B, S) int32 per-row query positions.
    last_pos: (B,) int32 newest WRITE position per slot (masking modulus
    anchor, live-page count — and the write window [last-S+1 .. last]
    when fusing).  ring_len: the real (unpadded) logical ring length
    P * page_size.  tile_k: pages per grid step.  interpret: run the
    Pallas interpreter instead of lowering to Mosaic (CPU tests).
    Returns (out, k_pool, v_pool) when fusing, else out, out being
    (B, KV, S*g, hd)."""
    fuse = k_new is not None
    if fuse and jnp.dtype(k_pool.dtype).itemsize != 4:
        raise ValueError(
            f"the fused row write needs a 32-bit pool (got {k_pool.dtype}): "
            f"a narrower pool packs rows into shared words; scatter its new "
            f"rows first and run attention-only (ops.py does)")
    B, KV, Sg, hd = q.shape
    S = q_pos.shape[1]
    g = Sg // S
    psz = k_pool.shape[2]
    n_pad = block_table.shape[1]
    scale = 1.0 / (hd ** 0.5)
    # row r of the query block is token r // g: its position, as a column
    # the mask broadcasts against (vector reads of the scalar-prefetched
    # operands are not allowed, so this rides in as a VMEM block)
    row_pos = jnp.broadcast_to(q_pos[:, :, None], (B, S, g)).reshape(
        B, Sg, 1)

    kernel = functools.partial(
        _kernel, scale=scale, page_size=psz, pages=ring_len // psz,
        n_pad=n_pad, tile_k=tile_k, window=window, S=S, T=ring_len,
        n_slots=B, fuse=fuse)

    rpos_spec = pl.BlockSpec((1, Sg, 1), lambda b, j, bt, lp: (b, 0, 0))
    q_spec = pl.BlockSpec((1, KV, Sg, hd), lambda b, j, bt, lp: (b, 0, 0, 0))
    # every slot's new rows at once: the first step writes them all
    new_spec = pl.BlockSpec((B, KV, S, hd), lambda b, j, bt, lp: (0, 0, 0, 0))
    # the kernel copies the pages it reads and the rows it writes: the
    # pools stay in HBM, pinned there as inputs or, fused, as the outputs
    # they alias onto
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)

    in_specs = [rpos_spec, q_spec] + ([new_spec, new_spec] if fuse else []) \
        + [pool_spec, pool_spec]
    out_specs = q_spec
    out_shape = jax.ShapeDtypeStruct((B, KV, Sg, hd), q.dtype)
    kwargs = {}
    if fuse:
        out_specs = [q_spec, pool_spec, pool_spec]
        out_shape = [out_shape,
                     pltpu.HBM(k_pool.shape, k_pool.dtype),
                     pltpu.HBM(v_pool.shape, v_pool.dtype)]
        # alias the pool inputs onto the pool outputs (in-place update;
        # indices count ALL flat inputs including the 2 scalar-prefetch
        # operands: bt=0, last=1, row_pos=2, q=3, k_new=4, v_new=5, pools)
        kwargs["input_output_aliases"] = {6: 1, 7: 2}

    L = tile_k * psz
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pad // tile_k),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((KV, Sg, hd), jnp.float32),          # acc
            pltpu.VMEM((KV, Sg, 1), jnp.float32),           # m (running max)
            pltpu.VMEM((KV, Sg, 1), jnp.float32),           # l (running sum)
            pltpu.VMEM((2, KV, L, hd), k_pool.dtype),       # K pages, x2
            pltpu.VMEM((2, KV, L, hd), v_pool.dtype),       # V pages, x2
            pltpu.SemaphoreType.DMA((2,)),                  # K copies
            pltpu.SemaphoreType.DMA((2,)),                  # V copies
            pltpu.SemaphoreType.DMA((1,)),                  # row writes
            pltpu.SMEM((1,), jnp.int32),                    # buffer in use
        ],
    )
    if not interpret:  # the interpreter takes no such constraint
        k_pool, v_pool = (pltpu.with_memory_space_constraint(x, pltpu.HBM)
                          for x in (k_pool, v_pool))

    args = (block_table.reshape(-1), last_pos, row_pos, q) + \
        ((k_new, v_new) if fuse else ()) + (k_pool, v_pool)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        **kwargs,
    )(*args)
