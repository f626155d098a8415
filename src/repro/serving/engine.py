"""Device-facing serving engines: the dispatch half of the serving stack.

The policy layer (serving/scheduler.py) decides WHO runs — FIFO admission,
page budgets and prefix sharing, slot assignment, completion accounting.
An engine decides HOW: it owns the device-resident decode state (stacked
caches or the shared page pool, per-slot positions, block tables) and the
jitted step functions from serve_step.py, and guarantees that advancing
the whole slot pool by one token — sampled or greedy — costs exactly ONE
device dispatch per tick.

Three engines share the same narrow surface (`mark_reset`, `admit`,
`release`, `prefill_block`, `decode`, `cache_nbytes`, dispatch counters):

- ``DenseEngine``: one (n_slots, capacity, KV, hd) ring per layer; "pos"
  lives on device as a (n_slots,) vector inside the cache tree; slot
  resets are fused into the decode dispatch via a reset mask.
- ``PagedEngine``: ONE shared (n_pages, KV, page_size, hd) pool per layer
  addressed through a host-owned (n_slots, pages_per_slot) block table;
  positions are host-tracked, page lifetime belongs to the policy layer's
  PageAllocator — the engine only writes table rows and scatters through
  them.
- ``PerSlotEngine``: the seed baseline — one jitted batch-1 call per
  active slot per tick, kept as the equivalence reference and the bench's
  "before" side.

Per-slot sampling state (serving/sampling.SlotSampling) rides through
every decode and prefill dispatch as batched arrays: greedy and sampled
slots share one compiled program, so turning sampling on never un-fuses
the dispatch.

Dense and Paged engines take ``mesh=`` (a jax.sharding.Mesh or a prebuilt
serving.sharding.ShardingPlan): params and caches are placed with
jax.device_put at construction and the jitted steps pin in/out shardings,
so one fused dispatch still advances the whole pool — 1.00 dispatch per
MESH tick, with slots sharded over the data axes and heads over "model".
``mesh=None`` keeps today's single-device path bit-for-bit.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving.kvcache import (DEFAULT_PAGE_SIZE, attn_cache_shape,
                                   init_cache, init_paged_cache,
                                   paged_attn_layout)
from repro.serving.sampling import (SlotSampling, argmax_with_margin,
                                    row_sample, token_logprob)
from repro.serving.serve_step import (make_engine_step,
                                      make_paged_engine_step,
                                      make_paged_prefill_step,
                                      make_slot_prefill_step)
from repro.serving.sharding import as_plan, tree_device_nbytes

# shared no-op context for the telemetry=None fast path: the annotate
# wrapper costs one `is not None` check and zero allocations per dispatch
_NULL = contextlib.nullcontext()


def _check_mesh_kernel(plan, use_pallas: bool, kernel: str = "xla"):
    """The Pallas kernels are single-device programs (opaque custom calls
    GSPMD cannot partition) — reject the combination loudly instead of
    letting XLA fail mid-compile."""
    if plan is not None and (use_pallas or kernel == "pallas"):
        raise ValueError(
            "mesh sharding and the Pallas kernels are mutually exclusive "
            "for now — the kernels are single-device programs; use the "
            "XLA path (use_pallas=False, kernel='xla') on a mesh")


def _check_slot_groups(plan, n_slots: int):
    if plan is not None and n_slots % plan.data_size:
        raise ValueError(
            f"n_slots={n_slots} must divide into {plan.data_size} data "
            f"shards — each data shard owns a contiguous slot group")


class DenseEngine:
    """Stacked dense-ring decode state driven by one fused dispatch/tick."""

    layout = "dense"

    def __init__(self, cfg: ModelConfig, params, *, n_slots: int,
                 capacity: int, use_pallas: bool = False, mesh=None,
                 telemetry=None):
        self.telemetry = telemetry
        self.plan = as_plan(mesh, cfg)
        self.mesh = None if self.plan is None else self.plan.mesh
        _check_mesh_kernel(self.plan, use_pallas)
        _check_slot_groups(self.plan, n_slots)
        self.n_slot_groups = 1 if self.plan is None else self.plan.data_size
        self.cfg, self.params = cfg, params
        self.n_slots, self.capacity = n_slots, capacity
        # ring size of the attention cache (multi-token prefill blocks must
        # not wrap it); None for pure-recurrent archs
        self.ring_cap = None
        if cfg.block_kind in ("attention", "hybrid"):
            self.ring_cap = attn_cache_shape(cfg, 1, capacity)["k"][1]
        # donate the pool cache: the host drops its reference at each
        # reassignment, so XLA may update the (large) KV/SSM pool in place
        # instead of copying it every tick
        self.cache = init_cache(cfg, n_slots, capacity,
                                pos=np.zeros((n_slots,), np.int32),
                                dtype=jnp.float32)
        if self.plan is None:
            self._decode = jax.jit(make_engine_step(cfg, use_pallas),
                                   donate_argnums=1)
            self._prefill = jax.jit(make_slot_prefill_step(cfg, use_pallas),
                                    donate_argnums=1)
        else:
            plan = self.plan
            psh = plan.param_shardings(params)
            csh = plan.dense_cache_shardings(self.cache)
            row, rep = plan.rows(), plan.replicated()
            # placement happens once at construction; the jits then PIN the
            # layout (in_shardings) so GSPMD never silently re-lays-out the
            # pool, and out cache shardings == in cache shardings so the
            # donated buffers alias shard-for-shard
            self.params = jax.device_put(params, psh)
            self.cache = jax.device_put(self.cache, csh)
            # sampling state rides in REPLICATED (its leaves are tiny and
            # the Gumbel-max region must stay unsharded — ShardingPlan.rep)
            self._decode = jax.jit(
                make_engine_step(cfg, use_pallas, plan=plan),
                donate_argnums=1,
                in_shardings=(psh, csh, row, row, row, rep),
                out_shardings=(rep, rep, rep, csh))
            self._prefill = jax.jit(
                make_slot_prefill_step(cfg, use_pallas, plan=plan),
                donate_argnums=1,
                in_shardings=(psh, csh, rep, rep, rep, rep),
                out_shardings=(rep, rep, rep, csh))
        self._reset_mask = np.zeros((n_slots,), bool)
        self.decode_dispatches = 0
        self.prefill_dispatches = 0

    # --------------------------------------------------- slot lifecycle

    def mark_reset(self, s: int):
        """Zero slot s's lanes inside the next decode dispatch."""
        self._reset_mask[s] = True

    def admit(self, s: int, pages=None, pos0: int = 0):
        """Nothing device-side: dense lanes are reclaimed by reset."""

    def release(self, s: int):
        """Nothing device-side: the refill reset reclaims the lanes."""

    def set_pos(self, s: int, pos: int):
        """No-op: dense positions live on device and advance in-dispatch."""

    # ---------------------------------------------------------- compute

    def prefill_block(self, s: int, block, off: int, reset: bool,
                      row: SlotSampling):
        """Write a (1, S) prompt block into slot s's lanes in one call;
        returns (token, margin, logprob) sampled from the block's last
        position."""
        with (self.telemetry.annotate("dense.prefill")
              if self.telemetry is not None else _NULL):
            tok, margin, logprob, self.cache = self._prefill(
                self.params, self.cache, s, jnp.asarray(block), reset, row)
        self.prefill_dispatches += 1
        return int(tok), float(margin), float(logprob)

    def decode(self, toks, active_mask, sampling: SlotSampling):
        """One fused tick: every slot advances one token in ONE dispatch."""
        with (self.telemetry.annotate("dense.decode")
              if self.telemetry is not None else _NULL):
            # jnp.array, not jnp.asarray: the mask is engine state rewritten
            # below while the dispatch may still run, and on the CPU
            # backend jnp.asarray can alias a numpy buffer instead of
            # copying it
            nxt, margins, logps, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(toks),
                jnp.array(self._reset_mask), jnp.asarray(active_mask),
                sampling)
        self.decode_dispatches += 1
        self._reset_mask[:] = False
        return np.asarray(nxt), np.asarray(margins), np.asarray(logps)

    def cache_nbytes(self) -> int:
        """GLOBAL decode-state bytes, summed across every device."""
        return sum(l.nbytes for l in jax.tree.leaves(self.cache))

    def cache_nbytes_per_device(self) -> int:
        """Max addressable decode-state bytes on any one device (== global
        when unsharded; the HBM number a capacity planner cares about)."""
        return tree_device_nbytes(self.cache)


class PagedEngine:
    """Shared-page-pool decode state: block tables + host-tracked pos.

    Page *lifetime* (alloc / refcount / free) belongs to the policy
    layer's PageAllocator; this engine owns the device pool and the block
    table the dispatches scatter through.

    kernel: decode-attention pool read and write — "xla" (default, the
    equivalence oracle: gather each lane's logical ring, scatter the new
    rows with `.at[].set`) or "pallas" (the kernels/paged_attention v2
    kernel: page tiles streamed through the block table in-kernel with
    the new rows' pool scatter fused into the same pass; decode ticks
    AND chunked-prefill / resume blocks run through it).  Both run
    inside the same single fused dispatch per tick and are
    token-equivalent.  Block tables and positions are int32 at
    construction — dispatch-side code assumes it and never casts."""

    layout = "paged"

    def __init__(self, cfg: ModelConfig, params, *, n_slots: int,
                 capacity: int, page_size: int = DEFAULT_PAGE_SIZE,
                 n_pages: int | None = None, use_pallas: bool = False,
                 kernel: str = "xla", mesh=None, telemetry=None):
        self.telemetry = telemetry
        if kernel not in ("xla", "pallas"):
            raise ValueError(
                f"kernel={kernel!r}: accepted values are ('xla', 'pallas')")
        self.plan = as_plan(mesh, cfg)
        self.mesh = None if self.plan is None else self.plan.mesh
        _check_mesh_kernel(self.plan, use_pallas, kernel)
        _check_slot_groups(self.plan, n_slots)
        self.n_slot_groups = 1 if self.plan is None else self.plan.data_size
        self.cfg, self.params = cfg, params
        self.n_slots, self.capacity = n_slots, capacity
        self.page_size = page_size
        self.kernel = kernel
        self.pages_per_slot, logical = paged_attn_layout(
            cfg, capacity, page_size)
        if n_pages is None:  # full provisioning (dense-equivalent)
            n_pages = 1 + n_slots * self.pages_per_slot
        self.n_pages = n_pages
        self.ring_cap = logical
        self.block_table = np.zeros((n_slots, self.pages_per_slot), np.int32)
        self.slot_pos = np.zeros((n_slots,), np.int32)
        self.cache = init_paged_cache(cfg, n_slots, capacity, n_pages,
                                      page_size, dtype=jnp.float32)
        if self.plan is None:
            self._decode = jax.jit(
                make_paged_engine_step(cfg, use_pallas, kernel),
                donate_argnums=1)
            self._prefill = jax.jit(
                make_paged_prefill_step(cfg, use_pallas, kernel),
                donate_argnums=1)
        else:
            plan = self.plan
            psh = plan.param_shardings(params)
            csh = plan.paged_cache_shardings(self.cache)
            row, rep = plan.rows(), plan.replicated()
            self.params = jax.device_put(params, psh)
            self.cache = jax.device_put(self.cache, csh)
            # the CoW copy arrays ride in replicated, like the sampling
            # state: they index the page axis, which replicates over data
            self._decode = jax.jit(
                make_paged_engine_step(cfg, use_pallas, kernel, plan=plan),
                donate_argnums=1,
                in_shardings=(psh, csh, row, row, row, row, rep, rep, rep),
                out_shardings=(rep, rep, rep, csh))
            self._prefill = jax.jit(
                make_paged_prefill_step(cfg, use_pallas, kernel, plan=plan),
                donate_argnums=1,
                in_shardings=(psh, csh, rep, rep, rep, rep, rep, rep),
                out_shardings=(rep, rep, rep, csh))
        self._reset_mask = np.zeros((n_slots,), bool)
        # pending copy-on-write page copies, shipped with the next decode
        # dispatch: slot s copies page _copy_src[s] -> _copy_dst[s] before
        # its token scatter (dst 0 = no copy queued for that slot)
        self._copy_src = np.zeros((n_slots,), np.int32)
        self._copy_dst = np.zeros((n_slots,), np.int32)
        self.decode_dispatches = 0
        self.prefill_dispatches = 0

    # --------------------------------------------------- slot lifecycle

    def mark_reset(self, s: int):
        """Zero slot s's dense recurrent lanes in the next dispatch (pool
        pages are never zeroed — stale entries masked by position
        validity)."""
        self._reset_mask[s] = True

    def admit(self, s: int, pages=None, pos0: int = 0):
        """Point slot s's block-table row at `pages`; pos0 > 0 jump-starts
        behind a refcount-shared prompt prefix."""
        self.block_table[s, :] = 0
        if pages:
            self.block_table[s, :len(pages)] = pages
        self.slot_pos[s] = pos0

    def release(self, s: int):
        """Fall the row back to the null page so the idle lane's scatter
        lands nowhere live (the allocator reclaims the pages host-side)."""
        self.block_table[s, :] = 0
        self._copy_src[s] = 0
        self._copy_dst[s] = 0

    def fork_slot(self, src: int, dst: int):
        """Fork slot src's sequence into slot dst: block-table row and
        position copied host-side — every page is now SHARED between the
        two rows (the allocator refcounts them; a branch that writes into
        a shared page goes through queue_copy first).  No device dispatch:
        the next tick's block table simply carries the new row."""
        self.block_table[dst, :] = self.block_table[src, :]
        self.slot_pos[dst] = self.slot_pos[src]

    def queue_copy(self, s: int, src: int, dst: int):
        """Queue a copy-on-write page copy for slot s's next decode tick:
        pool page dst becomes a copy of page src INSIDE the fused
        dispatch, before slot s's token scatter lands on it."""
        assert dst > 0, (s, src, dst)
        self._copy_src[s] = src
        self._copy_dst[s] = dst

    def set_page(self, s: int, idx: int, pid: int):
        """Lazy-allocation growth: point entry idx of slot s's block-table
        row at a just-acquired page (host-side write; the next dispatch
        scatters through it)."""
        self.block_table[s, idx] = pid

    def set_pos(self, s: int, pos: int):
        self.slot_pos[s] = pos

    # ---------------------------------------------------------- compute

    def prefill_block(self, s: int, block, off: int, reset: bool,
                      row: SlotSampling):
        with (self.telemetry.annotate("paged.prefill")
              if self.telemetry is not None else _NULL):
            tok, margin, logprob, self.cache = self._prefill(
                self.params, self.cache, s, jnp.asarray(block),
                np.int32(off), jnp.asarray(self.block_table[s:s + 1]),
                reset, row)
        self.prefill_dispatches += 1
        return int(tok), float(margin), float(logprob)

    def decode(self, toks, active_mask, sampling: SlotSampling):
        with (self.telemetry.annotate("paged.decode")
              if self.telemetry is not None else _NULL):
            # copies of the engine's host state (see DenseEngine.decode):
            # positions and masks are rewritten below, before the
            # dispatch's results are read
            nxt, margins, logps, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(toks),
                jnp.array(self.slot_pos), jnp.array(self.block_table),
                jnp.array(self._reset_mask), jnp.array(self._copy_src),
                jnp.array(self._copy_dst), sampling)
        self.decode_dispatches += 1
        self._reset_mask[:] = False
        self._copy_src[:] = 0
        self._copy_dst[:] = 0
        self.slot_pos[active_mask] += 1  # idle lanes stay pinned
        return np.asarray(nxt), np.asarray(margins), np.asarray(logps)

    def cache_nbytes(self) -> int:
        """GLOBAL decode-state bytes (every device summed), host block
        table + pos vector included."""
        n = sum(l.nbytes for l in jax.tree.leaves(self.cache))
        return n + self.block_table.nbytes + self.slot_pos.nbytes

    def cache_nbytes_per_device(self) -> int:
        """Max addressable decode-state bytes on any one device; the host
        block table + pos vector ride along with every device's program."""
        return (tree_device_nbytes(self.cache) + self.block_table.nbytes
                + self.slot_pos.nbytes)


class PerSlotEngine:
    """Seed baseline: one jitted batch-1 call per active slot per tick.

    Sampling is fused into the same batch-1 program (logits + Gumbel-max
    in one call), so the baseline still pays exactly one dispatch per
    active slot-step."""

    layout = "per_slot"

    def __init__(self, cfg: ModelConfig, params, *, n_slots: int,
                 capacity: int, use_pallas: bool = False, telemetry=None):
        self.telemetry = telemetry
        self.cfg, self.params = cfg, params
        self.n_slots, self.capacity = n_slots, capacity
        self.plan, self.mesh, self.n_slot_groups = None, None, 1
        # one single-sequence cache per slot => independent positions
        self.caches = [init_cache(cfg, 1, capacity, pos=0,
                                  dtype=jnp.float32)
                       for _ in range(n_slots)]

        def slot_step(params, cache, tok, row):
            out = T.forward(params, cfg, tok, cache=cache,
                            use_pallas=use_pallas)
            logits = out.logits[0, -1]
            scores, cut = row_sample(logits, row)
            tok_, margin = argmax_with_margin(scores[None], cut[None])
            logprob = token_logprob(logits[None], tok_)
            return tok_[0], margin[0], logprob[0], out.cache

        self._step = jax.jit(slot_step)
        self.decode_dispatches = 0
        self.prefill_dispatches = 0

    def reset_slot(self, s: int):
        """Re-initialise slot s's private cache for a fresh request."""
        self.caches[s] = init_cache(self.cfg, 1, self.capacity, pos=0,
                                    dtype=jnp.float32)

    def step(self, s: int, tok: int, row: SlotSampling):
        """Advance one slot by one token (its own batch-1 dispatch)."""
        with (self.telemetry.annotate("per_slot.step")
              if self.telemetry is not None else _NULL):
            t, m, lp, self.caches[s] = self._step(
                self.params, self.caches[s],
                jnp.asarray([[tok]], jnp.int32), row)
        self.decode_dispatches += 1
        return int(t), float(m), float(lp)

    def cache_nbytes(self) -> int:
        """Live device bytes of this engine's decode state."""
        return sum(l.nbytes for c in self.caches
                   for l in jax.tree.leaves(c))

    def cache_nbytes_per_device(self) -> int:
        """Single-device engine: per-device == global."""
        return self.cache_nbytes()
