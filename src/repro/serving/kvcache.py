"""Decode-state construction: KV caches (full / sliding-window ring),
Mamba2 SSM + conv states, RWKV6 shift + wkv states; stacked over layers to
match the scanned decode path in models/transformer.py.

Two attention-cache layouts:

- dense (``init_cache``): one ``(batch, capacity, KV, hd)`` ring per layer —
  every slot owns worst-case ``capacity`` entries whether it uses them or
  not;
- paged (``init_paged_cache``): ONE shared ``(n_pages, KV, page_size, hd)``
  pool per layer, addressed through per-slot block tables of page ids
  (vLLM-style).  Slots consume pages proportional to their actual sequence
  length, and slots with a common prompt prefix can refcount the same pages
  (see scheduler.PageAllocator).  Page 0 is reserved as the null page: idle
  lanes and unallocated block-table entries point at it, so their scatter
  traffic never lands on a live page.  Recurrent state (mamba2/rwkv6) is
  O(1) and keeps the dense per-slot layout under both settings; hybrid
  routes only its shared-attention leaves through the pool.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig

DEFAULT_PAGE_SIZE = 16


def attn_cache_shape(cfg: ModelConfig, batch: int, capacity: int):
    cap = min(capacity, cfg.sliding_window) if cfg.sliding_window else capacity
    if cfg.chunked_attention:
        cap = min(cap, cfg.chunked_attention)
    return {
        "k": (batch, cap, cfg.n_kv_heads, cfg.head_dim),
        "v": (batch, cap, cfg.n_kv_heads, cfg.head_dim),
    }


def init_cache(cfg: ModelConfig, batch: int, capacity: int, pos=0,
               dtype=None):
    """Zero-initialised decode state for `batch` sequences.

    capacity: max context length the cache must hold (ring size for windowed
    attention; ignored by recurrent blocks, whose state is O(1)).
    `pos` sets the current length (dry-run uses pos = seq_len - 1: a cache
    that already holds the whole context, as in the decode_32k / long_500k
    shapes); it may be an int (lock-step batch) or a (batch,) vector of
    per-sequence positions (the slot-batched serving engine).  KV tensors
    use cfg.kv_cache_dtype when set (e.g. float8_e4m3fn halves decode cache
    bandwidth)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    kv_dtype = jnp.dtype(cfg.kv_cache_dtype) if cfg.kv_cache_dtype else dtype
    L = cfg.n_layers

    def zeros(shape):
        return jnp.zeros(shape, dtype)

    if cfg.block_kind == "attention":
        sh = attn_cache_shape(cfg, batch, capacity)
        layers = {k: jnp.zeros((L,) + v, kv_dtype) for k, v in sh.items()}
    elif cfg.block_kind == "rwkv6":
        H = cfg.d_model // cfg.ssm_head_dim
        hd = cfg.ssm_head_dim
        layers = {
            "tm": {"shift": zeros((L, batch, cfg.d_model)),
                   "wkv": jnp.zeros((L, batch, H, hd, hd), jnp.float32)},
            "cm": zeros((L, batch, cfg.d_model)),
        }
    elif cfg.block_kind == "mamba2":
        H, N, hd = cfg.ssm_heads, cfg.ssm_state_dim, cfg.ssm_head_dim
        W = cfg.ssm_conv_width
        conv_d = cfg.d_inner + 2 * N
        layers = {
            "ssm": jnp.zeros((L, batch, H, N, hd), jnp.float32),
            "conv": zeros((L, batch, W - 1, conv_d)),
        }
    elif cfg.block_kind == "hybrid":
        H, N, hd = cfg.ssm_heads, cfg.ssm_state_dim, cfg.ssm_head_dim
        W = cfg.ssm_conv_width
        conv_d = cfg.d_inner + 2 * N
        G = cfg.n_layers // cfg.hybrid_attn_every
        per = cfg.hybrid_attn_every
        layers = {
            "mamba": {
                "ssm": jnp.zeros((G, per, batch, H, N, hd), jnp.float32),
                "conv": zeros((G, per, batch, W - 1, conv_d)),
            },
        }
    else:
        raise ValueError(cfg.block_kind)

    cache = {"layers": layers, "pos": jnp.asarray(pos, jnp.int32)}
    if cfg.block_kind == "hybrid":
        G = cfg.n_layers // cfg.hybrid_attn_every
        sh = attn_cache_shape(cfg, batch, capacity)
        # kv_cache_dtype applies to the shared-attention KV exactly as it
        # does for pure-attention archs (and as the paged pools do)
        cache["shared"] = {k: jnp.zeros((G,) + v, kv_dtype)
                           for k, v in sh.items()}
    return cache


def cache_bytes(cfg: ModelConfig, batch: int, capacity: int) -> int:
    cache = jax.eval_shape(lambda: init_cache(cfg, batch, capacity))
    return sum(int(jnp.prod(jnp.asarray(l.shape)) * l.dtype.itemsize)
               for l in jax.tree.leaves(cache))


# ------------------------------------------------------------- slot ops
#
# The slot-batched serving engine holds ONE stacked cache whose batch axis
# is the slot pool.  These helpers address a single slot's lanes inside the
# stacked tree (the batch axis sits at a different depth per leaf because
# layer/group axes are stacked in front of it).


def cache_batch_axes(cfg: ModelConfig, cache):
    """Pytree matching `cache` whose leaves are the batch-axis index.

    Mirrors the layout built by init_cache (kept adjacent on purpose) and
    self-checks against it: jax.tree.map raises on any structure drift, and
    the batch-dim assertion below catches a leaf whose axis position moved.
    """
    if cfg.block_kind == "attention":
        layers = {"k": 1, "v": 1}
    elif cfg.block_kind == "rwkv6":
        layers = {"tm": {"shift": 1, "wkv": 1}, "cm": 1}
    elif cfg.block_kind == "mamba2":
        layers = {"ssm": 1, "conv": 1}
    elif cfg.block_kind == "hybrid":
        layers = {"mamba": {"ssm": 2, "conv": 2}}
    else:
        raise ValueError(cfg.block_kind)
    axes = {"layers": layers, "pos": 0}
    if "shared" in cache:
        axes["shared"] = {"k": 1, "v": 1}
    batch = jnp.shape(cache["pos"])
    if batch:  # vector pos: every leaf must carry batch at its named axis

        def check(ax, a):
            assert a.shape[ax] == batch[0], (
                f"cache leaf {a.shape} has no batch dim {batch[0]} at axis "
                f"{ax} — cache_batch_axes is out of sync with init_cache")

        jax.tree.map(check, axes, cache)
    return axes


def slot_slice(cfg: ModelConfig, cache, slot):
    """Batch-1 cache holding slot `slot`'s lanes (jit-safe, traced index)."""
    return jax.tree.map(
        lambda ax, a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=ax),
        cache_batch_axes(cfg, cache), cache)


def slot_update(cfg: ModelConfig, cache, slot, sub):
    """Write a batch-1 cache `sub` back into slot `slot` of `cache`."""
    return jax.tree.map(
        lambda ax, a, s: jax.lax.dynamic_update_slice_in_dim(
            a, s.astype(a.dtype), slot, axis=ax),
        cache_batch_axes(cfg, cache), cache, sub)


def reset_slots(cfg: ModelConfig, cache, mask):
    """Zero the lanes (state and position) of every slot where mask is True.

    mask: (batch,) bool.  Runs inside the jitted engine step, so a slot
    refill costs no host-side re-init or extra dispatch."""
    def one(ax, a):
        m = mask.reshape((1,) * ax + (-1,) + (1,) * (a.ndim - ax - 1))
        return jnp.where(m, jnp.zeros((), a.dtype), a)

    return jax.tree.map(one, cache_batch_axes(cfg, cache), cache)


# ------------------------------------------------------------ paged layout
#
# The paged cache holds attention K/V in ONE shared page pool per layer; a
# slot's entries are located through its block table ((n_slots, P) int32
# page ids, host-managed and passed into every dispatch rather than stored
# on device).  "pos" is likewise host-tracked: the scheduler knows every
# slot's fed-token count exactly, so reset / refill / prefix jump-start are
# plain host-side integer writes instead of in-dispatch masking.  Pool
# pages are never zeroed — a freshly (re)allocated page may hold a dead
# sequence's entries, but the attention mask only admits ring positions
# <= the slot's last written position, which the slot (or a live prefix
# sharer) wrote itself.


def paged_attn_layout(cfg: ModelConfig, capacity: int,
                      page_size: int = DEFAULT_PAGE_SIZE):
    """(pages_per_slot, logical_ring) of the paged layout: the dense ring
    cap (capacity, window- and chunk-capped) rounded up to whole pages."""
    cap = attn_cache_shape(cfg, 1, capacity)["k"][1]
    pages = -(-cap // page_size)
    return pages, pages * page_size


def init_paged_cache(cfg: ModelConfig, n_slots: int, capacity: int,
                     n_pages: int, page_size: int = DEFAULT_PAGE_SIZE,
                     dtype=None):
    """Paged decode state: shared attention page pools + dense recurrent
    lanes.  No "pos" and no block table live in this tree — both are
    host-owned and passed per dispatch (see serve_step.make_paged_*)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    kv_dtype = jnp.dtype(cfg.kv_cache_dtype) if cfg.kv_cache_dtype else dtype
    L = cfg.n_layers
    pool = (n_pages, cfg.n_kv_heads, page_size, cfg.head_dim)

    if cfg.block_kind == "attention":
        return {"layers": {"k": jnp.zeros((L,) + pool, kv_dtype),
                           "v": jnp.zeros((L,) + pool, kv_dtype)}}
    if cfg.block_kind == "hybrid":
        H, N, hd = cfg.ssm_heads, cfg.ssm_state_dim, cfg.ssm_head_dim
        W = cfg.ssm_conv_width
        conv_d = cfg.d_inner + 2 * N
        G = cfg.n_layers // cfg.hybrid_attn_every
        per = cfg.hybrid_attn_every
        return {
            "layers": {"mamba": {
                "ssm": jnp.zeros((G, per, n_slots, H, N, hd), jnp.float32),
                "conv": jnp.zeros((G, per, n_slots, W - 1, conv_d), dtype),
            }},
            "shared": {"k": jnp.zeros((G,) + pool, kv_dtype),
                       "v": jnp.zeros((G,) + pool, kv_dtype)},
        }
    raise ValueError(
        f"{cfg.block_kind}: recurrent decode state is O(1) — nothing to "
        "page; use the dense layout")


def paged_cache_bytes(cfg: ModelConfig, n_slots: int, capacity: int,
                      n_pages: int,
                      page_size: int = DEFAULT_PAGE_SIZE) -> int:
    """Device bytes of the paged layout, block table + pos vector included."""
    cache = jax.eval_shape(
        lambda: init_paged_cache(cfg, n_slots, capacity, n_pages, page_size))
    pool = sum(int(jnp.prod(jnp.asarray(l.shape)) * l.dtype.itemsize)
               for l in jax.tree.leaves(cache))
    pages_per_slot, _ = paged_attn_layout(cfg, capacity, page_size)
    return pool + n_slots * pages_per_slot * 4 + n_slots * 4


def paged_cache_axes(cfg: ModelConfig):
    """Slot-axis pytree for a paged cache: per-slot (dense) leaves carry
    their slot-axis index, shared pool leaves carry -1."""
    if cfg.block_kind == "attention":
        return {"layers": {"k": -1, "v": -1}}
    if cfg.block_kind == "hybrid":
        return {"layers": {"mamba": {"ssm": 2, "conv": 2}},
                "shared": {"k": -1, "v": -1}}
    raise ValueError(cfg.block_kind)


def paged_slot_slice(cfg: ModelConfig, cache, slot):
    """Batch-1 view of slot `slot`: dense leaves sliced, pools passed whole
    (the block table, not the slice, scopes a slot's pool accesses)."""
    return jax.tree.map(
        lambda ax, a: a if ax < 0 else
        jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=ax),
        paged_cache_axes(cfg), cache)


def paged_slot_update(cfg: ModelConfig, cache, slot, sub):
    """Write a batch-1 `sub` back: dense leaves into slot `slot`'s lanes;
    pool leaves replace the pool wholesale (sub's pool IS the updated one)."""
    return jax.tree.map(
        lambda ax, a, s: s.astype(a.dtype) if ax < 0 else
        jax.lax.dynamic_update_slice_in_dim(a, s.astype(a.dtype), slot,
                                            axis=ax),
        paged_cache_axes(cfg), cache, sub)


def reset_paged_slots(cfg: ModelConfig, cache, mask):
    """Zero the per-slot dense lanes (hybrid recurrent state) of every slot
    where mask is True; pool pages are reclaimed by the allocator instead
    and their stale contents masked by position validity."""
    def one(ax, a):
        if ax < 0:
            return a
        m = mask.reshape((1,) * ax + (-1,) + (1,) * (a.ndim - ax - 1))
        return jnp.where(m, jnp.zeros((), a.dtype), a)

    return jax.tree.map(one, paged_cache_axes(cfg), cache)


def cow_copy_pages(cfg: ModelConfig, cache, copy_src, copy_dst):
    """Copy-on-write page copies INSIDE the fused dispatch: for every pair
    (copy_src[i], copy_dst[i]) with dst > 0, page dst of each shared pool
    becomes a copy of page src — the branch that is about to write into a
    refcount-shared page gets its private copy and the token scatter that
    follows in the same dispatch lands on it (on both kernels: the XLA
    `.at[].set` scatter and the Pallas in-kernel fused write each run
    AFTER this copy in the forward, so ordering holds regardless of
    which path writes the pool).  Rows with dst == 0 are
    no-ops (page 0 is the null page: src is forced to 0 too, so the
    gather/scatter is the identity on the null page).  A whole-batch
    ``cond`` skips the copy compute entirely on ticks where no slot forked
    — mirroring the all-greedy sampling skip — so non-forking workloads
    compile and pay exactly the pre-CoW program body.

    copy_src / copy_dst: (n_slots,) int32 page ids, one potential copy per
    slot per tick (a slot crosses at most one page boundary per token)."""
    src = jnp.where(copy_dst > 0, copy_src, 0)
    dst = jnp.where(copy_dst > 0, copy_dst, 0)

    def copy(cache):
        def one(ax, a):
            if ax >= 0:
                return a  # per-slot dense lanes: never shared, never CoW'd
            # pool leaves are (..., n_pages, KV, page_size, hd): page axis
            # is -4.  Duplicate dst=0 rows all write page 0 with page 0's
            # own contents, so scatter order does not matter.
            moved = jnp.moveaxis(a, -4, 0)
            moved = moved.at[dst].set(moved[src])
            return jnp.moveaxis(moved, 0, -4)

        return jax.tree.map(one, paged_cache_axes(cfg), cache)

    return jax.lax.cond(jnp.any(copy_dst > 0), copy, lambda c: c, cache)


def reset_paged_sub(cfg: ModelConfig, sub, reset):
    """Zero a batch-1 paged sub-cache's dense lanes where `reset` (traced
    bool) — the first prefill block of a refilled slot."""
    return jax.tree.map(
        lambda ax, a: a if ax < 0 else
        jnp.where(reset, jnp.zeros((), a.dtype), a),
        paged_cache_axes(cfg), sub)


# --------------------------------------------------------- mesh shardings
#
# NamedSharding trees for both cache layouts on a serving mesh (axis names
# from ("pod", "data", "model"); see serving/sharding.ShardingPlan, which
# wraps these with the engine-facing API).  Contract:
#
# - dense pool: the slot/batch axis of every leaf (and the (n_slots,) pos
#   vector) shards over the data axes; attention K/V leaves additionally
#   shard their KV-head axis over "model";
# - paged pool: per-slot (hybrid recurrent) leaves shard their slot axis
#   over data; the shared (n_pages, KV, page_size, hd) pools shard the
#   KV-head axis over "model" and REPLICATE over data — any slot's block
#   table may point at any page, so the page axis cannot follow the slots;
# - divisibility fallback everywhere: a dim shards only when the mesh axis
#   divides it evenly (GQA KV heads replicate when n_kv < model axis).


def _mesh_sizes(mesh, data_axes, model_axis):
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ds = math.prod(sizes.get(a, 1) for a in data_axes)
    ms = sizes.get(model_axis, 1) if model_axis else 1
    return ds, ms


def dense_cache_shardings(cfg: ModelConfig, cache, mesh, *,
                          data_axes=("data",), model_axis="model"):
    """NamedSharding tree for a dense (init_cache) pool cache."""
    ds, ms = _mesh_sizes(mesh, data_axes, model_axis)
    axes = cache_batch_axes(cfg, cache)

    def one(path, ax, leaf):
        spec = [None] * leaf.ndim
        if ds > 1 and leaf.ndim > ax and leaf.shape[ax] % ds == 0:
            spec[ax] = tuple(data_axes)
        name = getattr(path[-1], "key", None) if path else None
        # attention K/V leaves are (..., batch, T, KV, hd): KV at ax + 2
        if (name in ("k", "v") and ms > 1 and leaf.ndim == ax + 4
                and leaf.shape[ax + 2] % ms == 0):
            spec[ax + 2] = model_axis
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, axes, cache)


def paged_cache_shardings(cfg: ModelConfig, cache, mesh, *,
                          data_axes=("data",), model_axis="model"):
    """NamedSharding tree for a paged (init_paged_cache) cache."""
    ds, ms = _mesh_sizes(mesh, data_axes, model_axis)
    axes = paged_cache_axes(cfg)

    def one(ax, leaf):
        spec = [None] * leaf.ndim
        if ax >= 0:  # per-slot dense lanes (hybrid recurrent state)
            if ds > 1 and leaf.shape[ax] % ds == 0:
                spec[ax] = tuple(data_axes)
        elif ms > 1 and leaf.shape[-3] % ms == 0:
            # shared pool (..., n_pages, KV, page_size, hd): KV at -3
            spec[-3] = model_axis
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, axes, cache)


def constrain_cache(cache, shardings):
    """Sharded variant of the slot ops' output: re-pin a cache tree's
    shardings mid-trace (after reset_slots / slot_update / scatter) so
    GSPMD keeps the slot and KV axes partitioned instead of re-deciding
    the layout after every update."""
    return jax.tree.map(jax.lax.with_sharding_constraint, cache, shardings)
