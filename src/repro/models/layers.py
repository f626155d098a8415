"""Core transformer layers: RMSNorm, RoPE (incl. M-RoPE), GQA attention
(qk-norm, QKV-bias, sliding-window, chunked-local, KV-cache decode), SwiGLU.

Pure functions over param dicts; params carry a leading layer axis when used
under `lax.scan` (see transformer.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig


def rms_norm(x, gamma, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32)).astype(x.dtype)


# ------------------------------------------------------------------- RoPE


def rope_angles(positions, head_dim: int, theta: float):
    """positions: (..., S) -> cos/sin (..., S, head_dim/2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def mrope_angles(positions_3d, head_dim: int, theta: float, sections):
    """M-RoPE (Qwen2-VL): positions_3d (B, S, 3) = (t, h, w) ids.

    The head_dim/2 rotary frequencies are split into `sections` (t, h, w);
    each section rotates by its own position component.
    """
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    sec_id = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                        total_repeat_length=half)  # (half,)
    pos = jnp.take_along_axis(
        positions_3d.astype(jnp.float32),
        jnp.broadcast_to(sec_id[None, None, :].astype(jnp.int32),
                         positions_3d.shape[:2] + (half,)),
        axis=-1)  # (B, S, half)
    ang = pos * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (B, S, H, hd); cos/sin: (B, S, hd/2) or (S, hd/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos = cos[None]
        sin = sin[None]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# --------------------------------------------------------------- attention


def _attn_mask(q_pos, k_pos, window: int = 0, chunk: int = 0,
               chunk_on=None):
    """Boolean (..., S_q, S_k) mask: causal, optionally windowed/chunked.

    chunk_on: traced bool scalar selecting chunked-local vs global masking
    (llama4 interleaves both kinds across layers inside one lax.scan)."""
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window:
        m &= k_pos[..., None, :] > (q_pos[..., :, None] - window)
    if chunk:
        cm = (k_pos[..., None, :] // chunk) == (q_pos[..., :, None] // chunk)
        if chunk_on is None:
            m &= cm
        else:
            m &= jnp.where(chunk_on, cm, True)
    return m


def multi_head_attention(q, k, v, mask, dtype=None):
    """q: (B,S,H,hd), k/v: (B,T,KV,hd) with H = g*KV (GQA).  jnp reference
    path (the Pallas flash kernel lives in repro.kernels.flash_attention).

    Note: keeps operands in their storage dtype and accumulates the dots in
    fp32 via preferred_element_type — upcasting a 32k-token KV cache to fp32
    before the dot doubles its HBM traffic (§Perf decode finding)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    q = q.reshape(B, S, KV, g, hd)
    scale = 1.0 / float(hd) ** 0.5
    logits = jnp.einsum("bskgh,btkh->bkgst", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, hd).astype(dtype or v.dtype)


def chunked_attention(q, k, v, q_pos, k_pos, cfg: ModelConfig,
                      layer_chunked=None, dtype=None):
    """Flash-style online-softmax attention over k-blocks (pure jnp).

    Mirrors the Pallas kernel's algorithm (kernels/flash_attention) so the
    dry-run lowers the same memory behaviour XLA/Mosaic would see on TPU:
    no (S, S) probability tensor is ever materialized — the working set per
    scan step is (S, block).  This is the §Perf "memory term" lever for the
    prefill/train shapes."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    KV = k.shape[2]
    g = H // KV
    bk = min(cfg.attention_block, T)
    while T % bk:
        bk -= 1
    n_blocks = T // bk
    scale = 1.0 / float(hd) ** 0.5
    qh = q.reshape(B, S, KV, g, hd)

    kb = k.reshape(B, n_blocks, bk, KV, hd)
    vb = v.reshape(B, n_blocks, bk, KV, hd)
    kpb = k_pos.reshape(B, n_blocks, bk) if k_pos.ndim == 2 else \
        jnp.broadcast_to(k_pos.reshape(n_blocks, bk)[None], (B, n_blocks, bk))

    def body(carry, xs):
        m, l, acc = carry
        kj, vj, kp = xs  # (B, bk, KV, hd), (B, bk)
        s = jnp.einsum("bskgh,btkh->bkgst", qh, kj,
                       preferred_element_type=jnp.float32) * scale
        blk_mask = _attn_mask(q_pos, kp, cfg.sliding_window,
                              cfg.chunked_attention, chunk_on=layer_chunked)
        s = jnp.where(blk_mask[:, None, None, :, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgst,btkh->bskgh", p.astype(vj.dtype), vj,
                        preferred_element_type=jnp.float32)
        acc = acc * jnp.moveaxis(alpha, -1, 1)[..., None] + pv
        return (m_new, l, acc), None

    m0 = jnp.full((B, KV, g, S), -1e30, jnp.float32)
    l0 = jnp.zeros((B, KV, g, S), jnp.float32)
    acc0 = jnp.zeros((B, S, KV, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0),
         jnp.moveaxis(kpb, 1, 0)))
    l = jnp.maximum(l, 1e-30)
    out = acc / jnp.moveaxis(l, -1, 1)[..., None]
    return out.reshape(B, S, H, hd).astype(dtype or v.dtype)


def attention_block(p, x, cfg: ModelConfig, *, positions=None, cache=None,
                    layer_chunked: bool = False, use_pallas: bool = False,
                    paged_kernel: str = "xla", shard=None):
    """GQA attention with RoPE/M-RoPE, qk-norm, bias, window/chunk masking.

    cache: None for training (full self-attention over x), else a decode
    cache dict, in one of two layouts:
      - dense: {"k": (B, T, KV, hd), "v": ..., "pos": int32 current length}
        — each lane owns a T-entry ring;
      - paged: {"k": (n_pages, KV, page_size, hd), "v": ... (shared pools),
        "block_table": (B, P) int32 page ids, "pos": ...} — lanes address a
        shared page pool through their block table; the logical ring is
        P * page_size entries.
    Returns (out, new_cache).  "pos" is a scalar for a lock-step batch or a
    (B,) vector of per-sequence positions (the slot-batched serving engine);
    decode accepts S >= 1 tokens (chunked prefill writes a whole block).

    paged_kernel: "xla" (default) scatters the S new K/V rows into the
    pool and reads it back by gathering each lane's logical ring into a
    (B, T, KV, hd) tensor; "pallas" runs the v2 paged-attention kernel
    (kernels/paged_attention) — the scatter is FUSED into the kernel's
    page-streaming pass (no separate pool write) and any S >= 1 block
    with 1-D positions is eligible, so decode, chunked prefill, and
    resume-recompute all go through it.  Still XLA-only: M-RoPE (3-D
    positions), chunked-local masking, mesh sharding, S > ring length —
    those fall back, so both settings stay token-equivalent end to end.

    shard: optional serving.sharding.ShardingPlan — pins q/k/v, the cache
    writes, and the attention output with with_sharding_constraint (batch
    on the data axes, heads on the model axis; GQA KV heads replicate when
    n_kv does not divide the model axis).  No-op on 1-device meshes.
    """
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if shard is not None:
        q = shard.act(q, batch=0, heads=2)
        k = shard.act(k, batch=0, heads=2)
        v = shard.act(v, batch=0, heads=2)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)

    if cache is None:
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        if cfg.mrope:
            pos3 = (positions if positions.ndim == 3 else
                    jnp.broadcast_to(positions[..., None],
                                     positions.shape + (3,)))
            cos, sin = mrope_angles(pos3, hd, cfg.rope_theta,
                                    cfg.mrope_sections)
            pos_1d = pos3[..., 0]
        else:
            cos, sin = rope_angles(positions, hd, cfg.rope_theta)
            pos_1d = positions
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        window = cfg.sliding_window
        if use_pallas and not cfg.mrope and not cfg.chunked_attention:
            from repro.kernels.flash_attention import ops as fa_ops

            out = fa_ops.flash_attention(q, k, v, causal=True, window=window)
        elif cfg.attention_impl == "chunked":
            out = chunked_attention(q, k, v, pos_1d, pos_1d, cfg,
                                    layer_chunked=layer_chunked)
        else:
            mask = _attn_mask(pos_1d, pos_1d, window, cfg.chunked_attention,
                              chunk_on=layer_chunked)
            out = multi_head_attention(q, k, v, mask)
        new_cache = None
    else:
        # decode: append the S new tokens to the cache starting at
        # cache["pos"] (scalar, or (B,) per-slot positions).  A multi-token
        # block (chunked prefill) must not wrap the ring past entries its own
        # earlier tokens still attend to — the serving engine caps block
        # sizes so writes never evict live window entries.
        pos = cache["pos"]
        pos_b = jnp.broadcast_to(pos, (B,))
        abs_pos = pos_b[:, None] + jnp.arange(S)[None, :]  # (B, S)
        default_pos = positions is None
        if default_pos:
            positions = abs_pos
        if cfg.mrope:
            pos3 = (positions if positions.ndim == 3 else
                    jnp.broadcast_to(positions[..., None],
                                     positions.shape + (3,)))
            cos, sin = mrope_angles(pos3, hd, cfg.rope_theta,
                                    cfg.mrope_sections)
        else:
            cos, sin = rope_angles(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        paged = "block_table" in cache
        kv_dtype = cache["k"].dtype  # may be narrower (kv_cache_dtype)
        b_idx = jnp.arange(B)[:, None]
        out = None
        if paged:
            # paged pool: the S new tokens land in the shared pool through
            # the block table, then attention reads the pool back.  Two
            # paths: the Pallas v2 kernel fuses the scatter INTO the same
            # grid pass that streams page tiles through the block table
            # (no separate pool scatter, no (B, T, KV, hd) gather); the
            # XLA path scatters into the flat pool and gathers each lane's
            # whole logical ring.  Unallocated table entries point at the
            # null page 0; its (garbage) entries sit at ring indices past
            # `last` and are cut by the validity mask either way.
            bt = cache["block_table"]  # (B, P) page ids
            psz = cache["k"].shape[2]
            T = bt.shape[1] * psz
            if (paged_kernel == "pallas" and shard is None
                    and not cfg.mrope and not cfg.chunked_attention
                    and positions.ndim == 2 and S <= T):
                # eligible for the kernel: any S block (decode, chunked
                # prefill, resume-recompute), default or per-row 1-D
                # positions.  Still XLA-only: M-RoPE (3-D positions),
                # chunked-local masking, mesh sharding (the kernel is a
                # single-device program), S > ring.
                from repro.kernels.paged_attention import ops as pa_ops

                out, store_k, store_v = pa_ops.paged_attention_update(
                    q, k, v, cache["k"], cache["v"], bt, abs_pos[:, -1],
                    window=cfg.sliding_window,
                    q_positions=None if default_pos else positions)
            else:
                # pool (n_pages, KV, psz, hd): ring slot i of lane b is
                # row i % psz of page bt[b, i // psz]
                slots = abs_pos % T
                page = bt[b_idx, slots // psz]  # (B, S)
                store_k = cache["k"].at[page, :, slots % psz].set(
                    k.astype(kv_dtype))
                store_v = cache["v"].at[page, :, slots % psz].set(
                    v.astype(kv_dtype))
                if shard is not None:
                    store_k = shard.act(store_k, heads=1)
                    store_v = shard.act(store_v, heads=1)
                ring = jnp.arange(T)
                ring_page, ring_row = bt[:, ring // psz], ring % psz
                ck = store_k[ring_page, :, ring_row]  # (B, T, KV, hd)
                cv = store_v[ring_page, :, ring_row]
                if shard is not None:
                    ck = shard.act(ck, batch=0, heads=2)
                    cv = shard.act(cv, batch=0, heads=2)
        else:
            T = cache["k"].shape[1]
            slots = abs_pos % T  # ring writes; capacity == window when windowed
            ck = cache["k"].at[b_idx, slots].set(k.astype(kv_dtype))
            cv = cache["v"].at[b_idx, slots].set(v.astype(kv_dtype))
            if shard is not None:  # ring: (B, T, KV, hd)
                ck = shard.act(ck, batch=0, heads=2)
                cv = shard.act(cv, batch=0, heads=2)
            store_k, store_v = ck, cv
        if out is None:
            # absolute position held by ring slot i after the writes: the
            # largest value congruent to i (mod T) that is <= the last
            # written position.  For a non-ring cache (last < T) this
            # reduces to k_pos = i for i <= last, invalid beyond.
            last = abs_pos[:, -1]  # (B,)
            idx = jnp.arange(T)
            k_pos = last[:, None] - ((last[:, None] - idx[None, :]) % T)
            valid = k_pos >= 0  # (B, T)
            q_pos = positions[..., 0] if positions.ndim == 3 else positions
            mask = _attn_mask(q_pos, k_pos, cfg.sliding_window,
                              cfg.chunked_attention, chunk_on=layer_chunked)
            mask &= valid[:, None, :]
            out = multi_head_attention(q, ck.astype(q.dtype),
                                       cv.astype(q.dtype), mask,
                                       dtype=q.dtype)
        new_cache = {"k": store_k, "v": store_v, "pos": pos + S}

    if shard is not None:
        out = shard.act(out, batch=0, heads=2)
    out = out.reshape(B, S, H * hd) @ p["wo"]
    return out, new_cache


# ------------------------------------------------------------------- MLP


def swiglu_mlp(p, x):
    gate = jax.nn.silu(x @ p["w_gate"])
    up = x @ p["w_up"]
    return (gate * up) @ p["w_down"]
