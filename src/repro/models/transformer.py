"""Composable decoder: embedding -> scanned blocks -> head, for every
assigned architecture family.

- training forward: full-sequence, lax.scan over stacked layer params with
  optional remat (activation checkpointing);
- decode forward: new tokens against per-layer KV caches / SSM states (see
  repro.serving for cache construction).  cache["pos"] may be a scalar
  (lock-step batch) or a (B,) vector of per-sequence positions — the
  slot-batched serving engine; S > 1 is the chunked-prefill path, which
  writes a whole block of prompt tokens into the cache in one call;
- hybrid (zamba2): nested scan — groups of Mamba2 layers, with one *shared*
  attention block (single param copy) applied after every group;
- modality frontends are stubs per the assignment: VLM patch embeddings and
  audio codebook token frames arrive precomputed via input_specs().
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import layers as Lyr
from repro.models import moe as Moe
from repro.models import ssm as Ssm
from repro.models.config import ModelConfig


class ForwardOut(NamedTuple):
    logits: jax.Array           # fp32 (B, S, V) or (B, S, codebooks, V)
    cache: Any                  # None for training
    aux_loss: jax.Array         # MoE load-balance loss (0.0 otherwise)


# ----------------------------------------------------------------- blocks


def _attn_mlp_block(p, h, cfg: ModelConfig, *, positions, cache,
                    layer_chunked, use_pallas, paged_kernel="xla",
                    shard=None):
    a, new_cache = Lyr.attention_block(
        p["attn"], Lyr.rms_norm(h, p["ln1"], cfg.norm_eps), cfg,
        positions=positions, cache=cache, layer_chunked=layer_chunked,
        use_pallas=use_pallas, paged_kernel=paged_kernel, shard=shard)
    h = h + a
    x2 = Lyr.rms_norm(h, p["ln2"], cfg.norm_eps)
    if cfg.is_moe:
        m, aux = Moe.moe_ffn(p["moe"], x2, cfg)
    else:
        m, aux = Lyr.swiglu_mlp(p["mlp"], x2), jnp.float32(0.0)
    return h + m, new_cache, aux


def _rwkv_block(p, h, cfg: ModelConfig, *, cache, use_pallas):
    tm_state = None if cache is None else cache["tm"]
    cm_state = None if cache is None else cache["cm"]
    a, new_tm = Ssm.rwkv6_timemix(
        p["rwkv"], Lyr.rms_norm(h, p["ln1"], cfg.norm_eps), cfg,
        state=tm_state, use_pallas=use_pallas)
    h = h + a
    m, new_cm = Ssm.rwkv6_channelmix(
        p["rwkv"]["cm"], Lyr.rms_norm(h, p["ln2"], cfg.norm_eps), cfg,
        state=cm_state)
    new_cache = None if cache is None else {"tm": new_tm, "cm": new_cm}
    return h + m, new_cache, jnp.float32(0.0)


def _mamba_block(p, h, cfg: ModelConfig, *, cache, use_pallas):
    a, new_cache = Ssm.mamba2_block(
        p["mamba"], Lyr.rms_norm(h, p["ln1"], cfg.norm_eps), cfg,
        state=cache, use_pallas=use_pallas)
    return h + a, new_cache, jnp.float32(0.0)


def _block(p, h, cfg, *, positions, cache, layer_chunked, use_pallas,
           paged_kernel="xla", shard=None):
    if cfg.block_kind == "attention":
        return _attn_mlp_block(p, h, cfg, positions=positions, cache=cache,
                               layer_chunked=layer_chunked,
                               use_pallas=use_pallas,
                               paged_kernel=paged_kernel, shard=shard)
    if cfg.block_kind == "rwkv6":
        return _rwkv_block(p, h, cfg, cache=cache, use_pallas=use_pallas)
    if cfg.block_kind in ("mamba2", "hybrid"):
        return _mamba_block(p, h, cfg, cache=cache, use_pallas=use_pallas)
    raise ValueError(cfg.block_kind)


def _chunked_flags(cfg: ModelConfig) -> jnp.ndarray:
    """llama4-style: chunked-local attention on all layers except every
    `chunked_global_every`-th, which stays global."""
    idx = jnp.arange(cfg.n_layers)
    if cfg.chunked_attention:
        return ((idx + 1) % cfg.chunked_global_every) != 0
    return jnp.zeros((cfg.n_layers,), bool)


# ------------------------------------------------------------- embeddings


def embed_inputs(params, cfg: ModelConfig, tokens, patch_embeds=None):
    """tokens: (B, S) int32 — or (B, S, codebooks) for audio.

    VLM: patch_embeds (B, P, D) are prepended to the token embeddings
    (vision tower is a stub; embeddings arrive precomputed)."""
    emb = params["embed"]["tok"]
    if cfg.num_codebooks > 1:
        h = sum(emb[c][tokens[..., c]] for c in range(cfg.num_codebooks))
    else:
        h = emb[tokens]
    if patch_embeds is not None:
        h = jnp.concatenate([patch_embeds.astype(h.dtype), h], axis=1)
    return h


def mrope_positions(cfg: ModelConfig, batch: int, seq: int):
    """Default M-RoPE position ids: a sqrt(P) x sqrt(P) patch grid at t=0,
    then text positions advancing all three components (Qwen2-VL scheme)."""
    Pn = cfg.n_patches
    g = max(1, int(Pn ** 0.5))
    i = jnp.arange(seq)
    t = jnp.where(i < Pn, 0, i - Pn + g)
    hh = jnp.where(i < Pn, (i % (g * g)) // g, i - Pn + g)
    ww = jnp.where(i < Pn, (i % (g * g)) % g, i - Pn + g)
    pos3 = jnp.stack([t, hh, ww], axis=-1)  # (S, 3)
    return jnp.broadcast_to(pos3[None], (batch, seq, 3))


def unembed(params, cfg: ModelConfig, h):
    """Logits in fp32, straight from the dot's fp32 accumulator: rounding
    them to a bf16 model dtype would quantize every top1-top2 gap to the
    bf16 spacing of the top logit (1/64 near 3), so the tie margins the
    serving engines record could not tell a tie from a clear choice."""
    f32 = jnp.float32
    if cfg.num_codebooks > 1:
        return jnp.einsum("bsd,cdv->bscv", h, params["lm_head"],
                          preferred_element_type=f32)
    w = params["embed"]["tok"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.matmul(h, w, preferred_element_type=f32)


# ---------------------------------------------------------------- forward


def _scan_or_loop(body, carry, xs, use_scan: bool):
    """lax.scan or an unrolled python loop over the leading axis of xs.

    The unrolled path exists for the dry-run's cost calibration: XLA's
    cost_analysis counts a while-loop body ONCE, so per-layer FLOP/byte/
    collective deltas are measured on a small unrolled model and scaled
    (launch/dryrun.py)."""
    if use_scan:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


def forward(params, cfg: ModelConfig, tokens, *, patch_embeds=None,
            positions=None, cache=None, use_pallas: bool = False,
            paged_kernel: str = "xla", shard=None) -> ForwardOut:
    """Training (cache=None, full sequence) or decode (cache set, S>=1).

    paged_kernel: paged-pool attention implementation — "xla" (pool
    scatter + ring gather) or "pallas" (kernels/paged_attention v2: the
    S new K/V rows are written in-kernel and any S>=1 block with 1-D
    positions runs through it — decode AND chunked prefill); only
    consulted when the cache carries a block table (eligibility and the
    XLA fallback rules live in layers.attention_block).

    shard: optional serving.sharding.ShardingPlan — constrains the
    residual stream's batch dim to the data axes and the attention head
    dims to the model axis (with_sharding_constraint; a strict no-op on
    1-device meshes so the traced program matches shard=None)."""
    h = embed_inputs(params, cfg, tokens, patch_embeds)
    if shard is not None:
        h = shard.act(h, batch=0)
    B, S = h.shape[:2]
    if cfg.mrope and positions is None and cache is None:
        positions = mrope_positions(cfg, B, S)

    decode = cache is not None
    pos_scalar = None if not decode else cache["pos"]
    # paged attention: the (B, P) block table is shared by every layer's
    # pool; it rides the top-level cache dict and is injected per layer
    block_table = cache.get("block_table") if decode else None
    if decode and cfg.mrope:
        # decode M-RoPE: text positions advance all three components
        p1 = (jnp.broadcast_to(pos_scalar, (B,))[:, None]
              + jnp.arange(S)[None, :])
        decode_pos3 = jnp.broadcast_to(p1[..., None],
                                       (B, S, 3)).astype(jnp.int32)

    def body_fn(carry, xs):
        h, aux = carry
        p, flag, cache_l = xs
        if not decode:
            cache_l = None  # training: the scan xs slot is a dummy
        elif cfg.block_kind == "attention":
            cache_l = dict(cache_l, pos=pos_scalar)
            if block_table is not None:
                cache_l["block_table"] = block_table
        if decode and cfg.mrope:
            pos_l = decode_pos3
        else:
            pos_l = positions
        h, new_cache_l, aux_l = _block(
            p, h, cfg, positions=pos_l, cache=cache_l,
            layer_chunked=flag, use_pallas=use_pallas,
            paged_kernel=paged_kernel, shard=shard)
        if decode and cfg.block_kind == "attention":
            new_cache_l = {k: v for k, v in new_cache_l.items()
                           if k not in ("pos", "block_table")}
        return (h, aux + aux_l), new_cache_l

    body = body_fn
    if cfg.remat and cfg.remat_policy != "none" and not decode:
        policy = {
            "full": jax.checkpoint_policies.nothing_saveable,
            # save matmul outputs, recompute the cheap elementwise chain —
            # trades recompute FLOPs for HBM traffic (§Perf lever)
            "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        }[cfg.remat_policy]
        body = jax.checkpoint(body_fn, policy=policy)

    flags = _chunked_flags(cfg)
    aux0 = jnp.float32(0.0)
    layer_caches = None if not decode else cache["layers"]

    if cfg.block_kind == "hybrid" and cfg.hybrid_attn_every:
        G = cfg.n_layers // cfg.hybrid_attn_every
        gflags = flags.reshape(G, cfg.hybrid_attn_every)
        shared = params["shared"]

        def group_fn(carry, xs):
            p_group, f_group, c_group, c_shared = xs
            inner_caches = (None if not decode else c_group["mamba"])
            (h, aux), new_inner = _scan_or_loop(
                body, carry, (p_group, f_group,
                              _none_like(p_group, cfg) if not decode
                              else inner_caches), cfg.scan_layers)
            sc = None if not decode else dict(c_shared, pos=pos_scalar)
            if sc is not None and block_table is not None:
                sc["block_table"] = block_table
            h, new_sc, aux_s = _attn_mlp_block(
                shared, h, cfg, positions=positions, cache=sc,
                layer_chunked=False, use_pallas=use_pallas,
                paged_kernel=paged_kernel, shard=shard)
            if decode:
                new_sc = {k: v for k, v in new_sc.items()
                          if k not in ("pos", "block_table")}
                new_caches = {"mamba": new_inner, "shared": new_sc}
            else:
                new_caches = new_inner
            return (h, aux + aux_s), new_caches

        if decode:
            xs = (params["layers"], gflags, cache["layers"],
                  cache["shared"])
        else:
            xs = (params["layers"], gflags,
                  _none_like_outer(params["layers"], cfg),
                  _none_like_outer(params["layers"], cfg))
        (h, aux), new_layer_caches = _scan_or_loop(group_fn, (h, aux0), xs,
                                                   cfg.scan_layers)
        new_shared = None
        if decode:
            new_shared = new_layer_caches["shared"]
            new_layer_caches = {"mamba": new_layer_caches["mamba"]}
    else:
        xs_caches = (layer_caches if decode
                     else _none_like(params["layers"], cfg))
        (h, aux), new_layer_caches = _scan_or_loop(
            body, (h, aux0), (params["layers"], flags, xs_caches),
            cfg.scan_layers)
        new_shared = None

    h = Lyr.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, cfg, h)

    new_cache = None
    if decode:
        new_cache = {"layers": new_layer_caches, "pos": pos_scalar + S}
        if new_shared is not None:
            new_cache["shared"] = new_shared
    return ForwardOut(logits=logits, cache=new_cache, aux_loss=aux)


def _none_like(stacked_layer_params, cfg):
    """Per-layer dummy scan input when no cache is threaded (training)."""
    n = cfg.n_layers if cfg.block_kind != "hybrid" else cfg.hybrid_attn_every
    return jnp.zeros((n,), jnp.int32)


def _none_like_outer(stacked_layer_params, cfg):
    G = cfg.n_layers // cfg.hybrid_attn_every
    return jnp.zeros((G,), jnp.int32)
