"""Serving steps: batched single-token decode against a KV cache / SSM
state, prefill (full-sequence forward), a sampling-aware generation loop,
and the slot-batched engine steps (fused decode over a slot pool with
per-slot positions and per-slot sampling, chunked prefill into one slot's
lanes).

Every fused step takes a ``SlotSampling`` batch (per-slot PRNG keys, emit
indices, temperature / top-k / top-p — see serving/sampling.py): sampled
and greedy slots ride through the SAME compiled program, so stochastic
decode still costs exactly one dispatch per engine tick and a temperature
of 0 recovers the greedy trajectory bit-for-bit."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving.kvcache import (cow_copy_pages, paged_slot_slice,
                                   paged_slot_update, reset_paged_slots,
                                   reset_paged_sub, reset_slots, slot_slice,
                                   slot_update)
from repro.serving.sampling import (SamplingParams, argmax_with_margin,
                                    batched_sample, lockstep_scores,
                                    row_sample, token_logprob)


def make_serve_step(cfg: ModelConfig, use_pallas: bool = False):
    """Returns step(params, cache, tokens) -> (logits, new_cache).

    tokens: (B, 1) int32 — or (B, 1, codebooks) for audio — the token decoded
    at position cache["pos"]; logits predict position pos+1.
    """

    def step(params, cache, tokens):
        out = T.forward(params, cfg, tokens, cache=cache,
                        use_pallas=use_pallas)
        return out.logits[:, 0], out.cache

    return step


def make_prefill_step(cfg: ModelConfig, use_pallas: bool = False):
    """Full-sequence forward (inference-prefill shape): logits only."""

    def step(params, tokens, patch_embeds=None):
        out = T.forward(params, cfg, tokens, patch_embeds=patch_embeds,
                        use_pallas=use_pallas)
        return out.logits

    return step


def make_engine_step(cfg: ModelConfig, use_pallas: bool = False,
                     plan=None):
    """Fused slot-batched decode: ONE device program advances every slot of
    the pool by one token.

    step(params, cache, tokens, reset_mask, active_mask, sampling)
        -> (next_tok, margin, logprob, cache)

    cache: a stacked pool cache (batch == n_slots) with a (n_slots,) vector
    "pos" — every slot decodes at its own position.  tokens: (n_slots, 1)
    int32, the token each slot consumes this tick (prompt feed or last
    generated; don't-care for idle slots).  reset_mask: (n_slots,) bool —
    slots being refilled this tick have their lanes zeroed *inside* the same
    dispatch, so refill costs no extra device call.  active_mask: (n_slots,)
    bool — "pos" advances only for lanes carrying a sequence; an idle lane's
    position stays pinned (its dead-lane compute still runs but keeps
    writing the same ring entry of its own lanes, which the refill reset
    zeroes).  sampling: a SlotSampling batch — per-slot Gumbel-max sampling
    happens inside this dispatch; temperature-0 slots take the greedy
    argmax of the raw logits.  next_tok: (n_slots,) chosen token per slot;
    margin: (n_slots,) top1-top2 score gap (a near-zero margin marks a
    numerical tie where compiled variants of the same math may legitimately
    pick different tokens); logprob: (n_slots,) fp32 log-probability of the
    chosen token under the RAW (unscaled) distribution — best-of-n ranks
    branches by its cumulative sum.

    plan: optional ShardingPlan — re-pins the cache's slot/KV-head
    partitioning after the in-trace reset and threads activation
    constraints through the forward (no-op trace-wise on a 1-device
    mesh, so mesh=(1,1) compiles the same program as plan=None)."""

    def step(params, cache, tokens, reset_mask, active_mask, sampling):
        cache = reset_slots(cfg, cache, reset_mask)
        if plan is not None:
            cache = plan.constrain_dense_cache(cache)
        pos0 = cache["pos"]
        out = T.forward(params, cfg, tokens, cache=cache,
                        use_pallas=use_pallas, shard=plan)
        logits = out.logits[:, -1]
        if plan is not None:
            # replicate the Gumbel-max region: sharding the legacy threefry
            # RNG would change the noise bits (see ShardingPlan.rep)
            logits = plan.rep(logits)
        scores, cut = batched_sample(logits, sampling)
        if plan is not None:
            scores = plan.rep(scores)
        next_tok, margin = argmax_with_margin(scores, cut)
        logprob = token_logprob(logits, next_tok)
        new_cache = dict(out.cache,
                         pos=jnp.where(active_mask, out.cache["pos"], pos0))
        return next_tok, margin, logprob, new_cache

    return step


def make_paged_engine_step(cfg: ModelConfig, use_pallas: bool = False,
                           kernel: str = "xla", plan=None):
    """Fused slot-batched decode against the shared page pool.

    step(params, cache, tokens, pos, block_table, reset_mask,
         copy_src, copy_dst, sampling) -> (next_tok, margin, logprob, cache)

    kernel: how decode attention reads AND writes the pool — "xla"
    gathers each lane's logical ring and scatters the new K/V rows with
    `.at[].set`; "pallas" streams page tiles through the block table
    inside kernels/paged_attention with the new rows' scatter fused
    into the same kernel pass (in-place pool aliasing — no separate
    scatter op in the forward).  One fused dispatch either way; the XLA
    path is the default and the equivalence oracle.  The CoW copy below
    runs BEFORE the forward, so an in-kernel write always lands on the
    branch's private page.

    cache: a paged pool cache (kvcache.init_paged_cache) — attention K/V in
    shared (n_pages, KV, page_size, hd) pools, hybrid recurrent state in
    dense per-slot lanes.  pos: (n_slots,) int32, HOST-tracked (the
    scheduler knows each slot's fed count, so refill and prefix jump-start
    are host integer writes — idle lanes stay pinned by construction).
    block_table: (n_slots, P) int32 page ids; idle lanes point at the null
    page 0, so their dead-lane scatter never touches a live page.
    reset_mask: (n_slots,) bool — zeroes refilled slots' dense recurrent
    lanes; pool pages are never zeroed (stale entries are masked by
    position validity).  copy_src / copy_dst: (n_slots,) int32 page ids —
    copy-on-write pairs resolved host-side by the allocator (a branch
    about to write into a refcount-shared page): page dst becomes a copy
    of page src INSIDE this dispatch, before the token scatter that lands
    on it; rows with dst == 0 are no-ops and a whole-batch cond skips the
    copy compute on fork-free ticks.  sampling: per-slot SlotSampling,
    fused exactly as in make_engine_step."""

    def step(params, cache, tokens, pos, block_table, reset_mask,
             copy_src, copy_dst, sampling):
        cache = reset_paged_slots(cfg, cache, reset_mask)
        cache = cow_copy_pages(cfg, cache, copy_src, copy_dst)
        if plan is not None:
            cache = plan.constrain_paged_cache(cache)
        full = dict(cache, pos=pos, block_table=block_table)
        out = T.forward(params, cfg, tokens, cache=full,
                        use_pallas=use_pallas, paged_kernel=kernel,
                        shard=plan)
        logits = out.logits[:, -1]
        if plan is not None:
            logits = plan.rep(logits)
        scores, cut = batched_sample(logits, sampling)
        if plan is not None:
            scores = plan.rep(scores)
        next_tok, margin = argmax_with_margin(scores, cut)
        logprob = token_logprob(logits, next_tok)
        new_cache = {k: v for k, v in out.cache.items() if k != "pos"}
        return next_tok, margin, logprob, new_cache

    return step


def make_slot_prefill_step(cfg: ModelConfig, use_pallas: bool = False,
                           plan=None):
    """Chunked prefill into one slot of a stacked pool cache.

    step(params, cache, slot, tokens, reset, row)
        -> (next_tok, margin, logprob, cache)

    tokens: (1, S) int32 — a block of prompt tokens written into slot
    `slot`'s cache lanes in ONE device call (instead of S decode steps).
    reset: traced bool — zero the slot's lanes first (set on the first block
    of a request).  row: a scalar-leaf SlotSampling for this slot — the
    block's last-position logits are sampled (or argmaxed at temperature 0)
    inside the same dispatch; next_tok is the first generated token when
    the block ends the prompt, margin its top1-top2 score gap."""

    def step(params, cache, slot, tokens, reset, row):
        sub = slot_slice(cfg, cache, slot)
        sub = jax.tree.map(
            lambda a: jnp.where(reset, jnp.zeros((), a.dtype), a), sub)
        out = T.forward(params, cfg, tokens, cache=sub,
                        use_pallas=use_pallas, shard=plan)
        cache = slot_update(cfg, cache, slot, out.cache)
        if plan is not None:
            cache = plan.constrain_dense_cache(cache)
        logits = out.logits[0, -1]
        if plan is not None:
            logits = plan.rep(logits)
        scores, cut = row_sample(logits, row)
        if plan is not None:
            scores = plan.rep(scores)
        tok, margin = argmax_with_margin(scores[None], cut[None])
        logprob = token_logprob(logits[None], tok)
        return tok[0], margin[0], logprob[0], cache

    return step


def make_paged_prefill_step(cfg: ModelConfig, use_pallas: bool = False,
                            kernel: str = "xla", plan=None):
    """Chunked prefill of one slot against the shared page pool.

    step(params, cache, slot, tokens, pos0, bt_row, reset, row)
        -> (next_tok, margin, logprob, cache)

    tokens: (1, S) int32 prompt block, written at positions pos0..pos0+S-1
    through `bt_row` ((1, P) block-table row) into the pool.  pos0 > 0 on
    the first block resumes behind a refcount-shared prompt prefix whose
    pages an earlier request already wrote.  kernel="pallas" runs the
    whole S-token block through the paged-attention kernel (S>1 query
    block, write fused) instead of the XLA scatter+gather — so chunked
    prefill and preemption resume-recompute take the same code path the
    decode tick does.  reset: traced bool — zero the
    slot's dense recurrent lanes (hybrid) on a request's first block; pool
    pages need no zeroing.  row: scalar-leaf SlotSampling, as in
    make_slot_prefill_step."""

    def step(params, cache, slot, tokens, pos0, bt_row, reset, row):
        sub = paged_slot_slice(cfg, cache, slot)
        sub = reset_paged_sub(cfg, sub, reset)
        full = dict(sub, pos=pos0, block_table=bt_row)
        out = T.forward(params, cfg, tokens, cache=full,
                        use_pallas=use_pallas, paged_kernel=kernel,
                        shard=plan)
        new = {k: v for k, v in out.cache.items() if k != "pos"}
        cache = paged_slot_update(cfg, cache, slot, new)
        if plan is not None:
            cache = plan.constrain_paged_cache(cache)
        logits = out.logits[0, -1]
        if plan is not None:
            logits = plan.rep(logits)
        scores, cut = row_sample(logits, row)
        if plan is not None:
            scores = plan.rep(scores)
        tok, margin = argmax_with_margin(scores[None], cut[None])
        logprob = token_logprob(logits[None], tok)
        return tok[0], margin[0], logprob[0], cache

    return step


def greedy_generate(cfg: ModelConfig, params, cache, first_tokens,
                    n_steps: int, use_pallas: bool = False,
                    sampling: SamplingParams | None = None):
    """Decode loop (lax.scan over steps).  first_tokens: (B, 1[,C]).

    Greedy by default; pass `sampling` with temperature > 0 for stochastic
    decode — Gumbel-max sampling runs inside the scan body (still one
    compiled program), keyed by sampling.seed, the batch row, and the step
    index, so a rerun with the same seed reproduces the same tokens."""
    serve = make_serve_step(cfg, use_pallas)
    sample = sampling is not None and sampling.temperature > 0
    base_key = jax.random.PRNGKey(sampling.seed) if sample else None

    def body(carry, i):
        cache, toks = carry
        logits, cache = serve(params, cache, toks)
        if sample:
            logits = lockstep_scores(logits, base_key, i, sampling)
        nxt = jnp.argmax(logits, axis=-1)  # (B,) or (B, C)
        toks = nxt[:, None] if nxt.ndim == 1 else nxt[:, None, :]
        return (cache, toks.astype(jnp.int32)), nxt

    (_, _), toks = jax.lax.scan(body, (cache, first_tokens),
                                jnp.arange(n_steps))
    return jnp.moveaxis(toks, 0, 1)  # (B, n_steps[, C])
