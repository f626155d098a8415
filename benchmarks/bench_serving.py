"""Serving-engine bench: fused slot-batched decode vs the seed per-slot
loop at n_slots in {1, 4, 8, 16}, the paged KV pool vs the dense cache
layout on a skewed prompt-length mix, the Pallas paged-attention decode
kernel vs the XLA ring gather on that same mix, sampled
(temperature=0.8 / top_k=40) vs greedy decode on the same prompts and
slots, lazy page allocation (+ preemption) vs worst-case reservation
on an overloaded pool, best_of=n CoW-forked decoding (one prompt
prefill shared by every branch) vs n independent branch-keyed requests,
the Pallas kernel ladder (serving_pallas_ladder: fused in-kernel
K/V scatter, multi-page tiles, S>1 chunked-prefill blocks — greedy,
sampled, and direct-kernel equivalence vs the XLA path and ref.py),
the replica router (serving_router_migration: two heterogeneous
replicas behind one queue, mid-flight recompute-recipe migration +
a fail_replica drain drill, token parity vs the unrouted run, the
recipe-vs-KV-page byte ledger, and a Perfetto span-trace export to
TRACE_router_migration.json — the nightly artifact), and the telemetry
layer itself (serving_telemetry_overhead: the same fused workload with
a live Telemetry sink vs telemetry=None — token parity, tok/s overhead
ratio, span count, and 1.00 dispatch/tick with tracing on).

Reports decode tokens/sec, jitted device dispatches per engine tick (the
fused engine issues exactly ONE decode dispatch per tick — greedy OR
sampled, on both layouts — independent of n_slots; the seed loop issues
one per active slot), the fused/seed speedup, decode-state bytes (the
paged pool holds only the pages the mix actually touches; the dense
layout pays worst-case capacity on every slot), and — on the overload
mix — mean slot occupancy plus the preemption count.  CI gates on every
fused `*disp_per_tick` field staying <= 1.00, on lazy occupancy
exceeding worst-case occupancy, on the router row's migration
parity / failover completion / recipe-vs-KV byte ratio, and on the
telemetry row's parity / overhead / span presence
(benchmarks/check_serving.py).

    PYTHONPATH=src python -m benchmarks.run --only serving
    PYTHONPATH=src python benchmarks/bench_serving.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np


def _workload(vocab, n_requests, seed=0, max_new=(8, 16)):
    from repro.serving.scheduler import Request

    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(1, vocab,
                                        rng.integers(2, 12)).tolist(),
                    max_new=int(rng.integers(*max_new)))
            for i in range(n_requests)]


def _skewed_workload(vocab, n_requests, seed=0, long_every=4,
                     long_len=100, max_new=(4, 10)):
    """Mostly-short prompts with a rare long one: the mix the paged pool
    is provisioned for (dense must size every slot for the long case)."""
    from repro.serving.scheduler import Request

    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        plen = long_len if i % long_every == 0 else int(rng.integers(2, 10))
        reqs.append(Request(rid=i,
                            prompt=rng.integers(1, vocab, plen).tolist(),
                            max_new=int(rng.integers(*max_new))))
    return reqs


def _drive(eng, reqs):
    """Run a workload to completion; returns (completions, decode tokens,
    wall seconds, decode ticks, decode dispatches)."""
    d0 = eng.decode_dispatches
    eng.submit(reqs)
    start = time.time()
    done, steps = eng.run()
    wall = time.time() - start
    toks = sum(len(c.tokens) for c in done)
    return done, toks, wall, steps, eng.decode_dispatches - d0


def _clone(reqs):
    from repro.serving.scheduler import Request

    return [Request(r.rid, list(r.prompt), r.max_new, r.sampling)
            for r in reqs]


def _sampled(reqs, temperature=0.8, top_k=40):
    """The same workload decoded stochastically, one seed per request."""
    from repro.serving.sampling import SamplingParams
    from repro.serving.scheduler import Request

    return [Request(r.rid, list(r.prompt), r.max_new,
                    SamplingParams(temperature=temperature, top_k=top_k,
                                   seed=1000 + r.rid))
            for r in reqs]


def run(quick: bool = False):
    from repro.configs import get_smoke_config
    from repro.models import params as Pm
    from repro.serving.kvcache import paged_attn_layout
    from repro.serving.scheduler import (ContinuousBatcher, PerSlotBatcher,
                                         Request, completions_equivalent)

    cfg = get_smoke_config("qwen3_0_6b")
    params, _ = Pm.init_params(jax.random.PRNGKey(0), cfg)
    n_requests = 8 if quick else 24
    slot_counts = (1, 4) if quick else (1, 4, 8, 16)

    rows = []
    for n_slots in slot_counts:
        fused = ContinuousBatcher(cfg, params, n_slots=n_slots, capacity=64)
        seed = PerSlotBatcher(cfg, params, n_slots=n_slots, capacity=64)
        # warmup: compile every shape the measured run can dispatch — the
        # 15-token prompt covers all power-of-two prefill blocks (8+4+2+1)
        warm = (_workload(cfg.vocab_size, max(2, n_slots), seed=99)
                + [Request(rid=-1, prompt=list(range(1, 16)), max_new=2)])
        for eng in (fused, seed):
            _drive(eng, _clone(warm))

        f_done, f_tok, f_s, f_ticks, f_disp = _drive(
            fused, _workload(cfg.vocab_size, n_requests))
        s_done, s_tok, s_s, s_ticks, s_disp = _drive(
            seed, _workload(cfg.vocab_size, n_requests))
        equiv = completions_equivalent(f_done, s_done)

        f_tps, s_tps = f_tok / f_s, s_tok / s_s
        rows.append((
            f"serving_fused_vs_perslot_s{n_slots}",
            f_s / max(1, f_tok) * 1e6,
            f"slots={n_slots};tok={f_tok};equiv={equiv}"
            f";fused_tok_s={f_tps:.1f};perslot_tok_s={s_tps:.1f}"
            f";speedup={f_tps / s_tps:.2f}x"
            f";fused_disp_per_tick={f_disp / max(1, f_ticks):.4f}"
            f";perslot_disp_per_tick={s_disp / max(1, s_ticks):.2f}"
            f";fused_prefill_disp={fused.prefill_dispatches}"))

    # ---- paged pool vs dense layout on a skewed prompt-length mix.
    # capacity provisions the rare long request; the paged pool is sized
    # to what the mix concurrently touches (~1/4 of full provisioning).
    n_slots, capacity = (4, 128) if quick else (8, 128)
    pages_per_slot, _ = paged_attn_layout(cfg, capacity)
    n_pages = 1 + n_slots * pages_per_slot // 4
    n_skew = 8 if quick else 16
    dense = ContinuousBatcher(cfg, params, n_slots=n_slots,
                              capacity=capacity)
    paged = ContinuousBatcher(cfg, params, n_slots=n_slots,
                              capacity=capacity, cache_layout="paged",
                              n_pages=n_pages)
    warm = _skewed_workload(cfg.vocab_size, max(4, n_slots), seed=99)
    for eng in (dense, paged):
        _drive(eng, _clone(warm))
    mix = _skewed_workload(cfg.vocab_size, n_skew)
    d_done, d_tok, d_s, d_ticks, d_disp = _drive(dense, _clone(mix))
    p_done, p_tok, p_s, p_ticks, p_disp = _drive(paged, _clone(mix))
    equiv = completions_equivalent(p_done, d_done)
    d_bytes, p_bytes = dense.cache_nbytes(), paged.cache_nbytes()
    rows.append((
        "serving_paged_vs_dense_skewed",
        p_s / max(1, p_tok) * 1e6,
        f"slots={n_slots};tok={p_tok};equiv={equiv}"
        f";paged_tok_s={p_tok / p_s:.1f};dense_tok_s={d_tok / d_s:.1f}"
        f";paged_disp_per_tick={p_disp / max(1, p_ticks):.4f}"
        f";dense_disp_per_tick={d_disp / max(1, d_ticks):.4f}"
        f";paged_cache_bytes={p_bytes};dense_cache_bytes={d_bytes}"
        f";bytes_ratio={p_bytes / d_bytes:.3f}"
        f";pages={n_pages};page_size={paged.page_size}"
        f";peak_pages_in_use={paged.allocator.peak_in_use}"))

    # ---- Pallas paged-attention decode kernel vs the XLA ring gather on
    # the same skewed mix.  kernel="pallas" streams page tiles through the
    # block table inside the fused dispatch; off-TPU the kernel runs in
    # interpret mode, so CPU tokens/sec is a correctness/trajectory trace,
    # not a speed claim (the backend field says which reading applies).
    pallas_eng = ContinuousBatcher(cfg, params, n_slots=n_slots,
                                   capacity=capacity, cache_layout="paged",
                                   n_pages=n_pages, kernel="pallas")
    _drive(pallas_eng, _clone(warm))
    k_done, k_tok, k_s, k_ticks, k_disp = _drive(pallas_eng, _clone(mix))
    kequiv = completions_equivalent(k_done, p_done)
    k_tps, x_tps = k_tok / k_s, p_tok / p_s
    rows.append((
        "serving_paged_pallas_vs_xla",
        k_s / max(1, k_tok) * 1e6,
        f"slots={n_slots};tok={k_tok};equiv={kequiv}"
        f";pallas_tok_s={k_tps:.1f};xla_tok_s={x_tps:.1f}"
        f";pallas_over_xla={k_tps / x_tps:.2f}x"
        f";pallas_disp_per_tick={k_disp / max(1, k_ticks):.4f}"
        f";backend={jax.default_backend()}"))

    # ---- sampled decode (temperature=0.8, top_k=40) vs greedy on the same
    # prompts and slots: sampling rides inside the fused dispatch, so both
    # layouts must hold 1.00 decode dispatch/tick (CI gates on this), and
    # per-request seeds make dense and paged token-for-token reproducible.
    n_slots = 4 if quick else 8
    greedy_eng = ContinuousBatcher(cfg, params, n_slots=n_slots, capacity=64)
    s_dense = ContinuousBatcher(cfg, params, n_slots=n_slots, capacity=64)
    s_paged = ContinuousBatcher(cfg, params, n_slots=n_slots, capacity=64,
                                cache_layout="paged")
    base = _workload(cfg.vocab_size, n_requests)
    warm = (_workload(cfg.vocab_size, max(2, n_slots), seed=99)
            + [Request(rid=-1, prompt=list(range(1, 16)), max_new=2)])
    for eng in (greedy_eng, s_dense, s_paged):
        _drive(eng, _clone(warm))
    g_done, g_tok, g_s, _, _ = _drive(greedy_eng, _clone(base))
    d_done, d_tok, d_s, d_ticks, d_disp = _drive(s_dense, _sampled(base))
    p_done, p_tok, p_s, p_ticks, p_disp = _drive(s_paged, _sampled(base))
    # equivalence with the repo-wide tie tolerance (the engines compile
    # different programs); exact dict equality reported alongside
    repro = completions_equivalent(d_done, p_done)
    exact = ({c.rid: c.tokens for c in d_done}
             == {c.rid: c.tokens for c in p_done})
    g_tps, s_tps = g_tok / g_s, d_tok / d_s
    rows.append((
        "serving_sampled_vs_greedy",
        d_s / max(1, d_tok) * 1e6,
        f"slots={n_slots};tok={d_tok};temp=0.8;top_k=40"
        f";greedy_tok_s={g_tps:.1f};sampled_tok_s={s_tps:.1f}"
        f";sampled_over_greedy={s_tps / g_tps:.2f}x"
        f";sampled_dense_disp_per_tick={d_disp / max(1, d_ticks):.4f}"
        f";sampled_paged_disp_per_tick={p_disp / max(1, p_ticks):.4f}"
        f";sampled_equiv={repro};dense_paged_token_identical={exact}"))

    # ---- request lifecycle under overload: lazy page allocation (admit
    # on prompt pages, grow at page boundaries, preempt + resume on
    # exhaustion) vs worst-case reservation, on a skewed prompt mix over
    # a pool whose worst-case budget can only run ~half the requests
    # concurrently.  Lazy must buy strictly higher mean slot occupancy
    # (CI gates this) while staying token-equivalent and fused; it also
    # drains the mix in fewer engine ticks (lazy_ticks vs
    # worstcase_ticks).  CPU tok/s UNDERSTATES lazy: every resume pays a
    # recompute prefill whose small-block dispatches are host-roundtrip
    # bound here, while the concurrency it buys back is what matters on a
    # real accelerator — occupancy, not smoke-model wall clock, is the
    # gated claim.
    n_slots = 4 if quick else 8
    n_over = 8 if quick else 16

    def _overload_mix(seed=0):
        rng = np.random.default_rng(seed)
        reqs = []
        for i in range(n_over):
            plen = 20 if i % 4 == 0 else int(rng.integers(3, 8))
            reqs.append(Request(
                rid=i, prompt=rng.integers(1, cfg.vocab_size, plen).tolist(),
                max_new=24))
        return reqs

    # worst-case budget of the mix, sized so reservation-at-admission can
    # only keep ~half the slot pool busy
    mix = _overload_mix()
    ps = 16
    worst = [-(-min(len(r.prompt) + r.max_new, 64) // ps) for r in mix]
    n_pages = 1 + (n_slots // 2) * max(1, round(sum(worst) / len(worst)))
    lazy_eng = ContinuousBatcher(cfg, params, n_slots=n_slots, capacity=64,
                                 cache_layout="paged", n_pages=n_pages,
                                 allocation="lazy")
    wc_eng = ContinuousBatcher(cfg, params, n_slots=n_slots, capacity=64,
                               cache_layout="paged", n_pages=n_pages,
                               allocation="worst_case")
    warm = _overload_mix(seed=99)[:max(4, n_slots)]
    for eng in (lazy_eng, wc_eng):
        _drive(eng, _clone(warm))
        eng.decode_ticks = eng.decode_active_slots = 0
        eng.preemptions = 0
    l_done, l_tok, l_s, l_ticks, l_disp = _drive(lazy_eng, _clone(mix))
    w_done, w_tok, w_s, w_ticks, w_disp = _drive(wc_eng, _clone(mix))
    equiv = completions_equivalent(l_done, w_done)
    rows.append((
        "serving_lazy_vs_worstcase_overload",
        l_s / max(1, l_tok) * 1e6,
        f"slots={n_slots};tok={l_tok};equiv={equiv}"
        f";lazy_tok_s={l_tok / l_s:.1f};worstcase_tok_s={w_tok / w_s:.1f}"
        f";lazy_occupancy={lazy_eng.mean_occupancy():.3f}"
        f";worstcase_occupancy={wc_eng.mean_occupancy():.3f}"
        f";preemptions={lazy_eng.preemptions}"
        f";lazy_disp_per_tick={l_disp / max(1, l_ticks):.4f}"
        f";worstcase_disp_per_tick={w_disp / max(1, w_ticks):.4f}"
        f";pages={n_pages};lazy_ticks={l_ticks};worstcase_ticks={w_ticks}"))

    # ---- best-of-n CoW fork: ONE prompt prefill fans out n branches
    # whose block tables share every prompt page (a branch writing a
    # shared page copies it inside the fused tick), vs n independent
    # branch-keyed requests that each pay their own prefill.  CI gates
    # fork_equiv == True (branch b of the forked run token-identical to
    # an independent SamplingParams(seed, branch=b) request) and
    # shared_pages > 0; fork_disp_per_tick rides the repo-wide <= 1.00
    # fused-dispatch gate.
    import dataclasses

    from repro.serving.sampling import SamplingParams

    n_best = 4
    n_slots = 4 if quick else 8
    prompt = list(range(3, 27))  # 24 tokens: one full shared page + tail
    max_new = 8 if quick else 12
    sp = SamplingParams(temperature=0.9, top_k=40, seed=17)
    fork_eng = ContinuousBatcher(cfg, params, n_slots=n_slots, capacity=64,
                                 cache_layout="paged")
    solo_eng = ContinuousBatcher(cfg, params, n_slots=n_slots, capacity=64,
                                 cache_layout="paged", share_prefix=False)
    warm_prompt = list(range(60, 84))  # same shapes, different tokens
    fork_eng.submit([Request(rid=-1, prompt=list(warm_prompt), max_new=2,
                             sampling=sp, best_of=n_best)])
    fork_eng.run()
    solo_eng.submit([Request(rid=-(b + 1), prompt=list(warm_prompt),
                             max_new=2,
                             sampling=dataclasses.replace(sp, branch=b))
                     for b in range(n_best)])
    solo_eng.run()

    fp0, sp0 = fork_eng.prefill_dispatches, solo_eng.prefill_dispatches
    fd0 = fork_eng.decode_dispatches
    fs0, cw0 = fork_eng.fork_shared_pages, fork_eng.cow_copies
    fork_eng.submit([Request(rid=0, prompt=list(prompt), max_new=max_new,
                             sampling=sp, best_of=n_best)])
    start = time.time()
    _, f_ticks = fork_eng.run()
    f_s = time.time() - start
    branches = fork_eng.group_results[0]

    solo_eng.submit([Request(rid=b, prompt=list(prompt), max_new=max_new,
                             sampling=dataclasses.replace(sp, branch=b))
                     for b in range(n_best)])
    start = time.time()
    s_done, _ = solo_eng.run()
    s_s = time.time() - start
    want = {c.rid: c for c in s_done}
    fork_equiv = all(
        completions_equivalent([dataclasses.replace(branches[b], rid=0)],
                               [dataclasses.replace(want[b], rid=0)])
        for b in range(n_best))
    f_tok = sum(len(c.tokens) for c in branches.values())
    s_tok = sum(len(c.tokens) for c in s_done)
    rows.append((
        "serving_best_of_fork",
        f_s / max(1, f_tok) * 1e6,
        f"slots={n_slots};best_of={n_best};tok={f_tok}"
        f";fork_equiv={fork_equiv}"
        f";shared_pages={fork_eng.fork_shared_pages - fs0}"
        f";cow_copies={fork_eng.cow_copies - cw0}"
        f";fork_disp_per_tick="
        f"{(fork_eng.decode_dispatches - fd0) / max(1, f_ticks):.4f}"
        f";fork_tok_s={f_tok / f_s:.1f};solo_tok_s={s_tok / s_s:.1f}"
        f";fork_prefill_disp={fork_eng.prefill_dispatches - fp0}"
        f";solo_prefill_disp={solo_eng.prefill_dispatches - sp0}"))

    # ---- Pallas paged-attention v2 ladder: one gated row per rung.
    # Rung 1 (fused scatter): pallas decode issues NO separate XLA pool
    # scatter — token parity with the XLA path on the skewed mix, greedy
    # and sampled, at 1.00 decode dispatch/tick.  Rung 2 (multi-page
    # tiles): direct kernel timing tile_k=4 vs tile_k=1 on the same page
    # geometry, equivalence vs ref.reference_paged_attention.  Rung 3
    # (S>1 blocks): chunked prefill runs through the kernel — pallas
    # chunked prefill vs XLA chunked prefill token parity.  Off-TPU the
    # kernel interprets, so tok/s ratios are trajectory traces; the gated
    # fields are the equivalence flags and disp/tick (check_serving.py).
    from repro.kernels.paged_attention import ops as pa_ops, ref as pa_ref

    n_slots, capacity = (4, 128) if quick else (8, 128)
    pages_per_slot, _ = paged_attn_layout(cfg, capacity)
    n_pages = 1 + n_slots * pages_per_slot // 4
    lad_kw = dict(n_slots=n_slots, capacity=capacity, cache_layout="paged",
                  n_pages=n_pages, prefill_mode="chunked")
    lx = ContinuousBatcher(cfg, params, kernel="xla", **lad_kw)
    lp = ContinuousBatcher(cfg, params, kernel="pallas", **lad_kw)
    warm = _skewed_workload(cfg.vocab_size, max(4, n_slots), seed=99)
    for eng in (lx, lp):
        _drive(eng, _clone(warm))
    mix = _skewed_workload(cfg.vocab_size, n_skew)
    x_done, x_tok, x_s, _, _ = _drive(lx, _clone(mix))
    p_done, p_tok, p_s, p_ticks, p_disp = _drive(lp, _clone(mix))
    greedy_equiv = completions_equivalent(p_done, x_done)
    sx_done, _, _, _, _ = _drive(lx, _sampled(_clone(mix)))
    sp_done, _, _, _, _ = _drive(lp, _sampled(_clone(mix)))
    sampled_equiv = completions_equivalent(sp_done, sx_done)

    # rung 2: direct kernel point — tile_k sweep on the engine's page
    # geometry, checked against the jnp ring-gather oracle
    psz = lp.page_size
    P, B, KV = 4, 4, cfg.n_kv_heads
    hd, H = cfg.head_dim, cfg.n_heads
    kp = 1 + B * P
    rng = np.random.default_rng(5)
    import jax.numpy as jnp
    qk = jax.random.normal(jax.random.PRNGKey(5), (B, 1, H, hd))
    kpool = jax.random.normal(jax.random.PRNGKey(6), (kp, KV, psz, hd))
    vpool = jax.random.normal(jax.random.PRNGKey(7), (kp, KV, psz, hd))
    bt = jnp.asarray(rng.permutation(np.arange(1, kp)).reshape(B, P),
                     jnp.int32)
    last = jnp.asarray(rng.integers(psz, P * psz, B), jnp.int32)
    want = pa_ref.reference_paged_attention(qk[:, 0], kpool, vpool, bt, last)
    tile_us = {}
    for tk in (1, 4):
        fn = lambda: pa_ops.paged_attention(qk, kpool, vpool, bt, last,
                                            tile_k=tk)
        jax.block_until_ready(fn())  # compile
        t0 = time.time()
        for _ in range(3):
            out = fn()
        jax.block_until_ready(out)
        tile_us[tk] = (time.time() - t0) / 3 * 1e6
    kernel_err = float(jnp.max(jnp.abs(out[:, 0] - want)))
    kernel_equiv = kernel_err < 2e-3

    rows.append((
        "serving_pallas_ladder",
        p_s / max(1, p_tok) * 1e6,
        f"slots={n_slots};tok={p_tok};greedy_equiv={greedy_equiv}"
        f";sampled_equiv={sampled_equiv};kernel_ref_equiv={kernel_equiv}"
        f";kernel_ref_max_err={kernel_err:.1e}"
        f";pallas_tok_s={p_tok / p_s:.1f};xla_tok_s={x_tok / x_s:.1f}"
        f";pallas_over_xla={(p_tok / p_s) / (x_tok / x_s):.2f}x"
        f";tile4_over_tile1={tile_us[1] / tile_us[4]:.2f}x"
        f";pallas_disp_per_tick={p_disp / max(1, p_ticks):.4f}"
        f";prefill=chunked;backend={jax.default_backend()}"))

    # ---- replica router: two heterogeneous replicas (a small lazy paged
    # pool and a bigger dense one) behind one queue.  Drill 1 migrates
    # two mid-flight requests (one greedy, one sampled) to the other
    # replica by recompute recipe; drill 2 kills whichever replica holds
    # a mid-flight request (fail_replica) and drains it onto the
    # survivor.  Gated: migration_equiv (every stream token-identical to
    # the unrouted same-seed run), failover_ok (100% completion),
    # recipe_kv_ratio < 0.05 (recipes vs the counterfactual KV-page
    # transfer), ttft_p95_ms presence, and router_disp_per_tick <= 1.00
    # (each replica stays fused).  CPU wall clock includes per-replica
    # compile; latency percentiles are a presence check, not a threshold.
    import asyncio

    from repro.serving.config import ServingConfig
    from repro.serving.router import ReplicaRouter
    from repro.serving.telemetry import Telemetry

    n_rt = 8 if quick else 16
    rt_mix = _skewed_workload(cfg.vocab_size, n_rt, long_every=4,
                              long_len=40, max_new=(6, 12))

    def _rt_sampling(i):
        return (SamplingParams(temperature=0.8, top_k=40, seed=1000 + i)
                if i % 2 else None)

    base_reqs = [dataclasses.replace(r, sampling=_rt_sampling(r.rid))
                 for r in rt_mix]
    base_eng = ContinuousBatcher(cfg, params,
                                 ServingConfig(n_slots=4, capacity=96))
    base_done, _, _, _, _ = _drive(base_eng, _clone(base_reqs))

    async def _router_run():
        # per-replica telemetry: the drill's span log becomes the nightly
        # Perfetto trace artifact (TRACE_router_migration.json)
        configs = [ServingConfig(n_slots=2, capacity=96,
                                 cache_layout="paged", n_pages=9,
                                 allocation="lazy", telemetry=Telemetry()),
                   ServingConfig(n_slots=4, capacity=96,
                                 telemetry=Telemetry())]
        async with ReplicaRouter(cfg, params, configs) as router:
            t0 = time.time()
            handles = [await router.submit(list(r.prompt), r.max_new,
                                           sampling=r.sampling)
                       for r in base_reqs]
            for h in handles[:2]:  # drill 1: rid 0 greedy, rid 1 sampled
                while h._delivered < 2 and not h.done():
                    await asyncio.sleep(0)
                if not h.done():
                    await router.migrate(h.rid, 1 - h.replica)
            victim = None  # drill 2: kill a replica holding live work
            while victim is None and not all(h.done() for h in handles):
                for h in handles:
                    if (not h.done() and h.replica is not None
                            and h._delivered >= 1):
                        victim = h.replica
                        break
                else:
                    await asyncio.sleep(0)
            drained = await router.fail_replica(victim) \
                if victim is not None else 0
            results, errs = [], 0
            for h in handles:
                try:
                    results.append(await h.result())
                except Exception:
                    errs += 1
            return results, errs, drained, router, time.time() - t0

    results, errs, drained, router, rt_wall = asyncio.run(_router_run())
    trace = router.export_trace("TRACE_router_migration.json")
    ov = router.router_overhead_bytes()
    st = router.stats()
    rt_tok = sum(len(c.tokens) for c in results)
    mig_equiv = errs == 0 and completions_equivalent(results, base_done)
    failover_ok = errs == 0 and len(results) == n_rt
    rt_disp = max(
        rep.batcher.decode_dispatches / max(1, rep.batcher.decode_ticks)
        for rep in router.replicas)
    rows.append((
        "serving_router_migration",
        rt_wall / max(1, rt_tok) * 1e6,
        f"replicas=2;tok={rt_tok};migration_equiv={mig_equiv}"
        f";migrations={ov['migrations']};failovers={ov['failovers']}"
        f";failover_drained={drained};failover_ok={failover_ok}"
        f";recipe_bytes={ov['recipe_bytes']}"
        f";kv_page_bytes={ov['kv_page_bytes']}"
        f";recipe_kv_ratio={ov['ratio_vs_kv']:.4f}"
        f";ttft_p95_ms={st['ttft_p95_ms']:.1f}"
        f";tpot_p95_ms={st['tpot_p95_ms']:.2f}"
        f";router_disp_per_tick={rt_disp:.4f}"
        f";trace_events={len(trace['traceEvents'])}"))

    # ---- telemetry overhead: the identical fused workload with a live
    # Telemetry sink (lifecycle spans + tick metrics + dispatch
    # annotations) vs telemetry=None (every hot-path call site guarded
    # out).  Gated (check_serving.py): telemetry_equiv True — the traced
    # run token-identical to the untraced one; overhead_ratio <= 1.05 —
    # tok/s with telemetry on within 5% of off; spans > 0 — the sink
    # actually recorded the lifecycle; telemetry_on_disp_per_tick rides
    # the repo-wide <= 1.00 gate (tracing must never add a dispatch).
    # Each arm keeps the faster of two reps to damp wall-clock noise.
    n_slots = 4 if quick else 8
    tel = Telemetry()
    off_eng = ContinuousBatcher(cfg, params,
                                ServingConfig(n_slots=n_slots, capacity=64))
    on_eng = ContinuousBatcher(cfg, params,
                               ServingConfig(n_slots=n_slots, capacity=64,
                                             telemetry=tel))
    base = _workload(cfg.vocab_size, n_requests)
    warm = (_workload(cfg.vocab_size, max(2, n_slots), seed=99)
            + [Request(rid=-1, prompt=list(range(1, 16)), max_new=2)])
    for eng in (off_eng, on_eng):
        _drive(eng, _clone(warm))
    best = {}
    for key, eng in (("off", off_eng), ("on", on_eng)):
        for _ in range(2):
            done, tok, s, ticks, disp = _drive(eng, _clone(base))
            if key not in best or tok / s > best[key][1]:
                best[key] = (done, tok / s, ticks, disp)
    off_done, off_tps, _, _ = best["off"]
    on_done, on_tps, on_ticks, on_disp = best["on"]
    tel_equiv = ({c.rid: c.tokens for c in on_done}
                 == {c.rid: c.tokens for c in off_done})
    snap = tel.snapshot()
    rows.append((
        "serving_telemetry_overhead",
        1e6 / max(1e-9, on_tps),
        f"slots={n_slots};telemetry_equiv={tel_equiv}"
        f";telemetry_on_tok_s={on_tps:.1f}"
        f";telemetry_off_tok_s={off_tps:.1f}"
        f";overhead_ratio={off_tps / on_tps:.3f}"
        f";spans={snap['span_events']};tel_ticks={snap['ticks']['count']}"
        f";telemetry_on_disp_per_tick={on_disp / max(1, on_ticks):.4f}"))

    rows.append(_sharded_row(quick))
    return rows


# ---- mesh-sharded serving vs the single-device engine on the (2, 2)
# debug mesh.  Runs in a SUBPROCESS with 8 forced host devices (the main
# bench process must keep the real device world); on CPU the placeholder
# devices time-share one core, so sharded tok/s is a correctness /
# dispatch-contract trace, not a speed claim — the gated fields are the
# equivalence flags and the per-mesh-tick dispatch count.

_SHARDED_SCRIPT = textwrap.dedent("""
    import json
    import os
    import time

    import jax
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models import params as Pm
    from repro.serving.sampling import SamplingParams
    from repro.serving.scheduler import (ContinuousBatcher, Request,
                                         completions_equivalent)

    assert len(jax.devices()) == 8
    quick = os.environ.get("SHARDED_QUICK") == "1"
    cfg = get_smoke_config("qwen3_0_6b")
    params, _ = Pm.init_params(jax.random.PRNGKey(0), cfg)
    n_slots = 4 if quick else 8
    n_requests = 8 if quick else 16

    def workload(seed=0):
        rng = np.random.default_rng(seed)
        reqs = []
        for i in range(n_requests):
            sp = (SamplingParams(temperature=0.8, top_k=40, seed=1000 + i)
                  if i % 2 else None)
            reqs.append(Request(
                rid=i, prompt=rng.integers(1, cfg.vocab_size,
                                           rng.integers(2, 12)).tolist(),
                max_new=int(rng.integers(8, 16)), sampling=sp))
        return reqs

    def drive(b, seed=0):
        d0 = b.decode_dispatches
        b.submit(workload(seed))
        start = time.time()
        done, ticks = b.run()
        wall = time.time() - start
        toks = sum(len(c.tokens) for c in done)
        return done, toks / wall, b.decode_dispatches - d0, ticks

    mesh = make_mesh((2, 2), ("data", "model"))
    out = {"slots": n_slots, "mesh": "2x2"}
    res = {}
    for name, layout, m in (("single", "dense", None),
                            ("sharded", "dense", mesh),
                            ("paged_single", "paged", None),
                            ("paged_sharded", "paged", mesh)):
        b = ContinuousBatcher(cfg, params, n_slots=n_slots, capacity=64,
                              cache_layout=layout, mesh=m)
        drive(b, seed=99)  # warm: compile every dispatch shape
        done, tps, disp, ticks = drive(b)
        res[name] = done
        out[f"{name}_tok_s"] = round(tps, 1)
        out[f"{name}_disp_per_tick"] = round(disp / max(1, ticks), 4)
        if m is not None:
            out[f"{name}_groups"] = b.n_slot_groups
            out[f"{name}_bytes_global"] = b.cache_nbytes()
            out[f"{name}_bytes_dev"] = b.cache_nbytes_per_device()
    out["sharded_equiv"] = completions_equivalent(res["single"],
                                                  res["sharded"])
    out["paged_sharded_equiv"] = completions_equivalent(
        res["paged_single"], res["paged_sharded"])
    print("JSON::" + json.dumps(out))
""")


def _sharded_row(quick: bool):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    # this row counts on 8 virtual CPU devices; the parent may already hold
    # the accelerator, so the child must not reach for it
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env["SHARDED_QUICK"] = "1" if quick else "0"
    proc = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError("sharded serving bench subprocess failed:\n"
                           + proc.stderr[-3000:])
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("JSON::")][-1]
    o = json.loads(line[len("JSON::"):])
    s_tps = o["sharded_tok_s"]
    return (
        "serving_sharded_vs_single",
        1e6 / max(1e-9, s_tps),
        f"mesh={o['mesh']};slots={o['slots']}"
        f";sharded_equiv={o['sharded_equiv']}"
        f";paged_sharded_equiv={o['paged_sharded_equiv']}"
        f";single_tok_s={o['single_tok_s']:.1f}"
        f";sharded_tok_s={s_tps:.1f}"
        f";paged_sharded_tok_s={o['paged_sharded_tok_s']:.1f}"
        f";sharded_disp_per_tick={o['sharded_disp_per_tick']:.4f}"
        f";paged_sharded_disp_per_tick="
        f"{o['paged_sharded_disp_per_tick']:.4f}"
        f";slot_groups={o['sharded_groups']}"
        f";sharded_cache_bytes_global={o['sharded_bytes_global']}"
        f";sharded_cache_bytes_per_device={o['sharded_bytes_dev']}"
        f";backend={jax.default_backend()}")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for name, us, derived in run():
        print(f"{name},{us:.0f},{derived}", flush=True)
