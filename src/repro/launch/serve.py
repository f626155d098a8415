"""Serving launcher: the async request-lifecycle frontend over the fused
continuous-batching engine — per-token streaming, priority classes,
deadlines, lazy page allocation with preemption, and optional stochastic
sampling (temperature / top-k / top-p, seeded).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_0_6b --smoke \
      --requests 6 --slots 4 --gen 24 --layout paged --allocation lazy \
      --pages 9 --temperature 0.8 --top-k 40 --stream

``--best-of N`` races N copy-on-write branches per request off a single
prefill (paged layout; sampled decode) and reports only each request's
winner by cumulative logprob — shared prompt pages are forked, never
copied, until a branch actually writes one.

Mesh-sharded serving: ``--mesh DxM`` runs the engine on a
(data=D, model=M) jax.sharding.Mesh — slots shard over "data", heads
over "model" (requires D*M visible devices; set
XLA_FLAGS=--xla_force_host_platform_device_count=N to debug on CPU).
``--kernel pallas`` selects the paged-attention decode kernel (single
device only; needs --layout paged).

``--replicas N`` fronts N independent replicas with a `ReplicaRouter`:
requests place by load/prefix-affinity score and migrate between
replicas as recompute recipes (never KV pages); the run reports the
per-link byte ledger and fleet-wide TTFT/TPOT percentiles.

Observability: ``--trace out.json`` attaches a telemetry sink to every
replica and writes the run's Chrome/Perfetto trace_event JSON (open in
ui.perfetto.dev or chrome://tracing — one process track per replica,
engine ticks on thread 0, one thread per request);
``--stats-interval S`` prints a one-line telemetry snapshot to stderr
every S seconds while the run is live.
"""
from __future__ import annotations

import argparse
import asyncio
import sys
import time

import jax
import numpy as np


def _parse_mesh(spec: str):
    """"DxM" -> a (data=D, model=M) mesh over the first D*M devices."""
    try:
        d, m = (int(x) for x in spec.lower().split("x"))
    except ValueError:
        raise SystemExit(f"--mesh {spec!r}: expected DxM, e.g. 2x2")
    need, have = d * m, len(jax.devices())
    if need > have:
        raise SystemExit(
            f"--mesh {spec} needs {need} devices but only {have} are "
            f"visible (set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={need} to debug on CPU)")
    from repro.launch.mesh import make_mesh

    return make_mesh((d, m), ("data", "model"))


def _serving_config(args, cfg, telemetry=None):
    from repro.serving import ServingConfig

    layout = args.layout
    if args.best_of > 1 and layout != "paged":
        print("--best-of > 1 forks shared KV pages: switching "
              "--layout paged")
        layout = "paged"
    if args.allocation == "lazy" and layout != "paged":
        print("--allocation lazy needs the paged pool: switching "
              "--layout paged")
        layout = "paged"
    if args.kernel == "pallas" and layout != "paged":
        raise SystemExit("--kernel pallas selects the paged-attention "
                         "decode kernel — pass --layout paged as well")
    mesh = _parse_mesh(args.mesh) if args.mesh else None
    if mesh is not None and args.kernel == "pallas":
        raise SystemExit("--kernel pallas is single-device — drop --mesh "
                         "or use the default --kernel xla")
    kw = {}
    if layout == "paged" and args.pages:
        kw["n_pages"] = args.pages
    return ServingConfig(
        n_slots=args.slots, capacity=args.capacity, cache_layout=layout,
        allocation=args.allocation, kernel=args.kernel, mesh=mesh,
        telemetry=telemetry, **kw)


def _wants_telemetry(args) -> bool:
    return bool(args.trace) or args.stats_interval is not None


def _stats_line(stats: dict) -> str:
    """One-line operational snapshot (the --stats-interval ticker)."""
    fmt = (lambda v, spec="{:.1f}": "-" if v is None else spec.format(v))
    pending = stats.get("pending", stats.get("open_requests", "-"))
    return (f"[stats] pending={pending} "
            f"completed={stats['completed']} "
            f"ttft_p50={fmt(stats['ttft_p50_ms'])}ms "
            f"ttft_p95={fmt(stats['ttft_p95_ms'])}ms "
            f"tpot_p50={fmt(stats['tpot_p50_ms'], '{:.2f}')}ms")


async def _stats_ticker(stats_fn, interval: float):
    while True:
        await asyncio.sleep(interval)
        print(_stats_line(stats_fn()), file=sys.stderr)


async def _serve_router(args, cfg, params):
    """--replicas N: one ReplicaRouter over N same-shaped replicas —
    load-scored placement, recipe migration, per-link byte ledger."""
    from repro.serving import ReplicaRouter, SamplingParams, Telemetry

    configs = [_serving_config(args, cfg,
                               telemetry=(Telemetry()
                                          if _wants_telemetry(args)
                                          else None))
               for _ in range(args.replicas)]
    rng = np.random.default_rng(args.seed)
    sampled = args.temperature > 0

    async with ReplicaRouter(cfg, params, configs,
                             max_pending=args.max_pending) as router:
        ticker = None
        if args.stats_interval is not None:
            ticker = asyncio.get_running_loop().create_task(
                _stats_ticker(router.stats, args.stats_interval))
        handles = []
        t0 = time.time()
        for i in range(args.requests):
            sp = SamplingParams(temperature=args.temperature,
                                top_k=args.top_k, top_p=args.top_p,
                                seed=args.seed + i) if sampled else None
            handles.append(await router.submit(
                rng.integers(1, cfg.vocab_size,
                             args.prompt_len).tolist(),
                args.gen, sampling=sp, priority=args.priority,
                deadline_ms=args.deadline_ms, best_of=args.best_of))
        completions = await asyncio.gather(*(h.result() for h in handles))
        wall = time.time() - t0
        stats = router.stats()
        if ticker is not None:
            ticker.cancel()
        if args.trace:
            trace = router.export_trace(args.trace)
            print(f"wrote {len(trace['traceEvents'])} trace events to "
                  f"{args.trace} (open in ui.perfetto.dev)")

    toks = sum(len(c.tokens) for c in completions)
    placed = [h.replica for h in handles]
    ov = stats["overhead"]
    print(f"arch={cfg.name} replicas={args.replicas} layout={args.layout} "
          f"slots={args.slots}x{args.replicas} requests={args.requests} "
          f"gen={args.gen}")
    print(f"{toks} tokens in {wall:.2f}s ({toks / wall:.1f} tok/s); "
          f"placement { {r: placed.count(r) for r in sorted(set(placed))} }")
    print(f"migrations={ov['migrations']} recipe_bytes={ov['recipe_bytes']} "
          f"vs kv_page_bytes={ov['kv_page_bytes']} "
          f"(gain {ov['gain_vs_kv']:.2%})")
    print(f"ttft p50/p95 = {stats['ttft_p50_ms']:.1f}/"
          f"{stats['ttft_p95_ms']:.1f} ms, tpot p50/p95 = "
          f"{stats['tpot_p50_ms']:.2f}/{stats['tpot_p95_ms']:.2f} ms")


async def _serve(args, cfg, params):
    from repro.serving import (ContinuousBatcher, SamplingParams,
                               ServingFrontend, Telemetry, write_trace)

    telemetry = Telemetry() if _wants_telemetry(args) else None
    batcher = ContinuousBatcher(cfg, params,
                                _serving_config(args, cfg, telemetry))

    rng = np.random.default_rng(args.seed)
    sampled = args.temperature > 0

    async with ServingFrontend(batcher,
                               max_pending=args.max_pending) as frontend:
        ticker = None
        if args.stats_interval is not None:
            ticker = asyncio.get_running_loop().create_task(
                _stats_ticker(frontend.stats, args.stats_interval))
        handles = []
        t0 = time.time()
        for i in range(args.requests):
            sp = SamplingParams(temperature=args.temperature,
                                top_k=args.top_k, top_p=args.top_p,
                                seed=args.seed + i) if sampled else None
            handles.append(await frontend.submit(
                rng.integers(1, cfg.vocab_size,
                             args.prompt_len).tolist(),
                args.gen, sampling=sp, priority=args.priority,
                deadline_ms=args.deadline_ms, best_of=args.best_of))

        async def consume(h):
            toks = []
            async for tok in h:
                toks.append(tok)
                if args.stream and h.rid == 0:
                    print(f"  [stream rid=0] token {len(toks):3d}: {tok}")
            return toks

        streams = await asyncio.gather(*(consume(h) for h in handles))
        completions = await asyncio.gather(*(h.result() for h in handles))
        wall = time.time() - t0
        stats = frontend.stats()
        if ticker is not None:
            ticker.cancel()
        if args.trace:
            trace = write_trace(args.trace, frontend.telemetry)
            print(f"wrote {len(trace['traceEvents'])} trace events to "
                  f"{args.trace} (open in ui.perfetto.dev)")

    toks = sum(len(c.tokens) for c in completions)
    mode = (f"sampled(T={args.temperature}, top_k={args.top_k}, "
            f"top_p={args.top_p}, seed={args.seed}+rid)"
            if sampled else "greedy")
    print(f"arch={cfg.name} layout={batcher.cache_layout} "
          f"allocation={args.allocation} "
          f"slots={args.slots} requests={args.requests} "
          f"prompt={args.prompt_len} gen={args.gen} decode={mode} "
          f"kernel={args.kernel} mesh={stats['mesh']}")
    if args.best_of > 1:
        print(f"best_of={args.best_of}: {batcher.fork_shared_pages} pages "
              f"shared across forks, {batcher.cow_copies} copy-on-write "
              f"page copies (winner by cumulative logprob)")
    print(f"cache {stats['cache_bytes_global'] / 1e6:.2f} MB global, "
          f"{stats['cache_bytes_per_device'] / 1e6:.2f} MB/device over "
          f"{stats['slot_groups']} slot group(s)")
    print(f"{toks} tokens in {wall:.2f}s ({toks / wall:.1f} tok/s, "
          f"{batcher.decode_dispatches / max(1, batcher.decode_ticks):.2f} "
          f"dispatch/tick, occupancy "
          f"{batcher.mean_occupancy():.0%}, utilization "
          f"{batcher.utilization():.0%}, "
          f"{batcher.preemptions} preemptions)")
    for h, toks_ in zip(handles[:4], streams[:4]):
        print(f"  rid={h.rid} [{h.status}] streamed {len(toks_)} tokens: "
              f"{toks_[:8]}...")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--capacity", type=int, default=256)
    ap.add_argument("--layout", choices=("dense", "paged"), default="dense",
                    help="decode-state layout (recurrent archs stay dense)")
    ap.add_argument("--kernel", choices=("xla", "pallas"), default="xla",
                    help="paged decode-attention implementation: XLA ring "
                         "gather (default, the equivalence oracle) or the "
                         "Pallas paged-attention kernel (--layout paged)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="run the engine on a (data=D, model=M) mesh: "
                         "slots shard over the data axis, attention heads "
                         "over the model axis (needs D*M devices)")
    ap.add_argument("--allocation", choices=("worst_case", "lazy"),
                    default="worst_case",
                    help="paged admission: reserve the worst case up "
                         "front, or admit on prompt pages and grow on "
                         "demand (preempting on exhaustion)")
    ap.add_argument("--pages", type=int, default=0,
                    help="page-pool size (0 = full provisioning); "
                         "undersize it with --allocation lazy to watch "
                         "preemption keep the pool busy")
    ap.add_argument("--priority", type=int, default=0,
                    help="priority class for every request (lower is "
                         "preempted first under --allocation lazy)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline; sooner deadlines are "
                         "preempted later")
    ap.add_argument("--best-of", type=int, default=1,
                    help="race N copy-on-write branches per request off "
                         "one prefill and keep the winner by cumulative "
                         "logprob (paged layout; needs N free slots)")
    ap.add_argument("--max-pending", type=int, default=64,
                    help="bounded intake: submit() suspends beyond this")
    ap.add_argument("--replicas", type=int, default=1,
                    help="front N independent replicas with a "
                         "ReplicaRouter (load-scored placement, "
                         "recompute-recipe migration, per-link byte "
                         "accounting)")
    ap.add_argument("--stream", action="store_true",
                    help="print request 0's tokens as they stream")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write the run's Chrome/Perfetto trace_event "
                         "JSON here (one process track per replica, one "
                         "thread per request)")
    ap.add_argument("--stats-interval", type=float, default=None,
                    metavar="S",
                    help="print a one-line telemetry snapshot to stderr "
                         "every S seconds")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy (default); > 0 samples")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k most likely tokens (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling threshold (1.0 = off)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed (request i uses seed + i)")
    args = ap.parse_args()

    from repro.configs import get_config, get_smoke_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import params as Pm

    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params, _ = Pm.init_params(jax.random.PRNGKey(0), cfg)
    if args.replicas > 1:
        asyncio.run(_serve_router(args, cfg, params))
    else:
        asyncio.run(_serve(args, cfg, params))


if __name__ == "__main__":
    main()
