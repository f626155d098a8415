"""Chip smoke run: serve qwen3-0.6B at its published widths on a TPU.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the paths that exist only across chips

A smoke run, not a benchmark: the seconds it prints include compilation
and are for orientation only.  The model is `get_config("qwen3_0_6b")`
(28 layers, d_model 1024, 16/8 heads of 128, vocabulary 151,936, bf16)
with random weights from a fixed seed.  Eight seeded requests (prompts
of 32-512 tokens, 16-32 new tokens, all greedy but one) go through the
normal serving path, ServingConfig -> ServingFrontend ->
ContinuousBatcher -> engine.

One chip: the requests are served three times, each by its own engine
(freed before the next is built): the paged pool with lazy allocation
under the XLA attention path, the same pool under the Pallas
paged-attention kernel, and the dense layout (the CLI default).  Checks:
every request finishes with its max_new tokens, all in the vocabulary;
the three runs are token-equivalent (`completions_equivalent`); each
greedy request's first token is the argmax of the uncached forward pass
over its prompt, up to an argmax tie; the Pallas engine step lowers the
kernel to Mosaic (`tpu_custom_call`).

--chips 4: the same requests on a (data=2, model=2) mesh, and on four
one-chip replicas behind ReplicaRouter, each on its own chip through a
(1, 1) mesh over that device; the two are driven concurrently.  Checks:
the mesh run is token-equivalent to the one-chip replicas, and every
replica's parameters and cache sit on a different device.

Prompt lengths are multiples of the prefill chunk (16), so each engine
compiles one prefill program and one decode program.  The persistent
compilation cache is on (repro.launch.compile_cache), so a second run on
the same machine loads those programs instead of compiling them.

The last line of stdout is the JSON result; any failed check, and a
machine without a TPU, exits non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import params as Pm  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving import (ContinuousBatcher, ReplicaRouter,  # noqa: E402
                           SamplingParams, ServingConfig, ServingFrontend,
                           SlotSampling, completions_equivalent,
                           make_paged_engine_step)

SEED = 0
N_REQUESTS = 8
PREFILL_CHUNK = 16
# Where two compilations of the same bf16 model may part.  At the step
# where two runs first emit different tokens, the smaller of their two
# margins is at most the largest change of one score between the runs.
# On a v5e at these widths the fp32 logits of the paged XLA and Pallas
# paths differ by a median 0.004 and at most 0.028 over each step's top
# 64 -- as do the XLA path and itself with its attention output scaled by
# 1 + 2**-20: bf16 rounding through 28 layers lifts any last-bit
# difference to that floor -- and the sampled request's scores (logits /
# 0.8) by at most 0.035.  completions_equivalent's default, 1e-3, lies
# far below that floor.
TIE_TOL = 0.04
SAMPLED_RID = 5


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def say(**fields):
    print(json.dumps(fields), flush=True)


# ------------------------------------------------------------ workload


def make_requests(vocab: int, seed: int = SEED) -> list[dict]:
    """N_REQUESTS seeded requests: prompt lengths multiples of
    PREFILL_CHUNK in [32, 512] (the shortest and longest always present),
    16-32 new tokens, greedy except request SAMPLED_RID."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(2, 33, N_REQUESTS) * PREFILL_CHUNK
    lens[0], lens[-1] = 32, 512
    reqs = []
    for i, n in enumerate(lens):
        sampling = None
        if i == SAMPLED_RID:
            sampling = SamplingParams(temperature=0.8, top_k=64, top_p=0.95,
                                      seed=seed + i)
        reqs.append({"prompt": rng.integers(1, vocab, int(n)).tolist(),
                     "max_new": int(rng.integers(16, 33)),
                     "sampling": sampling})
    return reqs


class CompileMeter:
    """Backend-compile seconds and persistent-cache hits/misses, read from
    jax.monitoring; `take()` returns the counts since the last call."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        out = {"compile_s": round(self.seconds, 3),
               "cache_hits": self.hits, "cache_misses": self.misses}
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        return out


async def _serve(frontend, reqs):
    handles = [await frontend.submit(r["prompt"], r["max_new"],
                                     sampling=r["sampling"]) for r in reqs]
    return list(await asyncio.gather(*(h.result() for h in handles)))


def serve(batcher, reqs) -> list:
    """Serve `reqs` through a ServingFrontend; completions in rid order."""

    async def run():
        async with ServingFrontend(batcher, max_pending=len(reqs)) as fe:
            return await _serve(fe, reqs)

    return sorted(asyncio.run(run()), key=lambda c: c.rid)


def check_completions(name: str, done: list, reqs: list, vocab: int):
    check(len(done) == len(reqs),
          f"{name}: {len(done)} of {len(reqs)} requests finished")
    for c, r in zip(done, reqs):
        check(len(c.tokens) == r["max_new"],
              f"{name}: rid {c.rid} emitted {len(c.tokens)} tokens, "
              f"wanted {r['max_new']}")
        check(all(0 <= t < vocab for t in c.tokens),
              f"{name}: rid {c.rid} emitted a token outside the vocabulary")


def first_divergence(a: list, b: list) -> list:
    """Where two completion sets first disagree, per rid, with the margins
    each side recorded there (what completions_equivalent judges)."""
    out = []
    for ca, cb in zip(a, b):
        for i, (ta, tb) in enumerate(zip(ca.tokens, cb.tokens)):
            if ta != tb:
                out.append({"rid": ca.rid, "step": i, "tokens": [ta, tb],
                            "margins": [ca.margins[i], cb.margins[i]]})
                break
    return out


def equivalent(name_a: str, a: list, name_b: str, b: list) -> bool:
    """completions_equivalent, with where the two first disagree."""
    ok = completions_equivalent(a, b, tie_tol=TIE_TOL)
    say(equivalent=f"{name_a} vs {name_b}", ok=ok,
        diverged=first_divergence(a, b))
    return ok


# ---------------------------------------------------------- one chip


def reference_first_tokens(cfg, params, reqs):
    """Argmax and top1-top2 gap of the uncached forward pass at each
    prompt's last position (prompts padded to one length, so one compile;
    causal attention leaves the padded tail out of every earlier row)."""
    width = max(len(r["prompt"]) for r in reqs)

    @jax.jit
    def top2(params, tokens, last):
        logits = T.forward(params, cfg, tokens).logits[0, last]
        vals, idx = jax.lax.top_k(logits.astype(jnp.float32), 2)
        return idx[0], vals[0] - vals[1]

    out = []
    for r in reqs:
        toks = np.zeros((1, width), np.int32)
        toks[0, :len(r["prompt"])] = r["prompt"]
        tok, gap = top2(params, toks, len(r["prompt"]) - 1)
        out.append((int(tok), float(gap)))
    return out


def pallas_step_calls_mosaic(cfg, engine) -> bool:
    """Lower the fused paged decode step with kernel="pallas" at the
    engine's own shapes; True when the kernel reached Mosaic
    (tpu_custom_call) instead of the Pallas interpreter."""
    n, P = engine.block_table.shape
    sds = jax.ShapeDtypeStruct
    i32 = jnp.int32
    sampling = SlotSampling(sds((n, 2), jnp.uint32), sds((n,), i32),
                            sds((n,), jnp.float32), sds((n,), i32),
                            sds((n,), jnp.float32))
    step = jax.jit(make_paged_engine_step(cfg, False, "pallas"),
                   donate_argnums=1)
    lowered = step.lower(engine.params, engine.cache, sds((n, 1), i32),
                         sds((n,), i32), sds((n, P), i32), sds((n,), bool),
                         sds((n,), i32), sds((n,), i32), sampling)
    return "tpu_custom_call" in lowered.as_text()


def one_chip(cfg, params, reqs, meter):
    base = dict(n_slots=8, capacity=2048, prefill_chunk=PREFILL_CHUNK)
    runs = {
        "paged_xla": ServingConfig(cache_layout="paged", allocation="lazy",
                                   kernel="xla", **base),
        "paged_pallas": ServingConfig(cache_layout="paged",
                                      allocation="lazy", kernel="pallas",
                                      **base),
        "dense": ServingConfig(**base),
    }
    done = {}
    for name, sc in runs.items():
        t0 = time.perf_counter()
        batcher = ContinuousBatcher(cfg, params, sc)
        built = time.perf_counter() - t0
        t0 = time.perf_counter()
        done[name] = serve(batcher, reqs)
        wall = time.perf_counter() - t0
        check_completions(name, done[name], reqs, cfg.vocab_size)
        say(run=name, build_s=round(built, 3), serve_wall_s=round(wall, 3),
            tokens=sum(len(c.tokens) for c in done[name]),
            decode_ticks=batcher.decode_ticks,
            prefill_dispatches=batcher.engine.prefill_dispatches,
            cache_bytes=batcher.cache_nbytes(), **meter.take())
        if name == "paged_pallas":
            mosaic = pallas_step_calls_mosaic(cfg, batcher.engine)
            say(pallas_step_tpu_custom_call=mosaic)
            check(mosaic, "the Pallas engine step did not lower the kernel "
                          "to Mosaic (no tpu_custom_call)")
        del batcher
        gc.collect()

    # every comparison is printed before any of them can fail the run
    agree = {other: equivalent("paged_xla", done["paged_xla"],
                               other, done[other])
             for other in ("paged_pallas", "dense")}

    t0 = time.perf_counter()
    ref = reference_first_tokens(cfg, params, reqs)
    firsts = []
    for c, r, (tok, gap) in zip(done["paged_xla"], reqs, ref):
        if r["sampling"] is None:
            firsts.append({"rid": c.rid, "served": c.tokens[0],
                           "reference": tok, "margin": c.margins[0],
                           "reference_gap": gap,
                           "ok": (c.tokens[0] == tok
                                  or min(c.margins[0], gap) <= TIE_TOL)})
    say(reference_first_tokens=firsts,
        wall_s=round(time.perf_counter() - t0, 3), **meter.take())

    for other, ok in agree.items():
        check(ok, f"paged_xla and {other} are not token-equivalent")
    for f in firsts:
        check(f["ok"], f"rid {f['rid']}: first token {f['served']} is not "
                       f"the argmax {f['reference']} of the uncached "
                       f"forward pass")


# -------------------------------------------------------- four chips


async def _four_chip_serve(mesh_batcher, router, reqs):
    # every frontend ticks in a worker thread, so the five engines'
    # first ticks (their compiles) overlap instead of queueing on the
    # event loop
    mesh_fe = ServingFrontend(mesh_batcher, max_pending=len(reqs),
                              tick_in_thread=True)
    for rep in router.replicas:
        rep.frontend.tick_in_thread = True
    async with mesh_fe, router:
        return await asyncio.gather(_serve(mesh_fe, reqs),
                                    _serve(router, reqs))


def devices_of(tree) -> set:
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def four_chips(cfg, params, reqs, meter):
    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    devs = devs[:4]
    base = dict(n_slots=8, capacity=2048, prefill_chunk=PREFILL_CHUNK)
    mesh_batcher = ContinuousBatcher(cfg, params, ServingConfig(
        mesh=make_mesh((2, 2), ("data", "model"), devices=devs), **base))
    router = ReplicaRouter(cfg, params, [
        ServingConfig(mesh=make_mesh((1, 1), ("data", "model"),
                                     devices=[d]), **base)
        for d in devs])

    placed = []
    for rep, d in zip(router.replicas, devs):
        eng = rep.batcher.engine
        on = devices_of(eng.params) | devices_of(eng.cache)
        placed.append(sorted(x.id for x in on))
        check(on == {d}, f"replica {rep.idx}: params/cache on {on}, "
                         f"wanted only {d}")
    say(replica_devices=placed)
    check(len({tuple(p) for p in placed}) == 4,
          "router replicas do not sit on four distinct devices")

    t0 = time.perf_counter()
    mesh_done, router_done = asyncio.run(
        _four_chip_serve(mesh_batcher, router, reqs))
    mesh_done = sorted(mesh_done, key=lambda c: c.rid)
    router_done = sorted(router_done, key=lambda c: c.rid)
    check_completions("mesh_2x2", mesh_done, reqs, cfg.vocab_size)
    check_completions("router_4x1", router_done, reqs, cfg.vocab_size)
    stats = router.stats()
    say(run="mesh_2x2+router_4x1", serve_wall_s=round(
        time.perf_counter() - t0, 3),
        mesh_cache_bytes_per_device=mesh_batcher.cache_nbytes_per_device(),
        completed_per_replica=[len(rep.batcher.done)
                               for rep in router.replicas],
        migrations=stats["overhead"]["migrations"], **meter.take())
    check(equivalent("mesh_2x2", mesh_done, "router_4x1", router_done),
          "mesh_2x2 and router_4x1 are not token-equivalent")


# -------------------------------------------------------------- main


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip serving runs; 4: the (2, 2) mesh "
                         "and four router replicas, and nothing else")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform}); the smoke "
                 f"run has no CPU fallback")
    say(cache_dir=use_compile_cache(), platform=dev.platform,
        device_kind=dev.device_kind, device_count=len(jax.devices()),
        jax=jax.__version__)
    meter = CompileMeter()

    cfg = get_config("qwen3_0_6b")
    t0 = time.perf_counter()
    params, _ = Pm.init_params(jax.random.PRNGKey(SEED), cfg)
    jax.block_until_ready(params)
    say(model=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model,
        vocab=cfg.vocab_size, dtype=cfg.dtype,
        params=Pm.count_params(params),
        init_s=round(time.perf_counter() - t0, 3), **meter.take())
    reqs = make_requests(cfg.vocab_size)
    say(prompt_lens=[len(r["prompt"]) for r in reqs],
        max_new=[r["max_new"] for r in reqs], sampled_rid=SAMPLED_RID)

    try:
        if args.chips == 4:
            four_chips(cfg, params, reqs, meter)
        else:
            one_chip(cfg, params, reqs, meter)
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAILED: {e}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
