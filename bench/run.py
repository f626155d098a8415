"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's parts are found by name (bench/spec.py).  One run:

1. makes the weights on the device from --seed (the configuration's
   `model` file) and builds the serving stack the cell names
   (bench/stacks/<stack>.py);
2. warms up the cell's shapes (the decode step and the prefill blocks of
   1, 2, 4, 8 and 16 tokens) with JAX's persistent compilation cache on
   (repro.launch.compile_cache), so only a checkout's first run compiles;
   everything up to here is set-up;
3. offers the cell's traffic for --seconds through the mix's loop
   (bench/loops/<loop>.py), after the lead-in the cell's load may give,
   and times every request from the client's side; set-up ends when the
   window opens;
4. reads the device's peak memory, frees the serving state, and compares
   a sample of what was served with the configuration's plain reference
   (bench/check.py);
5. prints the end-to-end metrics (--trace 0), or with --trace 1 the
   per-layer metrics, each from its reader bench/metrics/<metric>.py,
   which reads the client's records, counters, spans and a device trace
   of a few seconds inside the window.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  The numbers compared for `correct` close
standard error, each beside its limit, and close the result line under
"checks".
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# past the window's close, how long a submitted request is waited for
GRACE_S = 150.0
# the traced part of a --trace 1 window: where it starts, and how long
TRACE_AT = 0.3
TRACE_S = 4.0
# warm-up prompts: 31 tokens run prefill blocks of 16, 8, 4, 2 and 1
WARM_PROMPT = 31


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def nearest_rank(values, q: float) -> float:
    """The q-th percentile by nearest rank (inf counts as the largest)."""
    v = sorted(values)
    if not v:
        return math.inf
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


class CompileCounter:
    """Backend compilations and persistent-cache hits, from
    jax.monitoring; `take()` returns the counts since the last call."""

    def __init__(self):
        import jax

        self.compiles = self.seconds = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> dict:
        out = {"compiles": self.compiles, "compile_s": self.seconds,
               "cache_hits": self.hits}
        self.compiles = self.seconds = self.hits = 0
        return out


def say(out, **fields):
    print(json.dumps(fields), file=out, flush=True)


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


def devices(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def warm_requests(mix: dict, vocab: int, seed: int):
    """One greedy and one request drawn as the mix draws them, each with
    a 31-token prompt and two new tokens: every prefill block size and
    the decode step run once."""
    import numpy as np

    from bench.traffic import Req

    rng = np.random.default_rng([seed, 3])
    smp = mix.get("sampling", {})
    out = []
    for i, temp in enumerate((0.0, float(smp.get("temperature", 0.0)))):
        out.append(Req(idx=-1 - i, due=0.0,
                       prompt=tuple(int(t) for t in
                                    rng.integers(1, vocab, WARM_PROMPT)),
                       max_new=2, temperature=temp,
                       top_p=float(smp.get("top_p", 1.0)) if temp else 1.0,
                       top_k=int(smp.get("top_k", 0)) if temp else 0,
                       seed=int(rng.integers(0, 2**31 - 1))))
    return out


async def _window(stack, loop, cell, reqs, seconds, profile, marks,
                  grace=GRACE_S):
    from bench.client import Window

    async with stack.frontend(max_pending=1 << 16) as fe:
        win = Window(fe, seconds, grace, cell["load"].get("lead_s", 0.0))
        marks["c_open"] = stack.counters()
        tasks = []
        if profile is not None:
            # it first runs once the loop has begun the window (win.start)
            tasks.append(asyncio.get_running_loop().create_task(
                _profile(win, profile, marks)))
        await loop.drive(win, reqs, cell["load"])
        for t in tasks:
            await t
        marks["c_end"] = stack.counters()
    return win


async def _profile(win, logdir, marks):
    import jax

    seconds = win.seconds
    await asyncio.sleep(win.start + TRACE_AT * seconds
                        - asyncio.get_running_loop().time())
    from bench.trace import CLOCK_SPAN, profile_options

    jax.profiler.start_trace(logdir, profiler_options=profile_options())
    with jax.profiler.TraceAnnotation(CLOCK_SPAN):
        marks["clock"] = time.monotonic()
    await asyncio.sleep(min(TRACE_S, (1 - TRACE_AT) * seconds))
    jax.profiler.stop_trace()


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class Context:
    """What a metric's reader may read (bench/metrics/*.py): the window's
    records, `setup_s`, the tick log, the counters at the window's open
    and close (`marks`), the cell, model config and architecture; with
    --trace 1 also the trace's Summary and the chip's peaks."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def finite(x: float) -> float:
    """A JSON-safe value: a request that never finished reads as 1e9."""
    return x if math.isfinite(x) else 1e9


def build(cell: dict, seed: int, trace: bool = False):
    """The cell's parts, model config, the weights drawn from `seed`, and
    a warm serving stack: every program the window runs is compiled or
    loaded from the persistent cache.  With `trace`, the stack records
    into a profiling Telemetry and its ticks carry a host span."""
    import jax

    from bench import spec

    parts = spec.parts(cell)
    cfg = parts.model.model_config(cell["conf"])
    weights = parts.model.make_weights(cfg, seed)
    jax.block_until_ready(weights)
    stack = parts.stack.build(cfg, weights, cell, trace)
    asyncio.run(stack.warm(warm_requests(cell["mix"], cfg.vocab_size,
                                         seed)))
    if trace:
        stack.annotate()
    return parts, cfg, weights, stack


def run(name: str, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, cell: dict | None = None,
        patch=None, controls=None) -> dict:
    """One run of cell `name`; returns the result line's object.

    For the tests and bench/tools: `cell`, a cell given in place of the
    one on disk; `patch`, a callable applied to the stack's `target`
    before the window (to plant a fault); `controls`, named factories
    `make(reference, weights, arch)` of an `alter` (bench/check.compare,
    bench/controls.py), under each of which the same records are
    compared again, its checks and verdict under "controls"."""
    from bench import spec

    bench = spec.benchmark()
    cell = cell or spec.cell(name)
    devs = devices(cell["chips"], require_chip)
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    meter = CompileCounter()

    from bench import check

    parts, cfg, weights, stack = build(cell, seed, trace)
    if patch is not None:
        patch(stack.target)
    reqs = parts.loop.generate(cell["mix"], cell["load"], seed, seconds,
                               cfg.vocab_size)
    say(sys.stdout, stage="setup", cache_dir=cache_dir,
        platform=devs[0].platform, kind=devs[0].device_kind, **meter.take())

    logdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    marks: dict = {}
    ticks = stack.ticks
    n_ticks0 = len(ticks) if ticks is not None else 0
    try:
        win = asyncio.run(_window(stack, parts.loop, cell, reqs, seconds,
                                  logdir, marks))
        # the event loop's clock is time.monotonic
        setup_s = win.start - T_START
        in_window = meter.take()
        lateness = win.lateness()
        say(sys.stdout, stage="window", requests=len(win.records),
            late_p50_ms=nearest_rank(lateness, 50) * 1e3,
            late_max_ms=max(lateness, default=0.0) * 1e3,
            compiles_in_window=in_window["compiles"],
            counters=delta(marks["c_open"], marks["c_end"]))
        peak = memory_peak(devs)
        ticks = ticks[n_ticks0:] if ticks is not None else []
        pool_itemsize = stack.pool_itemsize
        stack = None
        gc.collect()

        t0 = time.monotonic()
        ref = parts.reference
        checks = check.compare(ref, weights, cell, win.records, seed,
                               cfg.vocab_size)
        sample = checks.pop("_sample")
        say(sys.stdout, stage="check", reference_s=time.monotonic() - t0,
            **sample)

        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs), "memory_peak_bytes": peak}
        result = {"correct": check.passed(checks),
                  "attempted": len(win.records),
                  "failed": sum(1 for r in win.records
                                if r.completion is None)}
        ctx = Context(window=win, setup_s=setup_s, ticks=ticks, marks=marks,
                      cell=cell, cfg=cfg, arch=ref.arch_of(cell["conf"]),
                      pool_itemsize=pool_itemsize, chips=len(devs),
                      nearest_rank=nearest_rank)
        if trace:
            from bench import roofline
            from bench import trace as tr

            summary = tr.reduce(logdir)
            # the recorded interval on the host clock, for the readers
            # that place client-side tokens in it
            off = marks["clock"] - summary.clock_ns * 1e-9
            marks["trace_t0"] = off + summary.t0_ns * 1e-9
            marks["trace_t1"] = off + summary.t1_ns * 1e-9
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            result["breakdown"] = summary.breakdown()
            ctx.trace = summary
            ctx.peaks = roofline.peaks(devs[0].device_kind)
        metrics = {}
        kind = "per_layer" if trace else "end_to_end"
        for m in spec.metrics_of(bench, cell["name"], kind):
            value = spec.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": finite(value),
                                      "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        if controls:
            result["controls"] = {}
            for cname, make in controls.items():
                cc = check.compare(ref, weights, cell, win.records, seed,
                                   cfg.vocab_size,
                                   alter=make(ref, weights, ctx.arch))
                cc.pop("_sample")
                result["controls"][cname] = {"correct": check.passed(cc),
                                             "checks": cc}
        result["checks"] = checks
    finally:
        if logdir is not None:
            shutil.rmtree(logdir, ignore_errors=True)
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoChip as e:
        sys.exit(f"bench: {e}; the benchmark has no CPU fallback")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
