"""Unified telemetry substrate for the four-layer serving stack.

One `Telemetry` object per serving stack (threaded through
`ServingConfig.telemetry`) owns four things:

- a **metrics registry** — named `Counter` / `Gauge` / `Histogram`
  series created on first use (`tel.counter(name)`, ...).  Counters and
  gauges take free-form labels (``inc(1, reason="pool_exhausted")``);
  histograms use fixed buckets plus retained raw samples, so percentiles
  are exact and two replicas' histograms MERGE without loss
  (`Telemetry.merged` — the router's fleet aggregation).
- a **request-lifecycle tracer** — `trace(rid, event, **attrs)` appends
  a timestamped state transition to the request's span log.  The
  event vocabulary: ``intake`` (frontend accepted the submission),
  ``queued`` (scheduler intake), ``resume``/``prefill``/``decode``
  (slot placement), ``preempt`` (with a ``reason`` attr), ``migrate_out``
  / ``migrate_in`` (router recipe shipping), and the terminals
  ``finished`` / ``cancelled`` / ``expired`` / ``failed``.  Engine ticks
  are recorded separately (`tick(t0, dur, **attrs)`) with dispatch wall
  time, the seconds spent waiting on the device (``wait_s``) and CoW /
  page-growth annotations.
- **host spans** — `span(name)` names a stretch of host work with a
  `jax.profiler.TraceAnnotation` when ``Telemetry(profile=True)``, and
  returns the shared no-op `NULL_SPAN` otherwise.  The vocabulary is
  `HOST_SPANS`: the frontend's loop turn (``frontend.intake``,
  ``frontend.pump``), the scheduler's tick phases (``sched.admit``,
  ``sched.pages``, ``sched.inputs``, ``sched.commit``), the engines'
  dispatches (``paged.decode``, ...) and their blocking result fetches
  (``engine.wait``, whose seconds also feed the tick's ``wait_s``).
  Inside the compiled step programs the matching names are
  `DEVICE_SCOPES`, `jax.named_scope`s that tag every op's metadata:
  ``attn`` (models/layers.attention_block, from the projected q/k/v to
  the attention output: rope, the pool row write, the pool read),
  ``kv_pool`` (the steps' slot resets, copy-on-write page copies and
  slot slices / updates) and ``sample`` (sampling, argmax and logprob).
- **exporters** — `snapshot()` (one nested dict: counters, gauges,
  histogram percentiles, span/tick totals; the layer `stats()` methods
  are compatibility views over it) and `perfetto_trace()` /
  `write_trace()` (Chrome/Perfetto ``trace_event`` JSON: one process
  per replica, one thread per request plus an engine-tick track, so a
  router failover drill is visually inspectable in ui.perfetto.dev).

One clock: every timestamp here is `time.monotonic` (`Telemetry.now`),
the clock of the asyncio loop that drives the frontend, so lifecycle
events, ticks and a client's own records compare directly.

Naming convention for series: ``<layer>_<what>[_<unit>|_total]`` —
``serving_ttft_ms``, ``sched_preemptions_total{reason=...}``,
``router_recipe_bytes_total{link="0->1"}``, ``engine_cow_copies_total``,
``pool_pages_in_use``.

Zero-overhead rule: every recording call on the engine/scheduler hot
path is guarded by ``if telemetry is not None`` AT THE CALL SITE, so a
stack built with ``telemetry=None`` (the default) allocates nothing per
tick and dispatches nothing extra — recording is host-side only either
way, and the fused tick stays at 1.00 dispatch/tick with telemetry on.
"""
from __future__ import annotations

import bisect
import contextlib
import json
import time

import numpy as np

# latency-flavored default buckets (milliseconds); the +inf overflow
# bucket is implicit (counts[len(buckets)])
DEFAULT_BUCKETS = (0.1, 0.2, 0.5, 1.0, 2.0, 5.0, 10.0, 20.0, 50.0,
                   100.0, 200.0, 500.0, 1000.0, 2000.0, 5000.0)

# shared no-op context: span() with profiling off returns this one
# object, and call sites without a sink use it too, so the hot path
# never constructs a context manager per call
NULL_SPAN = contextlib.nullcontext()

# every host span a stack opens (Telemetry.span), frontend to engine:
# the frontend's loop turn, the tick's phases, the engines' dispatches
# (one per jitted step call) and their blocking result fetches
HOST_SPANS = ("frontend.intake", "frontend.pump", "sched.admit",
              "sched.pages", "sched.inputs", "sched.commit",
              "paged.decode", "paged.prefill", "dense.decode",
              "dense.prefill", "per_slot.step", "engine.wait")
# the jax.named_scope names inside the compiled step programs: the
# attention of each layer, the step-level KV-pool work, the sampler
DEVICE_SCOPES = ("attn", "kv_pool", "sample")


def percentile(samples, q: float):
    """Exact percentile over raw samples; None when there are none.
    THE percentile helper of the serving stack — `ServingFrontend` and
    `ReplicaRouter` stats both delegate here."""
    if samples is None or not len(samples):
        return None
    return float(np.percentile(samples, q))


def _label_key(labels: dict) -> tuple:
    return tuple(sorted(labels.items()))


def _label_str(key: tuple) -> str:
    return ",".join(f"{k}={v}" for k, v in key)


class Counter:
    """Monotonic labeled counter.  ``inc(n, **labels)`` books n under the
    label set; `total` sums every label; `value(**labels)` reads one."""

    __slots__ = ("name", "values")

    def __init__(self, name: str):
        self.name = name
        self.values: dict = {}

    def inc(self, n=1, **labels):
        k = _label_key(labels)
        self.values[k] = self.values.get(k, 0) + n

    def value(self, **labels):
        return self.values.get(_label_key(labels), 0)

    @property
    def total(self):
        return sum(self.values.values())

    def as_dict(self):
        """Snapshot form: a bare number when unlabeled, else
        {"k=v": n} per label set."""
        if set(self.values) <= {()}:
            return self.values.get((), 0)
        return {_label_str(k): v for k, v in sorted(self.values.items())}

    def merge_from(self, other: "Counter"):
        for k, v in other.values.items():
            self.values[k] = self.values.get(k, 0) + v


class Gauge:
    """Last-write-wins labeled gauge (None until first set)."""

    __slots__ = ("name", "values")

    def __init__(self, name: str):
        self.name = name
        self.values: dict = {}

    def set(self, v, **labels):
        self.values[_label_key(labels)] = v

    def value(self, **labels):
        return self.values.get(_label_key(labels))

    def as_dict(self):
        if set(self.values) <= {()}:
            return self.values.get(())
        return {_label_str(k): v for k, v in sorted(self.values.items())}

    def merge_from(self, other: "Gauge"):
        self.values.update(other.values)


class Histogram:
    """Fixed-bucket histogram that ALSO retains raw samples: bucket
    counts are the mergeable wire form, the samples give exact
    percentiles (p50/p95/p99) — fleet sizes here are small enough that
    exactness beats sketching."""

    __slots__ = ("name", "buckets", "counts", "samples", "sum")

    def __init__(self, name: str, buckets=DEFAULT_BUCKETS):
        self.name = name
        self.buckets = tuple(buckets)
        self.counts = [0] * (len(self.buckets) + 1)  # last = +inf overflow
        self.samples: list = []
        self.sum = 0.0

    def observe(self, x: float):
        x = float(x)
        self.counts[bisect.bisect_left(self.buckets, x)] += 1
        self.samples.append(x)
        self.sum += x

    @property
    def count(self) -> int:
        return len(self.samples)

    def percentile(self, q: float):
        return percentile(self.samples, q)

    def as_dict(self):
        d = {"count": self.count, "sum": self.sum,
             "min": min(self.samples) if self.samples else None,
             "max": max(self.samples) if self.samples else None,
             "p50": self.percentile(50), "p95": self.percentile(95),
             "p99": self.percentile(99)}
        d["buckets"] = {f"le_{b:g}": c
                        for b, c in zip(self.buckets, self.counts)}
        d["buckets"]["le_inf"] = self.counts[-1]
        return d

    def merge_from(self, other: "Histogram"):
        if other.buckets != self.buckets:
            raise ValueError(
                f"histogram {self.name}: cannot merge mismatched buckets "
                f"{other.buckets} into {self.buckets}")
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.samples.extend(other.samples)
        self.sum += other.sum


# lifecycle events that END a request's span track (perfetto instants)
TERMINAL_EVENTS = ("finished", "cancelled", "expired", "failed",
                   "migrate_out")


class Telemetry:
    """Per-stack telemetry: metrics registry + request tracer + tick log.

    Construction: share ONE instance across the layers of one replica by
    passing it as ``ServingConfig(telemetry=...)`` — the batcher, its
    engine and the frontend all record into it, so `snapshot()` and the
    Perfetto export see the whole replica.  ``profile=True`` additionally
    opens the host spans (`span`) as `jax.profiler.TraceAnnotation`s.

    Clock: `now` is `time.monotonic`, the asyncio loop's clock, so a
    lifecycle event or tick maps onto a client's records, and onto a
    profiler trace through one anchor span stamped on the same clock."""

    def __init__(self, profile: bool = False):
        self.profile = profile
        self.counters: dict[str, Counter] = {}
        self.gauges: dict[str, Gauge] = {}
        self.histograms: dict[str, Histogram] = {}
        # rid -> [(t, event, attrs), ...] in recording order
        self.spans: dict = {}
        # [(t0, dur, attrs), ...] — one entry per engine tick
        self.ticks: list = []
        # seconds spent in engine.wait so far (a tick books its share)
        self.waited_s = 0.0

    # ------------------------------------------------------------ registry

    now = staticmethod(time.monotonic)

    def counter(self, name: str) -> Counter:
        c = self.counters.get(name)
        if c is None:
            c = self.counters[name] = Counter(name)
        return c

    def gauge(self, name: str) -> Gauge:
        g = self.gauges.get(name)
        if g is None:
            g = self.gauges[name] = Gauge(name)
        return g

    def histogram(self, name: str, buckets=DEFAULT_BUCKETS) -> Histogram:
        h = self.histograms.get(name)
        if h is None:
            h = self.histograms[name] = Histogram(name, buckets)
        return h

    # -------------------------------------------------------------- tracer

    def trace(self, rid: int, event: str, t: float | None = None, **attrs):
        """Record one lifecycle transition for request `rid`."""
        self.spans.setdefault(rid, []).append(
            (self.now() if t is None else t, event, attrs))

    def last_event(self, rid: int):
        ev = self.spans.get(rid)
        return ev[-1][1] if ev else None

    def tick(self, t0: float, dur: float, **attrs):
        """Record one engine tick (start + wall seconds + annotations:
        seconds waiting on the device, active slots, dispatches, CoW
        copies, pages grown, preemptions, KV pages streamed by the Pallas
        decode dispatch and its block table's pages)."""
        self.ticks.append((t0, dur, attrs))

    def span(self, name: str):
        """Context manager naming host work `name` (one of HOST_SPANS): a
        `jax.profiler` TraceAnnotation when profiling is on, else the
        shared no-op NULL_SPAN."""
        if not self.profile:
            return NULL_SPAN
        from jax import profiler
        return profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def waiting(self):
        """The ``engine.wait`` span around a blocking fetch of a
        dispatch's results; its seconds add to `waited_s`."""
        t0 = self.now()
        try:
            with self.span("engine.wait"):
                yield
        finally:
            self.waited_s += self.now() - t0

    # ----------------------------------------------------------- exporters

    def snapshot(self) -> dict:
        """One nested dict over everything recorded here.  The layer
        `stats()` methods are compatibility views assembled from this."""
        tick_wall = sum(d for _, d, _ in self.ticks)
        return {
            "counters": {n: c.as_dict()
                         for n, c in sorted(self.counters.items())},
            "gauges": {n: g.as_dict()
                       for n, g in sorted(self.gauges.items())},
            "histograms": {n: h.as_dict()
                           for n, h in sorted(self.histograms.items())},
            "requests_traced": len(self.spans),
            "span_events": sum(len(v) for v in self.spans.values()),
            "ticks": {"count": len(self.ticks),
                      "wall_ms": tick_wall * 1e3,
                      "mean_ms": (tick_wall / len(self.ticks) * 1e3
                                  if self.ticks else None)},
        }

    @classmethod
    def merged(cls, telemetries) -> "Telemetry":
        """Fleet aggregation: a new Telemetry holding every input's
        series summed/merged and every span/tick concatenated (spans of a
        migrated rid interleave by timestamp).  Duplicate objects (a
        batcher and its frontend sharing one instance) are deduped."""
        out = cls()
        seen: set = set()
        for tel in telemetries:
            if tel is None or id(tel) in seen:
                continue
            seen.add(id(tel))
            for n, c in tel.counters.items():
                out.counter(n).merge_from(c)
            for n, g in tel.gauges.items():
                out.gauge(n).merge_from(g)
            for n, h in tel.histograms.items():
                out.histogram(n, h.buckets).merge_from(h)
            for rid, ev in tel.spans.items():
                merged = out.spans.setdefault(rid, [])
                merged.extend(ev)
                merged.sort(key=lambda e: e[0])
            out.ticks.extend(tel.ticks)
        out.ticks.sort(key=lambda e: e[0])
        return out


def perfetto_trace(telemetries, names=None) -> dict:
    """Chrome/Perfetto ``trace_event`` JSON over one or more Telemetry
    objects (one PROCESS per input — pass the fleet's replicas in order
    — one THREAD per request, plus thread 0 for engine ticks).

    Each lifecycle event opens a complete ("X") span named after the
    state ENTERED, closed by the next event on the same rid; the last
    event becomes an instant ("i") — terminals always do.  Timestamps
    are microseconds relative to the earliest event across all inputs,
    so `ts`/`dur` are non-negative and monotonically consistent."""
    if isinstance(telemetries, Telemetry):
        telemetries = [telemetries]
    telemetries = [t for t in telemetries if t is not None]
    starts = [ev[0] for tel in telemetries
              for evs in tel.spans.values() for ev in evs]
    starts += [tk[0] for tel in telemetries for tk in tel.ticks]
    t0 = min(starts) if starts else 0.0
    us = 1e6
    events: list = []
    seen: set = set()
    for pid, tel in enumerate(telemetries):
        if id(tel) in seen:
            continue
        seen.add(id(tel))
        pname = (names[pid] if names and pid < len(names)
                 else f"replica {pid}")
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0, "args": {"name": pname}})
        events.append({"ph": "M", "name": "thread_name", "pid": pid,
                       "tid": 0, "args": {"name": "engine ticks"}})
        for t, dur, attrs in tel.ticks:
            events.append({"ph": "X", "name": "tick", "pid": pid,
                           "tid": 0, "ts": (t - t0) * us,
                           "dur": max(0.0, dur) * us,
                           "args": dict(attrs)})
        for rid, evs in sorted(tel.spans.items()):
            tid = rid + 1  # tid 0 is the engine-tick track
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": tid, "args": {"name": f"rid {rid}"}})
            for i, (t, event, attrs) in enumerate(evs):
                ts = (t - t0) * us
                last = i + 1 >= len(evs)
                if last or event in TERMINAL_EVENTS:
                    events.append({"ph": "i", "name": event, "pid": pid,
                                   "tid": tid, "ts": ts, "s": "t",
                                   "args": dict(attrs)})
                else:
                    dur = (evs[i + 1][0] - t) * us
                    events.append({"ph": "X", "name": event, "pid": pid,
                                   "tid": tid, "ts": ts,
                                   "dur": max(0.0, dur),
                                   "args": dict(attrs)})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_trace(path: str, telemetries, names=None) -> dict:
    """Serialize `perfetto_trace(...)` to `path`; returns the dict."""
    doc = perfetto_trace(telemetries, names)
    with open(path, "w") as f:
        json.dump(doc, f)
    return doc
