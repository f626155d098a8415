"""Reduction of a JAX profiler trace to the numbers the per-layer
metrics read.

A traced run records a few seconds inside its window with the Python
tracer off, so the host side of the trace holds the program's own
`TraceAnnotation` spans (the engine's "paged.decode" / "paged.prefill"
dispatches, with telemetry's profiling on) and the runtime's events.
From the device planes:

- busy: the union of the intervals in which an operation ran, per chip,
  averaged over the chips;
- programs: each compiled program's executions and device seconds,
  labelled by the host span that dispatched it (the latest engine span
  that began before the execution did: the serving loop waits for each
  dispatch's result before it issues the next);
- ops: device seconds per operation name, and those of the
  paged-attention kernel inside each program (an operation belongs to
  the program execution whose interval holds it);
- idle gaps: the gaps between busy intervals on the first chip, by the
  innermost host span open at the gap's middle.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os

# the engine's dispatch spans (serving/engine.py annotates them)
DISPATCH_SPANS = ("paged.decode", "paged.prefill", "dense.decode",
                  "dense.prefill")
# the Pallas paged-attention call, as its operation is named on the device
KERNEL_MARK = "paged_attention"
# a host span the benchmark opens at a known host time, to place the
# trace's clock on the host's
CLOCK_SPAN = "bench.clock"


def profile_options():
    """Profiler options for a traced window: no Python function tracer
    (it records every call of the serving loop), no HLO protos."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def _union(intervals):
    """Merged (start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Summary:
    # the recorded interval: first to last event of the trace (ns on the
    # trace's clock) and its length
    t0_ns: float = 0.0
    t1_ns: float = 0.0
    window_s: float = 0.0
    busy_s: float = 0.0
    # trace time of the "bench.clock" span, opened at a known host time
    clock_ns: float | None = None
    # program (its module name on the device) -> {"label", "count",
    # "device_s", "kernel_s", "kernels"}
    programs: dict = dataclasses.field(default_factory=dict)
    # host spans in the traced window, by name
    spans: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)
    ops: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)
    gaps: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)

    def label(self, name: str) -> list[dict]:
        """The programs the host dispatched under span `name`."""
        return [p for p in self.programs.values() if p["label"] == name]

    def breakdown(self) -> dict:
        return {"device_ops": [[k, v] for k, v in self.ops.most_common(10)],
                "idle_gaps": [[k, v] for k, v in self.gaps.most_common(10)]}


def _device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:TPU:")
            and p.name[len("/device:TPU:"):].isdigit()]


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def reduce(logdir: str) -> Summary:
    """The Summary of the newest trace under `logdir`."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return summarize(ProfileData.from_file(files[-1]))


def summarize(pd) -> Summary:
    """The Summary of one loaded trace (jax.profiler.ProfileData)."""
    out = Summary()
    t0 = t1 = None
    spans = []  # (start, end, name) of host annotations
    for plane in pd.planes:
        host = plane.name.startswith("/host:")
        if not host and plane not in _device_planes(pd):
            continue
        for line in plane.lines:
            for ev in line.events:
                t0 = ev.start_ns if t0 is None else min(t0, ev.start_ns)
                t1 = ev.end_ns if t1 is None else max(t1, ev.end_ns)
                if host and ev.name == CLOCK_SPAN:
                    out.clock_ns = ev.start_ns
                elif host and (ev.name in DISPATCH_SPANS
                               or ev.name.startswith("bench.")):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    if t0 is not None:
        out.t0_ns, out.t1_ns = t0, t1
        out.window_s = (t1 - t0) * 1e-9
    spans.sort()
    out.spans = collections.Counter(n for _, _, n in spans)
    dispatch = [(s, n) for s, _, n in spans if n in DISPATCH_SPANS]
    d_starts = [s for s, _ in dispatch]

    devices = _device_planes(pd)
    busy = []
    for i, plane in enumerate(devices):
        ops_line = _line(plane, "XLA Ops")
        mod_line = _line(plane, "XLA Modules")
        mods = sorted(mod_line.events, key=lambda e: e.start_ns) \
            if mod_line is not None else []
        ops = list(ops_line.events) if ops_line is not None else []
        merged = _union([(e.start_ns, e.end_ns) for e in (ops or mods)])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for ev in ops:
            out.ops[_short(ev.name)] += ev.duration_ns * 1e-9 / len(devices)
        if i:
            continue
        for ev in mods:
            j = bisect.bisect_right(d_starts, ev.start_ns) - 1
            prog = out.programs.setdefault(
                ev.name, {"labels": collections.Counter(), "count": 0,
                          "device_s": 0.0, "kernel_s": 0.0, "kernels": 0})
            prog["labels"][dispatch[j][1] if j >= 0 else None] += 1
            prog["count"] += 1
            prog["device_s"] += ev.duration_ns * 1e-9
        _kernels(out, mods, ops)
        _gaps(out, merged, spans)
    for prog in out.programs.values():
        prog["label"] = prog["labels"].most_common(1)[0][0]
    out.busy_s = sum(busy) / len(busy) if busy else 0.0
    return out


def _short(op: str) -> str:
    """An operation's HLO text cut to its name and result type, without
    layouts: "%copy.142 = f32[28,1025,8,16,128]"."""
    return op.split("{", 1)[0][:100]


def _kernels(out: Summary, mods, ops):
    """Device time of the paged-attention kernel, by the program whose
    execution holds it (op events carry no program id: they lie inside
    their module's interval)."""
    starts = [m.start_ns for m in mods]
    for ev in ops:
        if KERNEL_MARK not in ev.name:
            continue
        j = bisect.bisect_right(starts, ev.start_ns) - 1
        if j >= 0 and ev.start_ns < mods[j].end_ns:
            prog = out.programs[mods[j].name]
            prog["kernel_s"] += ev.duration_ns * 1e-9
            prog["kernels"] += 1


def _gaps(out: Summary, merged, spans):
    """Idle gaps on one chip, by the innermost host span open at each
    gap's middle."""
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        mid = (e0 + s1) / 2
        inner = None
        for s, e, name in spans:
            if s > mid:
                break
            if e >= mid and (inner is None or s >= inner[0]):
                inner = (s, name)
        out.gaps[inner[1] if inner else "no host span"] += (s1 - e0) * 1e-9
