"""The split of a traced window by layer, below what bench/trace.py's
Summary holds:

- device seconds of each program label ("paged.decode", ...) by the
  named scope its ops ran under (serving/telemetry.DEVICE_SCOPES), the
  rest under "(none)";
- idle gaps by the innermost of the program's own host spans
  (serving/telemetry.HOST_SPANS) or the benchmark's;
- host time per tick, from the tick log's wait on the device;
- queue wait, from the lifecycle log.

Each op counts its self time once: a `while` or `conditional` event
holds its body's events, so it counts its interval less its children's,
and the scopes plus "(none)" sum to the ops' time inside the program.
An op takes the innermost of the scopes in its name stack; a fusion
takes its root's; an op without metadata (a copy the compiler added)
takes the scope of the op that holds it.

Op events on the v5e carry no name stack: an event's name is the
compiled instruction's text without its metadata.  The name stacks come
from the compiled programs' HLO text (`Recorder`: each jitted step's
lowering, captured at its first call and compiled once more after the
run, which the persistent compilation cache answers), keyed by
instruction name.  A program in the trace takes the compiled program that holds the
most of its instruction names.
"""
from __future__ import annotations

import bisect
import collections
import re
import types

from bench import trace

NONE = "(none)"

_INSTR = re.compile(r"\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_HEADER = re.compile(r"\s*(?:ENTRY\s+)?%([\w.\-]+) .*\{\s*$")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def vocabulary():
    """(device scopes, host spans) as the program declares them; empty
    for a program that declares none."""
    from repro.serving import telemetry

    return (tuple(getattr(telemetry, "DEVICE_SCOPES", ())),
            tuple(getattr(telemetry, "HOST_SPANS", ())))


def op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> its op_name metadata, over one compiled
    module's text; a fusion takes its fused computation's root's (its
    own where the root carries none)."""
    own, calls, roots = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            h = _HEADER.match(line)
            if h is not None:
                comp = h.group(1)
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        if " fusion(" in line:
            c = _CALLS.search(line)
            if c is not None:
                calls[name] = c.group(1)
        if line.lstrip().startswith("ROOT ") and comp is not None:
            roots[comp] = own[name]
    return {n: roots.get(calls.get(n), "") or op for n, op in own.items()}


def scope_of(op_name: str, scopes) -> str:
    """The innermost of `scopes` in a name stack ("jit(step)/while/body/
    attn/dot_general" -> "attn"), or NONE."""
    inner = NONE
    for part in op_name.split("/"):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        if part in scopes:
            inner = part
    return inner


def instruction(event_name: str) -> str:
    """The instruction name of an op event ("%fusion.3 = f32[...] ..."
    -> "fusion.3")."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


class Recorder:
    """Captures the lowering of each jitted step an engine calls, once
    per shape of its array arguments, in `lowered`.  Install before the
    warm-up, remove before the window."""

    def __init__(self):
        self.lowered = {}
        self._undo = []

    def install(self, engine, attrs=("_decode", "_prefill")):
        for attr in attrs:
            fn = getattr(engine, attr)

            def first_call(*args, _fn=fn, _attr=attr):
                key = (_attr,) + tuple(getattr(a, "shape", None)
                                       for a in args)
                if key not in self.lowered:
                    self.lowered[key] = _fn.lower(*args)
                return _fn(*args)

            setattr(engine, attr, first_call)
            self._undo.append((engine, attr, fn))

    def remove(self):
        for engine, attr, fn in self._undo:
            setattr(engine, attr, fn)
        self._undo = []


# op events are stamped in whole ns from ps counts: a child may end, or
# its next sibling start, a ns past its parent's or elder's end
SLACK_NS = 2


def _nest(ops):
    """Each op event in order of start, with its self time (s): its
    interval less those of the events directly inside it; and the
    position in that order of the event directly holding it (None at
    the top)."""
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i].start_ns, -ops[i].end_ns))
    own = [ev.duration_ns * 1e-9 for ev in ops]
    parent = [None] * len(ops)
    stack = []
    for k, i in enumerate(order):
        ev = ops[i]
        while stack and ops[order[stack[-1]]].end_ns <= ev.start_ns + SLACK_NS:
            stack.pop()
        if stack and ev.end_ns <= ops[order[stack[-1]]].end_ns + SLACK_NS:
            own[order[stack[-1]]] -= ev.duration_ns * 1e-9
            parent[k] = stack[-1]
        stack.append(k)
    return [(ops[i], own[i], parent[k]) for k, i in enumerate(order)]


def split(pd, summary, programs, scopes) -> dict:
    """{label: {"device_s", "mapped_s", "scopes": {scope: s},
    "ops": Counter of (op, scope) -> self s}} over the first chip, for
    every program label of `summary` (bench.trace.Summary of the same
    trace).  `programs`: op-name maps of the compiled programs.  Empty
    for a trace without a chip."""
    devices = trace._device_planes(pd)
    if not devices:
        return {}
    plane = devices[0]
    mods_line = trace._line(plane, "XLA Modules")
    ops_line = trace._line(plane, "XLA Ops")
    mods = sorted(mods_line.events, key=lambda e: e.start_ns) \
        if mods_line is not None else []
    ops = list(ops_line.events) if ops_line is not None else []
    starts = [m.start_ns for m in mods]
    inside = collections.defaultdict(list)
    for ev in ops:
        j = bisect.bisect_right(starts, ev.start_ns) - 1
        if j >= 0 and ev.start_ns < mods[j].end_ns:
            inside[j].append(ev)
    # each program name in the trace -> the compiled program holding the
    # most of its instruction names
    names = collections.defaultdict(set)
    for j, evs in inside.items():
        names[mods[j].name].update(instruction(e.name) for e in evs)
    match = {}
    for prog, held in names.items():
        best = max(programs, key=lambda p: len(held & p.keys()),
                   default={})
        match[prog] = best
    out = {}
    for j, m in enumerate(mods):
        label = summary.programs[m.name]["label"]
        row = out.setdefault(label, {
            "device_s": 0.0, "mapped_s": 0.0,
            "scopes": collections.Counter({NONE: 0.0}),
            "ops": collections.Counter()})
        row["device_s"] += m.duration_ns * 1e-9
        names_of = match.get(m.name, {})
        held = []  # scope of each op, in _nest's order
        for ev, own, up in _nest(inside.get(j, [])):
            instr = instruction(ev.name)
            if instr in names_of:
                row["mapped_s"] += own
            op_name = names_of.get(instr, "")
            # an op the compiler added carries no metadata: it serves
            # the op that holds it (a copy in a conditional's branch)
            scope = (scope_of(op_name, scopes) if op_name or up is None
                     else held[up])
            held.append(scope)
            row["scopes"][scope] += own
            row["ops"][(trace._short(ev.name), scope)] += own
    return out


def gaps(pd, spans_named) -> collections.Counter:
    """Idle gaps on the first chip by the innermost host span open at
    each gap's middle, among the spans `spans_named` holds, the engine's
    dispatch spans and the benchmark's own."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if (ev.name in spans_named or ev.name in trace.DISPATCH_SPANS
                        or ev.name.startswith("bench.")) \
                        and ev.name != trace.CLOCK_SPAN:
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    spans.sort()
    devices = trace._device_planes(pd)
    line = devices and (trace._line(devices[0], "XLA Ops")
                        or trace._line(devices[0], "XLA Modules"))
    merged = trace._union([(e.start_ns, e.end_ns) for e in line.events]) \
        if line else []
    out = types.SimpleNamespace(gaps=collections.Counter())
    trace._gaps(out, merged, spans)
    return out.gaps


def tick_host_s(ticks) -> float | None:
    """Mean over ticks of wall less the wait on the device (s); None
    where the tick log carries no wait."""
    if not ticks or any("wait_s" not in a for _, _, a in ticks):
        return None
    return sum(d - a["wait_s"] for _, d, a in ticks) / len(ticks)


def queue_waits(lifecycle: dict, records) -> list[float]:
    """Seconds from "queued" to first placement ("prefill" or "resume")
    of each finished request among `records` (bench.client.Record),
    found in the lifecycle log by its Completion's rid."""
    out = []
    for r in records:
        if r.completion is None:
            continue
        events = lifecycle.get(r.completion.rid, [])
        tq = next((t for t, e, _ in events if e == "queued"), None)
        if tq is None:
            continue
        tp = next((t for t, e, _ in events
                   if e in ("prefill", "resume") and t >= tq), None)
        if tp is not None:
            out.append(tp - tq)
    return out
