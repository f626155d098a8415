"""Split a cell's traced runs by layer, on the chip: the device time of
each step program by named scope, the idle gaps by the program's host
spans, the host's share of a tick and the queue wait (bench/scopes.py).

    python3 bench/tools/layers.py <cell> <seconds> <seed>... [--keep DIR]

Each seed runs the cell once as `bench/run.py --trace 1` does
(bench/run.run), with three hooks around it: while the stack warms up,
the engine's jitted steps are lowered once per shape (the hook is gone
before the window opens); the trace is split from the file the harness
reduces; and the split reads the context the harness's metric readers
get.  The persistent compilation cache keys programs with their
metadata here, so the programs that run carry the named scopes.  One
JSON line per seed: the run's `correct`, its per-layer metrics, and

- `split`: per dispatch span, ms per dispatch under each scope, the
  share under "(none)", the share of op time found in the compiled
  programs' HLO (`mapped`), the scopes' sum over the program's device
  time (`closure`) and the three costliest ops by self time;
- `idle_gaps`: seconds of the traced window's idle gaps by host span;
- `tick_host_ms`, `queue_wait_p50_ms` and the device time per dispatch
  by scope (`decode_attn_device_ms`, `decode_pool_device_ms`,
  `decode_sample_device_ms`, `prefill_pool_device_ms`).

With --keep, the raw trace and the compiled programs' HLO text of the
first seed are written under DIR.
"""
import argparse
import contextlib
import glob
import json
import os
import shutil
import sys
import traceback
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2] / "src"),
                str(Path(__file__).resolve().parents[2])]

METADATA_KEY = "jax_compilation_cache_include_metadata_in_key"
DECODE = ("paged.decode", "dense.decode")
PREFILL = ("paged.prefill", "dense.prefill")


def per_span(summary, split, labels, scope):
    """Seconds under `scope` in the programs of `labels`, per dispatch
    span, in ms; None where no op of those programs ran under it."""
    n = sum(summary.spans[s] for s in labels)
    rows = [split[s] for s in labels if s in split]
    if not n or not any(scope in r["scopes"] for r in rows):
        return None
    return sum(r["scopes"][scope] for r in rows if scope in r["scopes"]) \
        / n * 1e3


def describe(summary, split):
    """Per dispatch span: its count, device ms per span, ms per span by
    scope, the "(none)" share, the mapped share, the closure and the
    three costliest ops."""
    out = {}
    for label, row in split.items():
        n = summary.spans[label] or 1
        dev = row["device_s"]
        ops = sum(row["scopes"].values())
        out[label] = {
            "spans": summary.spans[label],
            "device_ms": dev / n * 1e3,
            "scopes_ms": {k: v / n * 1e3 for k, v in row["scopes"].items()},
            "none_share": row["scopes"]["(none)"] / dev if dev else None,
            "mapped": row["mapped_s"] / ops if ops else None,
            "closure": ops / dev if dev else None,
            "top_ops": [[op, scope, s / n * 1e3] for (op, scope), s in
                        row["ops"].most_common(3)]}
    return out


def traced_run(cell, seed, seconds, keep=None, require_chip=True):
    """One traced run of `cell`; returns (result, the context the
    readers got, the stack's telemetry, the raw trace, the compiled
    programs' HLO texts)."""
    import jax
    from jax.profiler import ProfileData

    from bench import run, scopes, spec, trace

    stack_module = spec.parts(cell).stack
    rec = scopes.Recorder()
    seen = {}
    build, reduce, reader = stack_module.build, trace.reduce, spec.reader

    def recording_build(cfg, weights, c, tr):
        stack = build(cfg, weights, c, tr)
        rec.install(stack.target.engine)
        seen["telemetry"] = stack.telemetry
        return stack

    def keeping_reduce(logdir):
        files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        seen["pd"] = ProfileData.from_file(files[-1])
        if keep:
            shutil.copy(files[-1], os.path.join(keep, "trace.xplane.pb"))
        return reduce(logdir)

    def seeing_reader(metric):
        read = reader(metric)

        def read_and_keep(ctx):
            seen["ctx"] = ctx
            return read(ctx)

        return read_and_keep

    with contextlib.ExitStack() as undo:
        for obj, attr, value in ((stack_module, "build", recording_build),
                                 (trace, "reduce", keeping_reduce),
                                 (spec, "reader", seeing_reader)):
            undo.callback(setattr, obj, attr, getattr(obj, attr))
            setattr(obj, attr, value)
        # the persistent cache keys programs without their metadata: a
        # program cached before its named scopes were added would run,
        # and be read back, without them
        undo.callback(jax.config.update, METADATA_KEY,
                      getattr(jax.config, METADATA_KEY))
        jax.config.update(METADATA_KEY, True)
        res = run.run(cell["name"], seed, seconds, True, cell=cell,
                      patch=lambda batcher: rec.remove(),
                      require_chip=require_chip)
        texts = [low.compile().as_text() for low in rec.lowered.values()]
    if keep:
        for k, text in enumerate(texts):
            Path(keep, f"program{k}.hlo.txt").write_text(text)
    return res, seen["ctx"], seen["telemetry"], seen["pd"], texts


def split_line(ctx, tel, pd, texts, scope_names, host_spans) -> dict:
    from bench import run, scopes

    programs = [scopes.op_names(t) for t in texts]
    split = scopes.split(pd, ctx.trace, programs, scope_names)
    host = scopes.tick_host_s(ctx.ticks)
    waits = scopes.queue_waits(tel.spans, ctx.window.records)
    line = {
        "split": describe(ctx.trace, split),
        "idle_gaps": scopes.gaps(pd, host_spans).most_common(12),
        "programs_compiled": len(texts),
        "tick_host_ms": None if host is None else host * 1e3,
        "queue_wait_p50_ms": (run.nearest_rank(waits, 50) * 1e3
                              if waits else None)}
    for scope, key in (("attn", "attn"), ("kv_pool", "pool"),
                       ("sample", "sample")):
        line[f"decode_{key}_device_ms"] = per_span(ctx.trace, split, DECODE,
                                                   scope)
    line["prefill_pool_device_ms"] = per_span(ctx.trace, split, PREFILL,
                                              "kv_pool")
    return line


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cell")
    ap.add_argument("seconds", type=float)
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--keep", default=None)
    args = ap.parse_args()

    from bench import scopes, spec

    cell = spec.cell(args.cell)
    scope_names, host_spans = scopes.vocabulary()
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
    for i, seed in enumerate(args.seeds):
        res, ctx, tel, pd, texts = traced_run(
            cell, seed, args.seconds, args.keep if i == 0 else None)
        line = {"seed": seed, "correct": res["correct"],
                "metrics": {k: v["value"]
                            for k, v in res["metrics"].items()}}
        try:
            line.update(split_line(ctx, tel, pd, texts, scope_names,
                                   host_spans))
        except Exception:  # the run's own line still comes out
            line["split_error"] = traceback.format_exc()
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
