"""Benchmark harness — one function per paper table/figure plus framework
benches.  Prints ``name,us_per_call,derived`` CSV rows and writes a
``BENCH_<suite>.json`` artifact per suite (rows plus parsed ``key=value``
fields) so the bench trajectory is tracked across PRs — CI uploads
``BENCH_serving.json`` from the serving suite.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME,...]
                                            [--artifact-dir DIR]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

SUITES = ("prediction", "malicious", "overhead", "aggregators", "dynamic",
          "kernels", "crosspod", "roofline", "serving")


def _parse_derived(derived: str) -> dict:
    """Split a ``k=v;k=v`` derived string into typed fields (best effort)."""
    fields = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            fields[k] = int(v)
        except ValueError:
            try:
                fields[k] = float(v.rstrip("x"))
            except ValueError:
                fields[k] = v
    return fields


def _write_artifact(suite: str, rows, quick: bool, artifact_dir: str):
    os.makedirs(artifact_dir, exist_ok=True)
    path = os.path.join(artifact_dir, f"BENCH_{suite}.json")
    payload = {
        "suite": suite,
        "quick": quick,
        "timestamp": time.time(),
        "rows": [{"name": name, "us_per_call": us, "derived": derived,
                  "fields": _parse_derived(derived)}
                 for name, us, derived in rows],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"wrote {path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced dataset sizes (CI-friendly)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of suites")
    ap.add_argument("--artifact-dir", default=".",
                    help="directory for BENCH_<suite>.json artifacts")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    import importlib

    print("name,us_per_call,derived")
    failures = 0
    for suite in SUITES:
        if only and suite not in only:
            continue
        try:
            mod = importlib.import_module(f"benchmarks.bench_{suite}")
            rows = list(mod.run(quick=args.quick))
            for name, us, derived in rows:
                print(f"{name},{us:.0f},{derived}", flush=True)
            _write_artifact(suite, rows, args.quick, args.artifact_dir)
        except Exception as e:
            failures += 1
            traceback.print_exc(file=sys.stderr)
            print(f"bench_{suite},0,ERROR:{type(e).__name__}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
