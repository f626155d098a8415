"""Where a cell's parts live, found by name: nothing here names a cell,
a configuration, a traffic mix, a loop, a stack or a metric.

- BENCHMARK.json (repo root): the cells, and which metrics each reports;
- bench/cells/<cell>.json: configuration, traffic mix, load, stack and
  its serving settings, chips, and the limits of the correctness
  comparison;
- bench/configs/<config>.json: the published configuration, and the
  modules that make its weights (`model`) and its plain reference
  (`reference`);
- bench/traffic/<mix>.json: the traffic mix's parameters, and its loop;
- bench/loops/<loop>.py: makes a mix's requests and offers them;
- bench/stacks/<stack>.py: builds the serving stack under test;
- bench/metrics/<metric>.py: the reader of one metric.
"""
from __future__ import annotations

import importlib.util
import json
import re
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load(ROOT / "BENCHMARK.json")


def cell(name: str) -> dict:
    """The cell file, with its configuration and traffic mix read in."""
    c = _load(BENCH / "cells" / f"{name}.json")
    c["name"] = name
    c["conf"] = _load(BENCH / "configs" / f"{c['config']}.json")
    c["mix"] = _load(BENCH / "traffic" / f"{c['traffic']}.json")
    return c


_MODULES: dict = {}


def load(path) -> types.ModuleType:
    """The Python file at `path` (relative to the repo root), loaded
    once."""
    path = (ROOT / path).resolve()
    if path not in _MODULES:
        name = "bench_part_" + re.sub(r"\W", "_", str(path.relative_to(ROOT)))
        mod_spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def parts(c: dict) -> types.SimpleNamespace:
    """The modules a cell runs on: its configuration's weights module
    and reference, its mix's loop and its stack."""
    return types.SimpleNamespace(
        model=load(c["conf"]["model"]),
        reference=load(c["conf"]["reference"]),
        loop=load(BENCH / "loops" / f"{c['mix']['loop']}.py"),
        stack=load(BENCH / "stacks" / f"{c['stack']}.py"))


def metrics_of(bench: dict, cell_name: str, kind: str) -> list[dict]:
    """The metrics of `kind` ("end_to_end" or "per_layer") that this cell
    reports: those that list it, and those that list no cells."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def reader(metric: str):
    """The `read(ctx)` function of bench/metrics/<metric>.py."""
    return load(BENCH / "metrics" / f"{metric}.py").read
