"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), from the peaks of the device kind
the compile targets (launch/mesh.PEAKS; TPU v5e shown):

    compute s    = per-device HLO FLOPs / 197 TFLOP/s
    memory s     = per-device HLO bytes accessed / 819 GB/s
    collective s = per-device collective operand bytes / 50 GB/s per link

cost_analysis() is post-SPMD (per-device).  collective bytes are NOT in
cost_analysis: we parse the compiled HLO text and sum the operand sizes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute (per-device shard shapes; all-reduce counted once per
operand — a ring implementation moves ~2x that, noted in EXPERIMENTS.md).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, asdict

from repro.launch.mesh import peaks

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|\S+(?:\[[\d,]*\])?(?:\{[^}]*\})?)\s+"
    r"([\w\-]+)\((.*)\)", re.M)


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes per collective kind over the (per-device) module."""
    # symbol table: instruction name -> result bytes
    sizes = {}
    for m in _INSTR_RE.finditer(hlo_text):
        name, type_str, _, _ = m.groups()
        sizes[name] = _type_bytes(type_str)

    out = {k: 0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}
    for m in _INSTR_RE.finditer(hlo_text):
        _, _, op, args = m.groups()
        # strip fused suffixes, e.g. all-reduce-start / all-gather-done
        base = op
        for k in COLLECTIVES:
            if op == k or op.startswith(k + "-"):
                base = k
                break
        else:
            continue
        if op.endswith("-done"):
            continue  # counted at -start
        # operands are bare "%x" references: size each from the symbol
        # table (the argument list's other tokens are not instruction names)
        out[base] += sum(sizes.get(name, 0)
                         for name in re.findall(r"%([\w.\-]+)", args))
        counts[base] += 1
    out["total"] = sum(out[k] for k in COLLECTIVES)
    out["counts"] = counts
    return out


@dataclass
class Roofline:
    flops: float
    bytes_accessed: float
    coll_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    useful_ratio: float

    def asdict(self):
        return asdict(self)


def roofline_terms(cost: dict, coll: dict, model_flops: float, *,
                   device_kind: str) -> Roofline:
    """The three terms against the peaks of one `device_kind` chip."""
    hw = peaks(device_kind)
    flops = float(cost.get("flops", 0.0))
    ba = float(cost.get("bytes accessed", 0.0))
    cb = float(coll.get("total", 0))
    compute_s = flops / hw["peak_flops_bf16"]
    memory_s = ba / hw["hbm_bw"]
    collective_s = cb / hw["ici_link_bw"]
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    return Roofline(
        flops=flops, bytes_accessed=ba, coll_bytes=cb,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        bottleneck=bottleneck, model_flops=model_flops,
        useful_ratio=(model_flops / flops) if flops else 0.0)


def model_flops_per_device(cfg, shape, n_devices: int, *,
                           backward: bool) -> float:
    """MODEL_FLOPS = 6 N D (train) / 2 N D (forward-only), N = active params,
    D = tokens processed this step — divided by device count."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        toks = shape.global_batch * shape.seq_len
        per_tok = 6 * n_active
    elif shape.kind == "prefill":
        toks = shape.global_batch * shape.seq_len
        per_tok = 2 * n_active
    else:  # decode: one token per sequence
        toks = shape.global_batch
        per_tok = 2 * n_active
    return per_tok * toks / n_devices
