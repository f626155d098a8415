import os
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_BASE_XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count="
                           + os.environ.get("DRYRUN_DEVICES", "512")).strip()

"""Multi-pod dry-run: lower + compile every (architecture x input shape x
mesh) and record memory / cost / collective analyses.

The two lines above MUST stay first: jax locks the device count at first
initialization, and the production meshes need 512 host placeholder devices.
Do not import this module from code that has already initialized jax with a
different device count (it is a __main__-style entry point; smoke tests and
benches must see the real 1-CPU device world instead).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh single,multi [--force] [--out benchmarks/results/dryrun]

Per combo this writes a JSON with:
    memory_analysis  (bytes per device: args/outputs/temps/code)
    cost_analysis    (per-device FLOPs / bytes accessed)
    collectives      (per-device operand bytes by kind, from the HLO)
    roofline         (three terms + bottleneck + MODEL_FLOPS ratio)
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import get_config, model_archs
from repro.configs.shapes import SHAPES
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh

# the chip the production meshes stand for: the roofline terms divide by
# its peaks (launch/mesh.PEAKS), whatever devices compile the dry run
TARGET_KIND = "TPU v5 lite"
from repro.launch.specs import (abstract_sharded_params, input_specs,
                                shape_variant)


def _zero1(sharding, shape, mesh):
    """ZeRO-1: additionally shard an optimizer-state tensor over the `data`
    axis (first unsharded dim divisible by it) — optimizer state has no
    reason to be replicated across data-parallel replicas."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if "data" not in sizes:
        return sharding
    spec = list(sharding.spec) + [None] * (len(shape) - len(sharding.spec))
    if any(s == "data" or (isinstance(s, tuple) and "data" in s)
           for s in spec):
        return sharding
    for i, (dim, s) in enumerate(zip(shape, spec)):
        if s is None and dim % sizes["data"] == 0 and dim >= sizes["data"]:
            spec[i] = "data"
            return NamedSharding(mesh, P(*spec))
    return sharding


def _opt_state_structs(params_structs, mesh, n_pods: int = 0,
                       zero1: bool = False):
    """AdamW state: mu/nu shaped+sharded like the params, fp32.  In cross-pod
    mode the state is vmapped over the pod axis, so `count` is (n_pods,)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.training.optimizer import AdamWState

    def like(a):
        sh = a.sharding
        if zero1:
            sh = _zero1(sh, a.shape, mesh)
        return jax.ShapeDtypeStruct(a.shape, jnp.float32, sharding=sh)

    if n_pods:
        count = jax.ShapeDtypeStruct((n_pods,), jnp.int32,
                                     sharding=NamedSharding(mesh, P("pod")))
    else:
        count = jax.ShapeDtypeStruct((), jnp.int32,
                                     sharding=NamedSharding(mesh, P()))
    return AdamWState(
        mu=jax.tree.map(like, params_structs),
        nu=jax.tree.map(like, params_structs),
        count=count,
    )


def build_step(cfg, shape, mesh, *, multi_pod: bool, rules=None,
               zero1: bool = False):
    """Returns (fn, example_kwargs_structs) ready for jit(...).lower()."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import crosspod as cp
    from repro.serving.serve_step import make_prefill_step, make_serve_step
    from repro.training import optimizer as O
    from repro.training import train_step as TS

    n_pods = mesh.devices.shape[0] if multi_pod else 0
    optimizer = O.adamw()

    if shape.kind == "train":
        params, _ = abstract_sharded_params(cfg, mesh, n_pods=n_pods,
                                            rules=rules)
        opt_state = _opt_state_structs(params, mesh, n_pods=n_pods,
                                       zero1=zero1)
        batch = input_specs(cfg, shape, mesh, n_pods=n_pods)
        scalar = jax.ShapeDtypeStruct((), jnp.int32,
                                      sharding=NamedSharding(mesh, P()))
        if multi_pod:
            # cross-pod GTL: per-pod local step (no collective may touch the
            # pod axis here — verified by tests/test_dryrun_small.py)
            step = TS.make_crosspod_train_step(cfg, optimizer)
            cross = cp.CrossPodState(params=params, anchor=params, ef=params,
                                     syncs=scalar)
            state = TS.CrossPodTrainState(cross=cross, opt_state=opt_state,
                                          step=scalar)
        else:
            step = TS.make_train_step(cfg, optimizer)
            state = TS.TrainState(params=params, opt_state=opt_state,
                                  step=scalar)
        return step, (state, batch)

    params, _ = abstract_sharded_params(cfg, mesh, n_pods=0, rules=rules)
    if shape.kind == "prefill":
        fn = make_prefill_step(cfg)
        batch = input_specs(cfg, shape, mesh, n_pods=0)
        if cfg.frontend == "vision":
            return (lambda p, t, pe: fn(p, t, patch_embeds=pe)), (
                params, batch["tokens"], batch["patch_embeds"])
        return fn, (params, batch["tokens"])

    # decode
    fn = make_serve_step(cfg)
    spec = input_specs(cfg, shape, mesh, n_pods=0)
    return fn, (params, spec["cache"], spec["tokens"])


def build_sync_step(cfg, mesh, sync_cfg=None):
    """Cross-pod GTL sync for the multi-pod mesh — the collective-bearing
    half of the paper's procedure (consensus mode by default; layer_rr /
    sparse_frac are the Sec-8/9 traffic levers)."""
    from repro.core import crosspod as cp
    from repro.training import train_step as TS
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_pods = mesh.devices.shape[0]
    params, _ = abstract_sharded_params(cfg, mesh, n_pods=n_pods)
    scalar = jax.ShapeDtypeStruct((), jnp.int32,
                                  sharding=NamedSharding(mesh, P()))
    cross = cp.CrossPodState(params=params, anchor=params, ef=params,
                             syncs=scalar)
    sync_cfg = sync_cfg or cp.SyncConfig(mode="consensus")

    def sync(state):
        new, _ = cp.sync_step(state, sync_cfg)
        return new

    return sync, (cross,)


def run_combo(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
              force: bool = False, sync: bool = False,
              overrides: dict | None = None, rules: dict | None = None,
              tag_suffix: str = "") -> dict:
    import os as _os

    tag = (f"{arch}__{shape_name}__{mesh_kind}" + ("__sync" if sync else "")
           + (f"__{tag_suffix}" if tag_suffix else ""))
    path = _os.path.join(out_dir, tag + ".json")
    if _os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "sync": sync}
    try:
        multi = mesh_kind == "multi"
        mesh = make_production_mesh(multi_pod=multi)
        shape = SHAPES[shape_name]
        cfg = shape_variant(get_config(arch), shape)
        sync_over = {}
        zero1 = False
        if overrides:
            rec["overrides"] = {k: str(v) for k, v in overrides.items()}
            overrides = dict(overrides)  # caller reuses the dict
            zero1 = bool(overrides.pop("zero1", False))
            cfg_over = {k: v for k, v in overrides.items()
                        if not k.startswith("sync_")}
            sync_over = {k[5:]: v for k, v in overrides.items()
                         if k.startswith("sync_")}
            if cfg_over:
                cfg = cfg.replace(**cfg_over)
        if rules:
            rec["rules"] = {k: str(v) for k, v in rules.items()}

        def compile_cfg(c, sync_=sync):
            if sync_:
                from repro.core import crosspod as _cp

                sc = _cp.SyncConfig(mode="consensus", **sync_over)
                fn, args = build_sync_step(c, mesh, sync_cfg=sc)
            else:
                fn, args = build_step(c, shape, mesh, multi_pod=multi,
                                      rules=rules, zero1=zero1)
            with mesh:
                compiled = jax.jit(fn).lower(*args).compile()
            cost = compiled.cost_analysis() or {}
            coll = RL.collective_bytes(compiled.as_text())
            return compiled, cost, coll

        # main compile: the real scanned program (memory footprint, proves
        # the full (arch x shape x mesh) lowers)
        compiled, cost, coll = compile_cfg(cfg)
        mem = compiled.memory_analysis()

        # cost calibration: XLA's cost_analysis counts a while(scan) body
        # ONCE, so per-layer terms are extrapolated from unrolled 1- and
        # 2-layer-unit compiles: X(L) = X(U1) + (L-1) * (X(U2) - X(U1)).
        if sync:
            cost_c, coll_c = dict(cost), dict(coll)
        else:
            if cfg.block_kind == "hybrid" and cfg.hybrid_attn_every:
                unit = cfg.hybrid_attn_every
                L_eff = cfg.n_layers // unit
            else:
                unit, L_eff = 1, cfg.n_layers
            u1 = cfg.replace(n_layers=unit, scan_layers=False)
            u2 = cfg.replace(n_layers=2 * unit, scan_layers=False)
            _, cost1, coll1 = compile_cfg(u1)
            _, cost2, coll2 = compile_cfg(u2)

            def extrap(a, b):
                return max(0.0, a + (L_eff - 1) * (b - a))

            mb = max(1, getattr(cfg, "microbatches", 1)) \
                if shape.kind == "train" else 1
            cost_c = {k: extrap(cost1.get(k, 0.0), cost2.get(k, 0.0)) * mb
                      for k in ("flops", "bytes accessed", "transcendentals")}
            coll_c = {k: extrap(coll1.get(k, 0), coll2.get(k, 0)) * mb
                      for k in RL.COLLECTIVES + ("total",)}

        n_dev = mesh.devices.size
        mf = RL.model_flops_per_device(
            cfg, shape, n_dev, backward=shape.kind == "train")
        rl = RL.roofline_terms(cost_c, coll_c, mf, device_kind=TARGET_KIND)
        rec.update(
            ok=True,
            seconds=round(time.time() - t0, 1),
            n_devices=n_dev,
            device_kind=TARGET_KIND,
            memory={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "code_bytes": mem.generated_code_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
            },
            cost_scan_body_once={
                "flops": cost.get("flops", 0.0),
                "bytes_accessed": cost.get("bytes accessed", 0.0)},
            cost={"flops": cost_c.get("flops", 0.0),
                  "bytes_accessed": cost_c.get("bytes accessed", 0.0),
                  "transcendentals": cost_c.get("transcendentals", 0.0)},
            collectives={k: v for k, v in coll_c.items()
                         if k in RL.COLLECTIVES + ("total",)},
            collective_counts=coll["counts"],
            roofline=rl.asdict(),
        )
    except Exception as e:  # record the failure, keep the sweep going
        rec.update(ok=False, seconds=round(time.time() - t0, 1),
                   error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    _os.makedirs(out_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--sync", action="store_true",
                    help="also lower the cross-pod GTL sync step (multi)")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (ints/floats/bools parsed)")
    ap.add_argument("--rules", default="",
                    help="sharding rule overrides, e.g. heads=none,kv=none")
    ap.add_argument("--tag", default="", help="output filename suffix")
    args = ap.parse_args()

    def parse_val(v):
        for cast in (int, float):
            try:
                return cast(v)
            except ValueError:
                pass
        return {"true": True, "false": False}.get(v.lower(), v)

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = parse_val(v)
    rules = None
    if args.rules:
        rules = {}
        for kv in args.rules.split(","):
            k, v = kv.split("=")
            rules[k] = None if v.lower() == "none" else v

    archs = model_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = args.mesh.split(",")

    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                rec = run_combo(arch, shape, mesh_kind, args.out,
                                args.force, overrides=overrides or None,
                                rules=rules, tag_suffix=args.tag)
                if rec.get("ok"):
                    rl = rec["roofline"]
                    print(f"OK   {arch:24s} {shape:12s} {mesh_kind:6s} "
                          f"{rec['seconds']:6.1f}s "
                          f"c={rl['compute_s']*1e3:8.2f}ms "
                          f"m={rl['memory_s']*1e3:8.2f}ms "
                          f"x={rl['collective_s']*1e3:8.2f}ms "
                          f"[{rl['bottleneck']}]", flush=True)
                else:
                    print(f"FAIL {arch:24s} {shape:12s} {mesh_kind:6s} "
                          f"{rec['error'][:120]}", flush=True)
        if args.sync and "multi" in meshes:
            rec = run_combo(arch, "train_4k", "multi", args.out, args.force,
                            sync=True, overrides=overrides or None,
                            rules=rules, tag_suffix=args.tag)
            status = "OK  " if rec.get("ok") else "FAIL"
            print(f"{status} {arch:24s} sync         multi  "
                  f"{rec.get('seconds', 0):6.1f}s", flush=True)


if __name__ == "__main__":
    main()
