"""Training launcher.

Two modes:
  real      — actually train (CPU-sized: use --smoke for the reduced config)
  lower     — lower+compile only (production mesh; see dryrun.py for the
              full sweep)

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3_0_6b --smoke \
      --steps 50 --crosspod --pods 4 --sync-every 10 --sync-mode gtl
  PYTHONPATH=src python -m repro.launch.train --arch gtl_paper   # paper repro
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.launch.compile_cache import use_compile_cache


def run_gtl_paper(args):
    """--arch gtl_paper: the faithful reproduction path."""
    from repro.core.experiment import run_scenario

    r = run_scenario("hapt" if args.scenario == "hapt" else args.scenario,
                     n_samples=args.samples)
    print(f"scenario={r.name}")
    for name, f in r.summary_rows():
        print(f"  {name:14s} F={f:.3f}")
    g = r.overhead.gains()
    print("  overhead:", {k: round(v, 3) for k, v in g.items()})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--crosspod", action="store_true")
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--sync-every", type=int, default=10)
    ap.add_argument("--sync-mode", default="gtl",
                    choices=["gtl", "consensus", "none"])
    ap.add_argument("--sparse-frac", type=float, default=0.0)
    ap.add_argument("--pod-skew", type=float, default=0.3)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--scenario", default="hapt")
    ap.add_argument("--samples", type=int, default=None)
    args = ap.parse_args()
    use_compile_cache()

    if args.arch in ("gtl_paper", "gtl-paper"):
        return run_gtl_paper(args)

    from repro.configs import get_config, get_smoke_config
    from repro.core import crosspod as cp
    from repro.data.lm import SyntheticLM
    from repro.training import optimizer as O
    from repro.training import train_step as TS
    from repro.training.checkpoint import save_checkpoint

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    opt = O.adamw(lr=args.lr)
    key = jax.random.PRNGKey(0)
    data = SyntheticLM(cfg.vocab_size, n_pods=max(1, args.pods),
                       pod_skew=args.pod_skew if args.crosspod else 0.0,
                       num_codebooks=cfg.num_codebooks)

    if args.crosspod:
        state = TS.init_crosspod_train_state(key, cfg, opt, args.pods)
        step = jax.jit(TS.make_crosspod_train_step(cfg, opt))
        sync_cfg = cp.SyncConfig(mode=args.sync_mode,
                                 sparse_frac=args.sparse_frac)
        sync = jax.jit(TS.make_sync_step(cfg, sync_cfg))
        for i in range(args.steps):
            batch = data.pod_batches(i, args.batch, args.seq)
            t0 = time.time()
            state, m = step(state, batch)
            loss = jax.device_get(m["loss"])
            if (i + 1) % args.sync_every == 0 and args.sync_mode != "none":
                probe = data.pod_batches(10_000 + i, 2, args.seq)
                state, _ = sync(state, probe)
                tag = " [sync]"
            else:
                tag = ""
            print(f"step {i:4d} loss/pod={[round(float(x),3) for x in loss]}"
                  f" ({time.time()-t0:.2f}s){tag}", flush=True)
        single = jax.tree.map(lambda a: a[0], state.cross.params)
        oh = cp.crosspod_overhead_bytes(single, args.pods, sync_cfg)
        print(f"per-sync traffic: dense={oh['dense_bytes']/1e6:.1f}MB "
              f"exchanged={oh['exchanged_bytes']/1e6:.1f}MB "
              f"(gain {oh['gain_vs_dense']:.1%}); "
              f"consensus collector={oh['consensus_bytes']/1e6:.1f}MB")
    else:
        state = TS.init_train_state(key, cfg, opt)
        step = jax.jit(TS.make_train_step(cfg, opt))
        for i in range(args.steps):
            batch = data.batch(i, args.batch, args.seq)
            t0 = time.time()
            state, m = step(state, batch)
            print(f"step {i:4d} loss={float(m['loss']):.4f} "
                  f"gnorm={float(m['grad_norm']):.3f} "
                  f"({time.time()-t0:.2f}s)", flush=True)

    if args.checkpoint:
        p = save_checkpoint(args.checkpoint,
                            state.params if not args.crosspod
                            else state.cross.params, step=args.steps)
        print("checkpoint written:", p)


if __name__ == "__main__":
    main()
