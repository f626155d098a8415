"""Size a cell's page pool without a chip: compile its decode step and
its 16-token prefill step for a described TPU v5e at the cell's shapes,
for each pool size given, and print what `memory_analysis()` reports.

    JAX_PLATFORMS=cpu python3 bench/tools/size_pool.py <cell> <n_pages>...

A pool fits when weights + the largest step's (arguments + outputs -
aliased + temporaries) leave about 1 GB of the chip's 16 GB.
"""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main():
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.serving.kvcache import init_paged_cache, paged_attn_layout
    from repro.serving.sampling import SlotSampling
    from repro.serving.serve_step import (make_paged_engine_step,
                                          make_paged_prefill_step)

    from bench import spec

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.cell(sys.argv[1])
    model = spec.parts(cell).model
    cfg = model.model_config(cell["conf"])
    sv = cell["serving"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: model.make_weights(cfg, 0)))
    w_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    n = sv["n_slots"]
    P, _ = paged_attn_layout(cfg, sv["capacity"])
    i32 = jnp.int32
    for n_pages in map(int, sys.argv[2:]):
        cache = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: init_paged_cache(cfg, n, sv["capacity"], n_pages,
                                     dtype=jnp.float32)))
        c_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
        batch = SlotSampling(sds((n, 2), jnp.uint32), sds((n,), i32),
                             sds((n,), jnp.float32), sds((n,), i32),
                             sds((n,), jnp.float32))
        row = SlotSampling(sds((2,), jnp.uint32), sds((), i32),
                           sds((), jnp.float32), sds((), i32),
                           sds((), jnp.float32))
        dec = jax.jit(make_paged_engine_step(cfg, False, sv["kernel"]),
                      donate_argnums=1).lower(
            params, cache, sds((n, 1), i32), sds((n,), i32),
            sds((n, P), i32), sds((n,), bool), sds((n,), i32),
            sds((n,), i32), batch).compile()
        pre = jax.jit(make_paged_prefill_step(cfg, False, sv["kernel"]),
                      donate_argnums=1).lower(
            params, cache, sds((), i32), sds((1, 16), i32), sds((), i32),
            sds((1, P), i32), sds((), bool), row).compile()
        for name, c in (("decode", dec), ("prefill16", pre)):
            m = c.memory_analysis()
            peak = (m.argument_size_in_bytes + m.output_size_in_bytes
                    - m.alias_size_in_bytes + m.temp_size_in_bytes)
            print(f"n_pages={n_pages} {name}: weights {w_bytes / 1e9:.3f} GB"
                  f" pool {c_bytes / 1e9:.3f} GB args "
                  f"{m.argument_size_in_bytes / 1e9:.3f} out "
                  f"{m.output_size_in_bytes / 1e9:.3f} alias "
                  f"{m.alias_size_in_bytes / 1e9:.3f} temp "
                  f"{m.temp_size_in_bytes / 1e9:.3f} -> peak "
                  f"{peak / 1e9:.3f} GB", flush=True)


if __name__ == "__main__":
    main()
