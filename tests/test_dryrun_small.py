"""Sharding/dry-run machinery on a tiny placeholder-device mesh.

Runs in a SUBPROCESS with --xla_force_host_platform_device_count=8 so the
main pytest process keeps its single real CPU device."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["DRYRUN_DEVICES"] = "8"  # consumed by repro.launch.dryrun
    from repro.launch.dryrun import build_step, build_sync_step
    import json
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_smoke_config
    from repro.configs.shapes import InputShape
    from repro.launch import roofline as RL
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import input_specs, abstract_sharded_params

    out = {}
    assert len(jax.devices()) == 8
    for arch in ["qwen3_0_6b", "qwen3_moe_30b_a3b", "rwkv6_7b",
                 "zamba2_2_7b"]:
        cfg = get_smoke_config(arch).replace(dtype="bfloat16")
        for multi in (False, True):
            mesh = make_mesh((2, 2, 2) if multi else (2, 4),
                             ("pod", "data", "model") if multi
                             else ("data", "model"))
            shape = InputShape("t", 64, 8, "train")
            fn, args = build_step(cfg, shape, mesh, multi_pod=multi)
            with mesh:
                compiled = jax.jit(fn).lower(*args).compile()
            hlo = compiled.as_text()
            coll = RL.collective_bytes(hlo)
            key = f"{arch}:{'multi' if multi else 'single'}"
            ca = compiled.cost_analysis()
            out[key] = {"ok": True, "coll_total": coll["total"],
                        "flops": (ca or {}).get("flops", 0)}
        # decode on the single mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        shape = InputShape("d", 64, 8, "decode")
        fn, args = build_step(cfg, shape, mesh, multi_pod=False)
        with mesh:
            compiled = jax.jit(fn).lower(*args).compile()
        out[arch + ":decode"] = {"ok": True}
    # sync step emits a cross-pod collective
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_smoke_config("qwen3_0_6b").replace(dtype="bfloat16")
    fn, args = build_sync_step(cfg, mesh)
    with mesh:
        compiled = jax.jit(fn).lower(*args).compile()
    coll = RL.collective_bytes(compiled.as_text())
    out["sync"] = {"ok": True, "coll_total": coll["total"]}
    print("JSON::" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def dryrun_out():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("JSON::")][-1]
    return json.loads(line[len("JSON::"):])


def test_all_small_mesh_combos_compile(dryrun_out):
    for k, v in dryrun_out.items():
        assert v["ok"], k


def test_sync_step_has_cross_pod_collective(dryrun_out):
    assert dryrun_out["sync"]["coll_total"] > 0


def test_roofline_hlo_parser_units():
    from repro.launch.roofline import collective_bytes, _type_bytes

    assert _type_bytes("bf16[4,8]{1,0}") == 64
    assert _type_bytes("f32[10]") == 40
    assert _type_bytes("(bf16[2,2]{1,0}, f32[4])") == 24
    hlo = """
      %p0 = bf16[8,16]{1,0} parameter(0)
      %ar = bf16[8,16]{1,0} all-reduce(%p0), replica_groups={}
      %ag = bf16[16,16]{1,0} all-gather(%ar), dimensions={0}
    """
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 8 * 16 * 2
    assert out["all-gather"] == 8 * 16 * 2  # operand size, not output
    assert out["total"] == 2 * 8 * 16 * 2
