"""Mesh-sharded serving parity.

The heavy checks run in a SUBPROCESS with
--xla_force_host_platform_device_count=8 (the test_dryrun_small.py
pattern — conftest.py forbids forcing placeholder devices globally):
the same mixed greedy+sampled workload is driven through dense and
paged engines under mesh=None, a (1, 1) mesh and the (2, 2) debug
mesh, then compared here.

Contracts under test (serving/sharding.py):
- mesh=None is the single-device path, and a (1, 1) mesh is
  TOKEN-IDENTICAL to it (constraints no-op on one device);
- the (2, 2) debug mesh is equivalent via completions_equivalent
  (margin-tolerant) on dense AND paged, greedy AND sampled decode;
- one fused dispatch still advances the whole pool: 1.00 dispatch per
  MESH tick;
- slots split into one contiguous group per data shard, and cache
  bytes report both globally and per device.

Cheap guards (keyword-only ctors, mesh x pallas rejection, per-device
bytes on one device) run in-process.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = textwrap.dedent("""
    import json

    import jax
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models import params as Pm
    from repro.serving import ContinuousBatcher, Request, SamplingParams

    assert len(jax.devices()) == 8
    cfg = get_smoke_config("qwen3_0_6b")
    params, _ = Pm.init_params(jax.random.PRNGKey(0), cfg)

    def requests():
        rng = np.random.default_rng(7)
        sp = [None,                                       # greedy
              SamplingParams(temperature=0.9, seed=11),   # pure temperature
              SamplingParams(temperature=0.8, top_k=20, seed=12),
              None,
              SamplingParams(temperature=0.7, top_k=0, top_p=0.9, seed=13),
              SamplingParams(temperature=1.1, top_k=12, top_p=0.95,
                             seed=14)]
        return [Request(rid=i,
                        prompt=rng.integers(1, cfg.vocab_size,
                                            6 + 3 * (i % 3)).tolist(),
                        max_new=10, sampling=sp[i])
                for i in range(6)]

    MESHES = {"none": None,
              "m11": make_mesh((1, 1), ("data", "model")),
              "m22": make_mesh((2, 2), ("data", "model"))}

    out = {}
    for layout in ("dense", "paged"):
        for mname, mesh in MESHES.items():
            b = ContinuousBatcher(cfg, params, n_slots=4, capacity=48,
                                  cache_layout=layout, mesh=mesh)
            b.submit(requests())
            while b.step():
                pass
            out[f"{layout}:{mname}"] = {
                "done": [{"rid": c.rid, "tokens": c.tokens,
                          "prompt_len": c.prompt_len,
                          "margins": c.margins} for c in b.done],
                "disp_per_tick": b.decode_dispatches / b.decode_ticks,
                "slot_groups": b.n_slot_groups,
                "group_occupancy": [float(x) for x in b.group_occupancy()],
                "bytes_global": b.cache_nbytes(),
                "bytes_per_device": b.cache_nbytes_per_device(),
            }
    print("JSON::" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def sharded_out():
    env = dict(os.environ, PYTHONPATH=SRC)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("JSON::")][-1]
    return json.loads(line[len("JSON::"):])


def _completions(entry):
    from repro.serving import Completion

    return [Completion(rid=d["rid"], tokens=d["tokens"],
                       prompt_len=d["prompt_len"], margins=d["margins"])
            for d in entry["done"]]


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_mesh11_token_identical(sharded_out, layout):
    """(1, 1) mesh must match mesh=None bit-for-bit: same tokens AND same
    margins (the traced program is identical, so no tie tolerance)."""
    base = sharded_out[f"{layout}:none"]["done"]
    m11 = sharded_out[f"{layout}:m11"]["done"]
    assert {d["rid"]: d["tokens"] for d in m11} == \
           {d["rid"]: d["tokens"] for d in base}
    assert {d["rid"]: d["margins"] for d in m11} == \
           {d["rid"]: d["margins"] for d in base}


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_mesh22_equivalent(sharded_out, layout):
    from repro.serving import completions_equivalent

    base = _completions(sharded_out[f"{layout}:none"])
    m22 = _completions(sharded_out[f"{layout}:m22"])
    assert completions_equivalent(base, m22)


@pytest.mark.parametrize("key", ["dense:m11", "dense:m22", "paged:m11",
                                 "paged:m22"])
def test_one_dispatch_per_mesh_tick(sharded_out, key):
    assert sharded_out[key]["disp_per_tick"] <= 1.0


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_slot_groups_and_bytes(sharded_out, layout):
    m22 = sharded_out[f"{layout}:m22"]
    assert m22["slot_groups"] == 2
    assert len(m22["group_occupancy"]) == 2
    assert sum(m22["group_occupancy"]) > 0
    # heads/slots shard on the (2, 2) mesh, so any one device holds
    # strictly less than the global decode state
    assert m22["bytes_per_device"] < m22["bytes_global"]
    none = sharded_out[f"{layout}:none"]
    assert none["bytes_per_device"] == none["bytes_global"]
    assert none["slot_groups"] == 1


# ----------------------------------------------------- in-process guards


def _smoke():
    import jax

    from repro.configs import get_smoke_config
    from repro.models import params as Pm

    cfg = get_smoke_config("qwen3_0_6b")
    params, _ = Pm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_engine_ctors_keyword_only():
    from repro.serving import DenseEngine, PagedEngine, PerSlotEngine

    cfg, params = _smoke()
    for eng in (DenseEngine, PagedEngine, PerSlotEngine):
        with pytest.raises(TypeError):
            eng(cfg, params, 2, 32)


def test_mesh_rejects_pallas():
    from repro.launch.mesh import make_mesh
    from repro.serving import DenseEngine, PagedEngine

    cfg, params = _smoke()
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="[Pp]allas"):
        DenseEngine(cfg, params, n_slots=2, capacity=32, use_pallas=True,
                    mesh=mesh)
    with pytest.raises(ValueError, match="[Pp]allas"):
        PagedEngine(cfg, params, n_slots=2, capacity=32, kernel="pallas",
                    mesh=mesh)


def test_mesh_rejects_indivisible_slots():
    import jax

    from repro.launch.mesh import make_mesh
    from repro.serving import DenseEngine

    cfg, params = _smoke()
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for a data axis > 1")
    mesh = make_mesh((2, 1), ("data", "model"))
    with pytest.raises(ValueError, match="slot group"):
        DenseEngine(cfg, params, n_slots=3, capacity=32, mesh=mesh)


def test_per_device_bytes_unsharded():
    from repro.serving import DenseEngine, PagedEngine, PerSlotEngine

    cfg, params = _smoke()
    for eng, kw in ((DenseEngine, {}), (PagedEngine, {}),
                    (PerSlotEngine, {})):
        e = eng(cfg, params, n_slots=2, capacity=32, **kw)
        assert e.cache_nbytes_per_device() == e.cache_nbytes()
