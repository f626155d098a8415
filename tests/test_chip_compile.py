"""The Pallas kernels compile for a TPU v5e at the widths they serve.

Interpret mode (the rest of the suite) cannot see what the TPU compiler
refuses: block shapes whose last two dims are neither (8, 128)-aligned
nor whole, vector reads of scalar-prefetched operands, primitives Mosaic
has no lowering for.  These tests compile each kernel for one chip of a
described v5e topology — no chip needed, a second or two each — and check
that the kernel lowered to Mosaic (`tpu_custom_call`).

The topology is described inside a fixture (never at import): only one
process may hold the TPU library, and under pytest-xdist every worker
imports this file.  The persistent compilation cache is off around the
compiles: an executable compiled for a described chip cannot be read back
without one."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd
from repro.kernels.greedy_scores import greedy_scores as gs
from repro.kernels.paged_attention.paged_attention import \
    paged_attention_grouped
from repro.kernels.ssm_scan.ssm_scan import ssm_scan_bhsd


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_for_chip(fn, sharding, *shapes):
    """Compile fn for the described chip from (shape, dtype) pairs (None
    passes through); returns the compiled HLO text."""
    args = [None if s is None else
            jax.ShapeDtypeStruct(s[0], s[1], sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("S", [1, 16])
@pytest.mark.parametrize("fuse", [False, True])
def test_paged_attention_v2_compiles(one_chip, S, fuse):
    """qwen3-0.6B widths (16 query / 8 KV heads of 128), page 16, tile_k
    4, 8 slots over a 2048-token ring: decode (S=1) and a prefill block
    (S=16), with and without the fused K/V write."""
    cfg = get_config("qwen3_0_6b")
    B, KV, hd, psz, P = 8, cfg.n_kv_heads, cfg.head_dim, 16, 128
    g = cfg.n_heads // KV
    bf16, i32 = jnp.bfloat16, jnp.int32
    new = ((B, KV, S, hd), bf16) if fuse else None
    pool = ((1 + B * P, KV, psz, hd), bf16)
    text = _compile_for_chip(
        lambda q, kn, vn, kp, vp, bt, qp, lp: paged_attention_grouped(
            q, kn, vn, kp, vp, bt, qp, lp, ring_len=P * psz, tile_k=4,
            interpret=False),
        one_chip, ((B, KV, S * g, hd), bf16), new, new, pool, pool,
        ((B, P), i32), ((B, S), i32), ((B,), i32))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    cfg = get_config("qwen3_0_6b")
    shape = ((1, cfg.n_heads, 1024, cfg.head_dim), jnp.bfloat16)
    text = _compile_for_chip(
        lambda q, k, v: flash_attention_bhsd(q, k, v, interpret=False),
        one_chip, shape, shape, shape)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch,bonus", [("rwkv6_7b", True),
                                        ("zamba2_2_7b", False)])
def test_ssm_scan_compiles(one_chip, arch, bonus):
    """RWKV6 (per-channel decay + u bonus) and Mamba2 head geometry."""
    cfg = get_config(arch)
    if bonus:
        H, Dk = cfg.d_model // cfg.ssm_head_dim, cfg.ssm_head_dim
    else:
        H, Dk = cfg.ssm_heads, cfg.ssm_state_dim
    B, S, Dv = 1, 512, cfg.ssm_head_dim
    bf16, f32 = jnp.bfloat16, jnp.float32
    text = _compile_for_chip(
        lambda q, k, v, ld, u: ssm_scan_bhsd(q, k, v, ld, u, bonus=bonus,
                                             interpret=False),
        one_chip, ((B, H, S, Dk), bf16), ((B, H, S, Dk), bf16),
        ((B, H, S, Dv), bf16), ((B, H, S, Dk), f32), ((H, Dk), f32))
    assert "tpu_custom_call" in text


def test_gram_compiles(one_chip):
    text = _compile_for_chip(lambda z: gs.gram(z, interpret=False),
                             one_chip, ((1024, 512), jnp.float32))
    assert "tpu_custom_call" in text


def test_scores_argmax_compiles(one_chip):
    n = ((4096,), jnp.float32)
    text = _compile_for_chip(
        lambda c, d, s: gs.scores_argmax(c, d, s, 0.1, interpret=False),
        one_chip, n, n, n)
    assert "tpu_custom_call" in text
