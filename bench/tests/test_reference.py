"""The program's prefill-then-decode logits, through its paged cache,
against the plain reference (CPU, smoke size, float32)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model
from bench import reference as R
from bench.tests import tiny


def program_logits(cfg, w, tokens, n_prefill, kernel):
    """Logits at every position: one prefill block, then one decode step
    per token, through the program's paged pool."""
    from repro.models import transformer as T
    from repro.serving.kvcache import init_paged_cache, paged_attn_layout

    cap = 64
    P, _ = paged_attn_layout(cfg, cap)
    cache = init_paged_cache(cfg, 1, cap, 1 + P, dtype=jnp.float32)
    bt = jnp.arange(1, P + 1, dtype=jnp.int32)[None]
    out = []
    pos, step = 0, n_prefill
    while pos < len(tokens):
        blk = jnp.asarray(tokens[None, pos:pos + step], jnp.int32)
        res = T.forward(w, cfg, blk,
                        cache=dict(cache, pos=jnp.int32(pos),
                                   block_table=bt),
                        paged_kernel=kernel)
        cache = {k: v for k, v in res.cache.items() if k != "pos"}
        out.append(np.asarray(res.logits[0]))
        pos, step = pos + blk.shape[1], 1
    return np.concatenate(out)


@pytest.mark.parametrize("kind", ["qwen3-0.6b", "qwen1.5-4b"])
@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_prefill_then_decode_matches_reference(kind, kernel):
    conf = tiny.conf(kind)
    cfg = model.model_config(conf)
    w = model.make_weights(cfg, 2**31 + 99)
    tokens = np.random.default_rng(0).integers(1, cfg.vocab_size, 40)
    got = program_logits(cfg, w, tokens, 16, kernel)
    arch = R.arch_of(conf)
    with jax.default_matmul_precision("highest"):
        h = R.hidden(w, jnp.asarray(tokens), arch)
        want = np.asarray(R._dot(h, R._head(w, arch), False))
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=2e-4 * scale, rtol=0)
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.95


@pytest.mark.parametrize("kind", ["qwen3-0.6b", "qwen1.5-4b"])
def test_reference_sees_each_architecture_part(kind):
    """Dropping qk-norm, the biases or the untied head changes the
    reference's logits: the reference computes each part it claims."""
    conf = tiny.conf(kind)
    cfg = model.model_config(conf)
    w = model.make_weights(cfg, 5)
    toks = jnp.asarray(np.arange(1, 30))
    arch = R.arch_of(conf)
    base = np.asarray(R.hidden(w, toks, arch))
    flip = "qk_norm" if arch["qk_norm"] else "qkv_bias"
    other = np.asarray(R.hidden(w, toks, dict(arch, **{flip: False})))
    assert np.abs(base - other).max() > 1e-2


def test_control_is_coarser_than_float32():
    """The float8 control's first choices lie below the float32 argmax
    by more than float32 misses itself (zero)."""
    conf = tiny.conf("qwen3-0.6b")
    cfg = model.model_config(conf)
    w = model.make_weights(cfg, 11)
    arch = R.arch_of(conf)
    toks = jnp.asarray(np.random.default_rng(1).integers(1, 512, 64))
    picks = R.control_tokens(w, arch, toks)
    best, tgt, _ = R.readings(w, arch, toks, picks,
                              jnp.zeros(64, jnp.float32))
    assert np.asarray(best - tgt).max() > 1e-2
    best, tgt, _ = R.readings(w, arch, toks, jnp.argmax(
        R._dot(R.hidden(w, toks, arch), R._head(w, arch), False), -1),
        jnp.zeros(64, jnp.float32))
    assert np.asarray(best - tgt).max() == 0.0
