"""Fused sampling layer: SamplingParams validation, the Gumbel-max score
transform (greedy recovery, top-k / top-p filtering, determinism), and the
sampling-aware generate loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving.sampling import (GREEDY, SamplingParams, SlotSampling,
                                    argmax_with_margin, batched_scores,
                                    key_zeros, request_key, sampled_scores)


def _row(temperature=0.0, top_k=0, top_p=1.0, seed=0, step=0):
    return SlotSampling(
        key=request_key(seed), step=np.int32(step),
        temperature=np.float32(temperature), top_k=np.int32(top_k),
        top_p=np.float32(top_p))


def test_sampling_params_validation():
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=1.5)
    assert GREEDY.temperature == 0.0


def test_temperature_zero_returns_raw_logits_bitwise():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(32,)),
                         jnp.float32)
    r = _row(temperature=0.0, top_k=5, top_p=0.3)
    out = sampled_scores(logits, r.key, r.step, r.temperature, r.top_k,
                         r.top_p)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(logits))


def test_sampled_scores_deterministic_in_key_and_step():
    logits = jnp.asarray(np.random.default_rng(1).normal(size=(64,)),
                         jnp.float32)

    def scores(seed, step):
        r = _row(temperature=1.0, seed=seed, step=step)
        return np.asarray(sampled_scores(logits, r.key, r.step,
                                         r.temperature, r.top_k, r.top_p))

    np.testing.assert_array_equal(scores(7, 3), scores(7, 3))
    assert not np.array_equal(scores(7, 3), scores(7, 4))
    assert not np.array_equal(scores(7, 3), scores(8, 3))


def test_top_k_restricts_support():
    """With top_k=2 only the two highest-logit tokens can ever win."""
    logits = jnp.asarray([3.0, 1.0, 2.5, -1.0, 0.0])
    picks = set()
    for step in range(64):
        r = _row(temperature=1.5, top_k=2, step=step)
        s = sampled_scores(logits, r.key, r.step, r.temperature, r.top_k,
                           r.top_p)
        picks.add(int(jnp.argmax(s)))
    assert picks <= {0, 2}
    assert len(picks) == 2  # at T=1.5 both survivors actually occur


def test_top_p_restricts_support():
    """A token holding > top_p of the mass is the only one ever sampled."""
    logits = jnp.asarray([10.0, 0.0, 0.0, 0.0])  # ~100% on token 0
    for step in range(16):
        r = _row(temperature=1.0, top_p=0.5, step=step)
        s = sampled_scores(logits, r.key, r.step, r.temperature, r.top_k,
                           r.top_p)
        assert int(jnp.argmax(s)) == 0
    # top_p=1.0 leaves the tail reachable at high temperature
    picks = set()
    for step in range(256):
        r = _row(temperature=10.0, step=step)
        s = sampled_scores(logits, r.key, r.step, r.temperature, r.top_k,
                           r.top_p)
        picks.add(int(jnp.argmax(s)))
    assert len(picks) > 1


def test_top_k_exact_under_tied_logits():
    """Rank-based masking: duplicate logits at the cutoff must not widen
    the support — top_k=1 keeps exactly the argmax token (ties broken
    toward the lower index, matching argmax) even on a flat row."""
    for logits in (jnp.zeros((4,)), jnp.asarray([1.0, 1.0, 0.0, 0.0])):
        for step in range(32):
            r = _row(temperature=1.0, top_k=1, step=step)
            s = sampled_scores(logits, r.key, r.step, r.temperature,
                               r.top_k, r.top_p)
            assert int(jnp.argmax(s)) == 0
            assert int(jnp.sum(jnp.isfinite(s))) == 1  # exactly k survive


def test_top_p_applies_after_top_k_renormalization():
    """HF/vLLM filter order: top-k first, then the nucleus cut over the
    RENORMALIZED survivors.  probs (0.4, 0.35, 0.25) with top_k=2 →
    renormalized (0.533, 0.467); top_p=0.5 keeps only token 0 (over the
    unrenormalized distribution 0.4 < 0.5 would have kept token 1 too)."""
    logits = jnp.log(jnp.asarray([0.4, 0.35, 0.25]))
    for step in range(64):
        r = _row(temperature=1.0, top_k=2, top_p=0.5, step=step)
        s = sampled_scores(logits, r.key, r.step, r.temperature, r.top_k,
                           r.top_p)
        assert int(jnp.argmax(s)) == 0, step


def test_temperature_only_fast_path_matches_full():
    """The no-filter fast path (top_k=0, top_p=1) must be bitwise equal to
    the full filter path on that subdomain."""
    from repro.serving.sampling import _temperature_scores

    logits = jnp.asarray(np.random.default_rng(3).normal(size=(48,)),
                         jnp.float32)
    for step in (0, 5):
        r = _row(temperature=1.3, seed=11, step=step)
        full = sampled_scores(logits, r.key, r.step, r.temperature,
                              r.top_k, r.top_p)
        fast = _temperature_scores(logits, r.key, r.step, r.temperature,
                                   r.top_k, r.top_p)
        np.testing.assert_array_equal(np.asarray(full), np.asarray(fast))


def test_batched_scores_mixes_greedy_and_sampled_rows():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(3, 16)), jnp.float32)
    kz = key_zeros()
    ss = SlotSampling(
        key=np.stack([kz, request_key(5), kz]),
        step=np.zeros((3,), np.int32),
        temperature=np.asarray([0.0, 1.0, 0.0], np.float32),
        top_k=np.zeros((3,), np.int32),
        top_p=np.ones((3,), np.float32))
    out = np.asarray(batched_scores(logits, ss))
    # greedy rows pass through bitwise; the sampled row is perturbed
    np.testing.assert_array_equal(out[0], np.asarray(logits[0]))
    np.testing.assert_array_equal(out[2], np.asarray(logits[2]))
    assert not np.array_equal(out[1], np.asarray(logits[1]))


def test_argmax_with_margin_infinite_when_single_survivor():
    scores = jnp.asarray([[1.0, -jnp.inf, -jnp.inf]])
    tok, margin = argmax_with_margin(scores)
    assert int(tok[0]) == 0 and np.isinf(float(margin[0]))


@jax.jit
def _sample_one(logits, ss):
    from repro.serving.sampling import batched_sample
    return argmax_with_margin(*batched_sample(logits[None], ss))


def _margin(logits, **kw):
    ss = SlotSampling(*(np.asarray(x)[None] for x in _row(**kw)))
    tok, margin = _sample_one(jnp.asarray(logits, jnp.float32), ss)
    return int(tok[0]), float(margin[0])


def test_margin_counts_a_top_k_cut_next_to_the_winner():
    # top_k=1 keeps token 0 alone, 0.01 above the token it drops: a nudge
    # of the logits swaps them, so the margin is that gap, not inf
    logits = np.asarray([3.0, 2.99, -1.0, -2.0])
    for seed in range(4):
        tok, margin = _margin(logits, temperature=1.0, top_k=1, seed=seed)
        assert tok == 0
        np.testing.assert_allclose(margin, 0.01, rtol=1e-4)
    # unfiltered sampling leaves the Gumbel gap alone
    tok, margin = _margin(logits, temperature=1.0, seed=0)
    assert margin > 0.0 and np.isfinite(margin)


def test_margin_counts_a_nucleus_cut_next_to_top_p():
    # top_p=0.52 keeps token 0 (mass p0 ~ 0.525) alone: the mass before
    # token 1 is 0.005 past top_p, so a nudge of 2 * 0.005 admits it.
    # That moves the sampled token only where token 1 outscores token 0
    # once admitted; elsewhere token 1 must overtake token 0 (gap 0.1)
    logits = np.asarray([0.1, 0.0, -30.0, -30.0])
    p0 = float(jax.nn.softmax(jnp.asarray(logits, jnp.float32))[0])
    rivals = 0
    for seed in range(16):
        tok, margin = _margin(logits, temperature=1.0, top_p=0.52, seed=seed)
        assert tok == 0
        free, _ = _margin(logits, temperature=1.0, seed=seed)
        if free == 1:
            rivals += 1
            np.testing.assert_allclose(margin, 4 * (p0 - 0.52), rtol=1e-3)
        else:
            np.testing.assert_allclose(margin, 0.1, rtol=1e-5)
    assert 0 < rivals < 16
    # greedy rows are never filtered: the plain top1-top2 gap
    tok, margin = _margin(logits, temperature=0.0, top_p=0.52)
    assert tok == 0
    np.testing.assert_allclose(margin, 0.1, rtol=1e-5)


def test_margin_counts_a_dropped_rival_below_the_cut():
    # top_k=2 drops token 2, 0.03 below the lowest survivor; where its
    # Gumbel draw would beat the winner, the margin is at most 0.03
    logits = np.asarray([1.0, 0.5, 0.47, -30.0])
    rivals = 0
    for seed in range(32):
        tok, margin = _margin(logits, temperature=1.0, top_k=2, seed=seed)
        assert tok in (0, 1)
        free, _ = _margin(logits, temperature=1.0, seed=seed)
        if free == 2:
            rivals += 1
            assert margin <= 0.03 + 1e-5
    assert rivals > 0


def test_generate_sampled_reproducible_and_greedy_default():
    from repro.configs import get_smoke_config
    from repro.models import params as Pm
    from repro.serving import greedy_generate, init_cache

    cfg = get_smoke_config("qwen3_0_6b")
    params, _ = Pm.init_params(jax.random.PRNGKey(0), cfg)
    first = jnp.ones((2, 1), jnp.int32)

    def gen(sampling):
        cache = init_cache(cfg, 2, 32, pos=0, dtype=jnp.float32)
        return np.asarray(greedy_generate(cfg, params, cache, first, 8,
                                          sampling=sampling))

    greedy = gen(None)
    # temperature-0 SamplingParams is the greedy path exactly
    np.testing.assert_array_equal(gen(SamplingParams()), greedy)
    sampled = gen(SamplingParams(temperature=1.2, top_k=40, seed=3))
    np.testing.assert_array_equal(
        sampled, gen(SamplingParams(temperature=1.2, top_k=40, seed=3)))
    assert not np.array_equal(sampled,
                              gen(SamplingParams(temperature=1.2, top_k=40,
                                                 seed=4)))
    # batch rows get independent noise (identical first tokens must not
    # force identical sampled continuations)
    assert not np.array_equal(sampled[0], sampled[1])
