"""Fused per-slot stochastic sampling for the serving engines.

Sampling is expressed as Gumbel-max over filtered, temperature-scaled
logits: the sampled token is the argmax of

    scores = scaled_filtered_logits + gumbel_noise

which lets every engine reuse the greedy machinery — the token is
``argmax(scores)`` and the top1-top2 gap of the SAME scores is the tie
margin that ``completions_equivalent`` already understands (a near-zero
margin marks a perturbed-score tie where differently-compiled variants of
the same math may legitimately pick different tokens).  A top-k / top-p
filter is a second place where they may: when moving its cut could change
the token, the margin is at most how far the cut is from moving.  At
``temperature <= 0`` the scores ARE the raw fp32 logits, so the greedy
path is recovered bit-for-bit and a whole-batch ``lax.cond`` skips the
sampling compute entirely when no slot samples.

Randomness is keyed per request, not per slot or engine: a request's
``SamplingParams.seed`` derives a base PRNG key (host-side, once, at
admission) and the key for its i-th emitted token is
``jax.random.fold_in(base, i)`` INSIDE the fused dispatch.  Token i of a
request therefore sees identical noise whichever slot it lands in and
whichever engine (dense / paged / per-slot) decodes it — same-seed runs
are reproducible token-for-token across all three, and sampled decode
still costs exactly one dispatch per engine tick.

Filtering order matches the de-facto standard (HF/vLLM): temperature
scale, then top-k, then top-p (nucleus) on the scaled distribution.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode policy.

    temperature: 0 (default) is greedy argmax; > 0 samples from the
    scaled distribution.  top_k: keep only the k highest-probability
    tokens (0 = off).  top_p: keep the smallest set of tokens whose
    cumulative probability reaches top_p (1.0 = off).  seed: derives the
    request's PRNG key — same seed, same tokens, on every engine.
    branch: best-of-n branch index — branch b keys its noise off
    ``branch_key(seed, b)``, so an independent request with (seed, b) is
    token-identical to branch b of a forked best_of run (the fork-parity
    oracle).  branch 0 keys off the plain seed key, preserving every
    pre-fork trajectory bit-for-bit."""

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    branch: int = 0

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0: {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0 (0 = off): {self.top_k}")
        if not 0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1]: {self.top_p}")
        if self.branch < 0:
            raise ValueError(f"branch must be >= 0: {self.branch}")


GREEDY = SamplingParams()

_KEY0 = None


def request_key(seed: int) -> np.ndarray:
    """Host-side base key for a request (uint32 key data, np array)."""
    return np.asarray(jax.random.PRNGKey(seed), np.uint32)


def branch_key(seed: int, branch: int) -> np.ndarray:
    """Host-side base key for branch `branch` of a best-of-n request:
    ``fold_in(seed_key, branch)`` for branch > 0, the plain seed key for
    branch 0 (so a non-forked request's trajectory is untouched).  An
    independent request with ``SamplingParams(seed=seed, branch=b)`` is
    therefore token-identical to branch b of a forked run — the parity
    oracle the fork tests drive."""
    if branch == 0:
        return request_key(seed)
    return np.asarray(
        jax.random.fold_in(jax.random.PRNGKey(seed), branch), np.uint32)


def key_zeros() -> np.ndarray:
    """A zeroed key of the backend's key width (don't-care / greedy)."""
    global _KEY0
    if _KEY0 is None:
        _KEY0 = np.zeros_like(request_key(0))
    return _KEY0


class SlotSampling(NamedTuple):
    """Per-slot sampling state, batched over the slot pool and passed into
    the fused dispatch (leaves are plain arrays; field order matches the
    positional arguments of ``sampled_scores``).  ``step`` is the request's
    emit index — the fold_in counter, NOT the engine tick."""

    key: np.ndarray          # (n_slots, key_width) uint32 base keys
    step: np.ndarray         # (n_slots,) int32 per-request emit index
    temperature: np.ndarray  # (n_slots,) float32; <= 0 means greedy
    top_k: np.ndarray        # (n_slots,) int32; 0 means off
    top_p: np.ndarray        # (n_slots,) float32; 1.0 means off


def _scaled(logits, temperature):
    t = jnp.where(temperature > 0, temperature, jnp.float32(1.0))
    return logits.astype(jnp.float32) / t


def _gumbel(key, step, V):
    return jax.random.gumbel(jax.random.fold_in(key, step), (V,),
                             jnp.float32)


class _Cut(NamedTuple):
    """Where a top-k / top-p filter cut the ranked vocabulary: the lowest
    survivor and the highest dropped token (ids and scaled logits; the
    dropped logit is -inf when nothing is dropped), and, for a nucleus
    cut, how near the mass ranked before each is to top_p, in margin units
    (a nudge of e moves a softmax mass by at most e / 2, and a margin is a
    gap, twice the nudge; inf without a nucleus)."""

    last: jax.Array
    first: jax.Array
    last_logit: jax.Array
    first_logit: jax.Array
    mass_last: jax.Array
    mass_first: jax.Array


def _filter_keep(scaled, top_k, top_p):
    """Boolean keep mask over (V,) scaled logits: top-k first, then the
    nucleus cut over the RENORMALIZED top-k survivors (HF/vLLM order) —
    the smallest prefix of the surviving distribution reaching top_p (the
    token that crosses the threshold is kept).  Masks are rank-based, not
    value-threshold-based: exactly k (resp. n_keep) tokens survive even
    when the cutoff logit is tied (stable argsort breaks ties toward the
    lower index, matching argmax).  Returns (keep, _Cut)."""
    V = scaled.shape[-1]
    order = jnp.argsort(-scaled)  # descending, stable
    ranks = jnp.zeros((V,), jnp.int32).at[order].set(
        jnp.arange(V, dtype=jnp.int32))
    k = jnp.where(top_k > 0, jnp.minimum(top_k, V), V)
    srt = scaled[order]
    probs = jax.nn.softmax(jnp.where(jnp.arange(V) < k, srt, -jnp.inf))
    before = jnp.cumsum(probs) - probs
    n_keep = jnp.maximum(1, jnp.sum(before < top_p))
    n_p = jnp.where(top_p < 1.0, n_keep, V)
    n = jnp.minimum(k, n_p)  # survivors
    nxt = jnp.minimum(n, V - 1)
    nucleus = top_p < 1.0
    cut = _Cut(order[n - 1], order[nxt], srt[n - 1],
               jnp.where(n < V, srt[nxt], -jnp.inf),
               jnp.where(nucleus, 4.0 * (top_p - before[n - 1]), jnp.inf),
               jnp.where(nucleus & (n_p < k), 4.0 * (before[nxt] - top_p),
                         jnp.inf))
    return (ranks < k) & (ranks < n_p), cut


def _sampled(logits, key, step, temperature, top_k, top_p):
    """sampled_scores plus the filter's cut margin: how near the cut is
    to changing the sampled token, to first order.  The winner leaves when
    the highest dropped token overtakes it (or, as the lowest survivor,
    when the nucleus mass before it reaches top_p); a dropped token that
    outscores the winner enters when it overtakes the lowest survivor (or,
    as the highest dropped, when the mass before it falls below top_p)."""
    logits = logits.astype(jnp.float32)
    scaled = _scaled(logits, temperature)
    keep, cut = _filter_keep(scaled, top_k, top_p)
    noisy = scaled + _gumbel(key, step, logits.shape[-1])
    perturbed = jnp.where(keep, noisy, -jnp.inf)
    best = jnp.argmax(perturbed)
    leave = scaled[best] - cut.first_logit
    leave = jnp.where(best == cut.last, jnp.minimum(leave, cut.mass_last),
                      leave)
    ids = jnp.arange(scaled.shape[-1])
    enter = jnp.where(ids == cut.first,
                      jnp.minimum(cut.last_logit - scaled, cut.mass_first),
                      cut.last_logit - scaled)
    enter = jnp.where(~keep & (noisy > perturbed[best]), enter, jnp.inf)
    sampled = temperature > 0
    return (jnp.where(sampled, perturbed, logits),
            jnp.where(sampled, jnp.minimum(leave, jnp.min(enter)), jnp.inf))


def sampled_scores(logits, key, step, temperature, top_k, top_p):
    """(V,) logits + scalar params -> (V,) fp32 scores whose argmax is the
    sampled token (Gumbel-max); temperature <= 0 returns the raw fp32
    logits, so argmax recovers greedy bit-for-bit."""
    return _sampled(logits, key, step, temperature, top_k, top_p)[0]


def _temperature_scores(logits, key, step, temperature, top_k, top_p):
    """sampled_scores specialised to no filtering (top_k=0, top_p=1.0):
    bitwise-identical output on that subdomain, without the O(V log V)
    sort / softmax / cumsum of the filter path."""
    logits = logits.astype(jnp.float32)
    perturbed = _scaled(logits, temperature) + _gumbel(key, step,
                                                       logits.shape[-1])
    return jnp.where(temperature > 0, perturbed, logits)


def _unfiltered(logits, key, step, temperature, top_k, top_p):
    return (_temperature_scores(logits, key, step, temperature, top_k,
                                top_p), jnp.float32(jnp.inf))


def _filtered(top_k, top_p):
    return (top_k > 0) | (top_p < 1.0)


def batched_sample(logits, sampling: SlotSampling):
    """(B, V) logits + batched SlotSampling -> ((B, V) scores, (B,) cut
    margins; see `_sampled`).  Whole-batch conds keep the common cases
    cheap: every-slot-greedy pays only the argmax it always paid, and
    pure-temperature sampling skips the top-k/top-p filter's full-vocab
    sort."""
    greedy = (logits.astype(jnp.float32),
              jnp.full(logits.shape[:1], jnp.inf, jnp.float32))

    def sample(_):
        return jax.lax.cond(
            jnp.any(_filtered(sampling.top_k, sampling.top_p)),
            lambda __: jax.vmap(_sampled)(logits, *sampling),
            lambda __: jax.vmap(_unfiltered)(logits, *sampling),
            None)

    return jax.lax.cond(jnp.any(sampling.temperature > 0), sample,
                        lambda _: greedy, None)


def batched_scores(logits, sampling: SlotSampling):
    """(B, V) logits + batched SlotSampling -> (B, V) scores."""
    return batched_sample(logits, sampling)[0]


def row_sample(logits, row: SlotSampling):
    """(V,) logits + scalar-leaf SlotSampling row -> ((V,) scores, cut
    margin) (the chunked-prefill steps sample one slot's first generated
    token)."""

    def sample(_):
        return jax.lax.cond(
            _filtered(row.top_k, row.top_p),
            lambda __: _sampled(logits, *row),
            lambda __: _unfiltered(logits, *row), None)

    return jax.lax.cond(
        row.temperature > 0, sample,
        lambda _: (logits.astype(jnp.float32), jnp.float32(jnp.inf)), None)


def argmax_with_margin(scores, cut=None):
    """(B, V) -> (argmax (B,), top1-top2 margin (B,) in fp32); with the
    (B,) cut margins of `batched_sample`, the margin is the smaller of the
    two, so a token a nudged filter cut would change counts as a tie."""
    top2 = jax.lax.top_k(scores.astype(jnp.float32), 2)[0]
    margin = top2[:, 0] - top2[:, 1]
    if cut is not None:
        margin = jnp.minimum(margin, cut)
    return jnp.argmax(scores, axis=-1), margin


def token_logprob(logits, tok):
    """(B, V) raw logits + (B,) chosen tokens -> (B,) fp32 log-probability
    of each chosen token under the UNSCALED model distribution.  Best-of-n
    ranks branches by the sum of these (the model's own likelihood of the
    branch), independent of the temperature/filter policy that sampled
    it."""
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(lp, tok[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]


def lockstep_scores(logits, base_key, step, sp: SamplingParams):
    """Scores for one step of a lock-step decode loop: logits (..., V),
    one static SamplingParams for the whole batch.  Every leading-axis row
    (batch element, audio codebook) gets independent noise via a per-row
    fold_in, then the per-token fold_in on `step` inside sampled_scores."""
    V = logits.shape[-1]
    flat = logits.reshape((-1, V))
    R = flat.shape[0]
    keys = jax.vmap(lambda r: jax.random.fold_in(base_key, r))(jnp.arange(R))
    ss = SlotSampling(
        key=keys,
        step=jnp.full((R,), step, jnp.int32),
        temperature=jnp.full((R,), sp.temperature, jnp.float32),
        top_k=jnp.full((R,), sp.top_k, jnp.int32),
        top_p=jnp.full((R,), sp.top_p, jnp.float32))
    return batched_scores(flat, ss).reshape(logits.shape)
