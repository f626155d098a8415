"""What every traffic loop shares: the request as offered, and the
seeded draws of lengths and gaps that a mix file's parameters describe.

A mix (`bench/traffic/<mix>.json`) names its loop (`"loop"`: the module
`bench/loops/<loop>.py`, which makes the requests and offers them) and
gives the loop's parameters; the cell gives the load.

Lengths and gaps are drawn so that every seed gets the same work in
another order.  A window serves a few tens of heavy-tailed requests, so
which of them come first moves what a window can serve by a fifth from
seed to seed when each is an independent draw.  So the values are the
stated distribution's quantiles (i + 0.5) / m, the same multiset for
every seed, and the seed orders them at random within blocks: the
sorted values fall into `block` strata of consecutive rank, each block
of `block` consecutive requests takes one value of each stratum (which
one, the seed draws), and the seed shuffles each block.  Inside a block
any order occurs, so short gaps fall together and bursts form; across
blocks the mix stays the same.

Without a `period` in the mix, m is the run's number of requests.  A
cell above its knee serves only the first part of what is due, and
which values fall in that part still moves the work from seed to seed;
so with a `period`, m is the period and every run of `period`
consecutive requests holds those same m values, each run in its own
block order.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

_Z = NormalDist()


@dataclasses.dataclass(frozen=True)
class Req:
    """One request as offered: `due` is seconds after the window opens
    (0 where the loop sends on reply rather than on a schedule)."""

    idx: int
    due: float
    prompt: tuple
    max_new: int
    temperature: float
    top_p: float
    top_k: int
    seed: int

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0


def rng_for(seed: int, *stream: int):
    """The generator of one stream of a run's draws."""
    return np.random.default_rng([seed, *stream])


def quantiles(spec: dict, n: int) -> np.ndarray:
    """n values at the quantiles (i + 0.5) / n of the stated
    distribution, ascending.  Lengths (lognormal: median, sigma; uniform)
    are rounded and clipped to [min, max]; `exponential` (mean) is left
    as it is."""
    qs = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "exponential":
        return spec["mean"] * -np.log1p(-qs)
    if dist == "lognormal":
        vals = [spec["median"] * math.exp(spec["sigma"] * _Z.inv_cdf(q))
                for q in qs]
    elif dist == "uniform":
        vals = spec["min"] + qs * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def block_order(values: np.ndarray, block: int, rng) -> np.ndarray:
    """`values` (ascending) in a random order that keeps each block of
    `block` consecutive positions one value from each of `block` strata
    of consecutive rank.  A `block` of 1, or of n and more, gives a
    plain random permutation."""
    n = len(values)
    if block <= 1 or block >= n:
        return values[rng.permutation(n)]
    strata = [[] for _ in range(block)]
    for i, v in enumerate(values):
        strata[i * block // n].append(v)
    for s in strata:
        rng.shuffle(s)
    out = []
    for b in range(-(-n // block)):
        members = [s[b] for s in strata if b < len(s)]
        rng.shuffle(members)
        out += members
    return np.asarray(out, dtype=values.dtype)


def ordered(spec: dict, n: int, mix: dict, rng) -> np.ndarray:
    """n values of `spec` in the order a run offers them: the n
    quantiles in the mix's block order, or with the mix's `period` its
    quantiles again in every run of `period` requests."""
    block = mix.get("block", 1)
    period = mix.get("period")
    if not period:
        return block_order(quantiles(spec, n), block, rng)
    base = quantiles(spec, period)
    runs = [block_order(base, block, rng) for _ in range(-(-n // period))]
    return np.concatenate(runs)[:n]


def requests(mix: dict, n: int, dues, seed: int, vocab: int) -> list[Req]:
    """n requests with the mix's prompt and output lengths and decode
    policy: each `greedy_every`-th request greedy (every one where the
    mix samples nothing), the rest sampled as the mix states."""
    p_len = ordered(mix["prompt_tokens"], n, mix, rng_for(seed, 1))
    o_len = ordered(mix["output_tokens"], n, mix, rng_for(seed, 2))
    smp = mix.get("sampling", {})
    greedy_every = mix.get("greedy_every", 0 if smp else 1)
    rng = rng_for(seed, 3)
    out = []
    for i in range(n):
        greedy = greedy_every and i % greedy_every == 0
        out.append(Req(
            idx=i, due=float(dues[i]),
            prompt=tuple(int(t) for t in rng.integers(1, vocab, p_len[i])),
            max_new=int(o_len[i]),
            temperature=0.0 if greedy else float(smp.get("temperature", 0)),
            top_p=1.0 if greedy else float(smp.get("top_p", 1.0)),
            top_k=0 if greedy else int(smp.get("top_k", 0)),
            seed=int(rng.integers(0, 2**31 - 1))))
    return out
