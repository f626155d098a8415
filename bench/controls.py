"""Controls and faults that a run's records can be compared under in
place of what the program served (bench/check.compare's `alter`), for
the readings the correctness limits are set from (bench/tools/control.py)
and for the tests.

- `fp8`: the configuration's reference computed in float8, the precision
  below the served bf16, put in the program's place: every greedy
  request's tokens become those float8 ranks first at the same positions
  of the same sequence; sampled requests keep theirs.
- `altered`: a token altered where it is produced: every sampled
  request's tokens are replaced by tokens drawn uniformly from the
  vocabulary; greedy requests keep theirs.
"""
import numpy as np


def fp8(ref, weights, arch: dict):
    def alter(rec, toks):
        if not rec.req.greedy:
            return rec.tokens
        p, n = len(rec.req.prompt), len(rec.tokens)
        picks = np.asarray(ref.control_tokens(weights, arch, toks))
        return picks[p - 1:p - 1 + n]

    return alter


def altered(vocab: int, seed: int):
    rng = np.random.default_rng([seed, 11])

    def alter(rec, toks):
        if rec.req.greedy:
            return rec.tokens
        return rng.integers(0, vocab, len(rec.tokens))

    return alter
