"""The comparison that decides `correct`.

After the window has closed and the program's state is freed, a sample
of the finished requests, drawn from the seed and holding the longest of
each kind, is run once through the configuration's plain reference (the
module its `reference` key names) with the tokens the program served.
Numbers, each with the limit the cell file gives it:

- greedy_gap: the widest gap by which a greedy request's served token
  lies below the reference's best logit at that position;
- nucleus_excess: how far past top_p the reference's probability mass
  above a sampled token reaches (0 while every sampled token lies in
  the reference's nucleus);
- lost: requests due in the window that never finished;
- malformed: finished requests with the wrong number of tokens, or a
  token outside the vocabulary.
"""
from __future__ import annotations

import numpy as np


def pick(records, n: int, rng) -> list:
    """Up to n finished records, the longest always among them, the rest
    drawn by rng."""
    if not records or n <= 0:
        return []
    longest = max(records, key=lambda r: (len(r.req.prompt) + len(r.tokens),
                                          -r.req.idx))
    rest = [r for r in records if r is not longest]
    order = rng.permutation(len(rest))
    return [longest] + [rest[i] for i in order[:n - 1]]


def sequences(recs, length: int, alter=None):
    """Per record: (tokens fed, target served after each, temperature,
    mask of positions that produced a served token), padded to `length`
    (causal attention leaves the padding out of every earlier row).
    `alter(record, toks)`, where given, returns the targets compared in
    place of the served tokens, at the same positions of the same fed
    sequence."""
    for r in recs:
        p, out = list(r.req.prompt), list(r.tokens)
        fed = p + out[:-1]
        toks = np.zeros(length, np.int32)
        tgts = np.zeros(length, np.int32)
        mask = np.zeros(length, bool)
        toks[:len(fed)] = fed
        if alter is not None:
            out = [int(t) for t in alter(r, toks)]
        tgts[len(p) - 1:len(p) - 1 + len(out)] = out
        mask[len(p) - 1:len(p) - 1 + len(out)] = True
        temps = np.full(length, r.req.temperature, np.float32)
        yield r, toks, tgts, temps, mask


def compare(ref, weights, cell: dict, records, seed: int, vocab: int,
            alter=None) -> dict:
    """The numbers of one run, each as {"value", "limit"}, and under
    "_sample" what was compared.  `ref` is the reference module,
    `records` every request due in the window; `alter` (a control or a
    fault, for bench/tools and the tests) puts other tokens in place of
    the sample's served ones."""
    arch = ref.arch_of(cell["conf"])
    capacity = cell["serving"]["capacity"]
    limits = cell["checks"]
    done = [r for r in records if r.completion is not None]
    lost = len(records) - len(done)
    malformed = 0
    for r in done:
        want = min(r.req.max_new, capacity - len(r.req.prompt))
        if len(r.tokens) != want or not all(0 <= t < vocab
                                            for t in r.tokens):
            malformed += 1
    good = [r for r in done
            if len(r.tokens) and all(0 <= t < vocab for t in r.tokens)]
    rng = np.random.default_rng([seed, 7])
    sample = cell["check_sample"]
    greedy = pick([r for r in good if r.req.greedy],
                  sample.get("greedy", 0), rng)
    sampled = pick([r for r in good if not r.req.greedy],
                   sample.get("sampled", 0), rng)
    gaps, excess, n_tokens = [0.0], [0.0], 0
    for r, toks, tgts, temps, mask in sequences(greedy + sampled, capacity,
                                                alter):
        best, tgt, above = (np.asarray(a) for a in
                            ref.readings(weights, arch, toks, tgts, temps))
        n_tokens += int(mask.sum())
        if r.req.greedy:
            gaps.append(float(np.max((best - tgt)[mask])))
        else:
            excess.append(float(np.max(above[mask] - r.req.top_p)))
    out = {"lost": lost, "malformed": malformed}
    if greedy:
        out["greedy_gap"] = max(gaps)
    if sampled:
        out["nucleus_excess"] = max(0.0, max(excess))
    checks = {k: {"value": v, "limit": limits[k]} for k, v in out.items()}
    checks["_sample"] = {"greedy": len(greedy), "sampled": len(sampled),
                         "tokens": n_tokens}
    return checks


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for k, c in checks.items()
               if not k.startswith("_"))
