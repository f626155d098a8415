"""The traffic loops: a seed fixes the requests, every seed gets the same
work in another order, the order is random within the mix's blocks, and
the stated ranges hold."""
import asyncio
from collections import Counter

import numpy as np
import pytest

from bench import spec, traffic
from bench.client import Window

# chat: 1.6 req/s over a 10 s lead-in and a 30 s window, 64 requests,
# four of the mix's periods of 16
LOADS = {"chat": {"rate_per_s": 1.6, "lead_s": 10.0},
         "decode": {"clients": 16}}
BIG_SEED = 2**31 + 12345


def _gen(name, seed, seconds=30, load=None):
    mix = spec._load(spec.BENCH / "traffic" / f"{name}.json")
    loop = spec.load(spec.BENCH / "loops" / f"{mix['loop']}.py")
    return mix, loop.generate(mix, load or LOADS[name], seed, seconds,
                              151936)


@pytest.mark.parametrize("name", sorted(LOADS))
def test_same_seed_same_requests(name):
    _, a = _gen(name, BIG_SEED)
    _, b = _gen(name, BIG_SEED)
    assert a == b
    _, c = _gen(name, BIG_SEED + 1)
    assert [r.prompt for r in a] != [r.prompt for r in c]
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]


@pytest.mark.parametrize("name", sorted(LOADS))
def test_every_seed_gets_the_same_work(name):
    """Seeds reorder lengths and gaps; the multisets stay."""
    runs = [_gen(name, s)[1] for s in (1, 2, BIG_SEED)]
    for key in (lambda r: len(r.prompt), lambda r: r.max_new,
                lambda r: r.greedy):
        counts = [Counter(map(key, reqs)) for reqs in runs]
        assert counts[0] == counts[1] == counts[2]
    if LOADS[name].get("rate_per_s"):
        gaps = [np.sort(np.diff(sorted(x.due for x in r))) for r in runs]
        # the seed leaves out a different last gap; the rest agree
        for g in gaps[1:]:
            near = np.abs(g[:, None] - gaps[0][None, :]).min(1) < 1e-9
            assert near.sum() >= len(g) - 1


@pytest.mark.parametrize("name", sorted(LOADS))
def test_each_block_spans_the_distribution(name):
    """Every block of `block` consecutive requests holds one prompt
    length from each stratum of consecutive rank."""
    mix, reqs = _gen(name, BIG_SEED)
    m, n = mix["block"], len(reqs)
    ranked = np.sort([len(r.prompt) for r in reqs])
    bounds = [ranked[(j * n + m - 1) // m] for j in range(m)] + [np.inf]
    for b in range(n // m):
        lens = sorted(len(r.prompt) for r in reqs[b * m:(b + 1) * m])
        for j, v in enumerate(lens):
            assert bounds[j] <= v <= bounds[j + 1], (b, lens, bounds)


def test_block_order_is_random_within_blocks():
    """Any order occurs inside a block: over seeds, each position of a
    block holds each stratum, and runs of short gaps (the lower half)
    form."""
    vals = np.arange(40.0)
    firsts, together = set(), 0
    for seed in range(200):
        out = traffic.block_order(vals, 4, traffic.rng_for(seed, 0))
        firsts.add(int(out[0]) // 10)
        together += any(out[i] < 20 and out[i + 1] < 20 and out[i + 2] < 20
                        for i in range(len(out) - 2))
    assert firsts == {0, 1, 2, 3}
    assert together > 100
    plain = traffic.block_order(vals, 1, traffic.rng_for(1, 0))
    assert sorted(plain) == list(vals) and list(plain) != list(vals)


@pytest.mark.parametrize("name", sorted(LOADS))
def test_stated_ranges_hold(name):
    mix, reqs = _gen(name, 7)
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(o["min"] <= r.max_new <= o["max"] for r in reqs)
    assert all(0 < t < 151936 for r in reqs for t in r.prompt)
    lens = sorted(len(r.prompt) for r in reqs)
    assert abs(lens[len(lens) // 2] - p["median"]) <= 0.1 * p["median"]
    # the extremes are reached, so the longest prompts are exercised
    assert lens[-1] == p["max"] or p["dist"] != "lognormal" or len(lens) < 80


def test_every_period_holds_the_same_values():
    """With a `period`, each run of `period` consecutive requests holds
    the distribution's `period` quantiles, and each run of gaps the
    exponential's, whatever the seed."""
    mix, _ = _gen("chat", 1)
    m = mix["period"]
    want = {k: sorted(traffic.quantiles(mix[k], m))
            for k in ("prompt_tokens", "output_tokens")}
    gap_q = traffic.quantiles({"dist": "exponential", "mean": 1.0}, m)
    for seed in (1, 2, BIG_SEED):
        _, reqs = _gen("chat", seed)
        assert len(reqs) % m == 0
        dues = [r.due for r in reqs] + [30.0]
        for b in range(0, len(reqs), m):
            run = reqs[b:b + m]
            assert sorted(len(r.prompt) for r in run) == \
                want["prompt_tokens"]
            assert sorted(r.max_new for r in run) == want["output_tokens"]
            gaps = np.diff(dues[b:b + m + 1])
            assert np.allclose(np.sort(gaps) / gaps.sum(),
                               gap_q / gap_q.sum())


def test_open_loop_lead_in_precedes_the_window():
    """The lead-in's requests are due before the window opens (negative
    dues), at the same rate, and the window's fill it to its end."""
    load = LOADS["chat"]
    _, reqs = _gen("chat", BIG_SEED)
    dues = [r.due for r in reqs]
    assert dues == sorted(dues)
    assert len(reqs) == round(load["rate_per_s"] * (load["lead_s"] + 30))
    assert dues[0] == -load["lead_s"] and dues[-1] < 30.0
    early = sum(d < 0 for d in dues)
    assert abs(early - load["rate_per_s"] * load["lead_s"]) <= 6


class _Handle:
    """A streaming reply: a token every `dt` seconds."""

    def __init__(self, n, dt):
        self.n, self.dt = n, dt

    def __aiter__(self):
        return self._gen()

    async def _gen(self):
        for i in range(self.n):
            await asyncio.sleep(self.dt)
            yield i

    async def result(self):
        return "done"


class _Frontend:
    async def submit(self, prompt, max_new, sampling=None):
        return _Handle(max_new, 0.01)


def test_lead_in_tokens_count_only_inside_the_window():
    """A lead-in request is kept apart from the window's records, and of
    its tokens only those received inside the window count."""
    import repro.serving  # noqa: F401  (imported before the clock runs)

    async def go():
        win = Window(_Frontend(), 0.6, 5.0, lead=0.3)
        win.begin()
        loop = asyncio.get_running_loop()
        lead_req = traffic.Req(0, -0.3, (1,), 80, 0.0, 1.0, 0, 0)
        await win.send(lead_req, loop.time())
        await asyncio.sleep(win.start + 0.05 - loop.time())
        win_req = traffic.Req(1, 0.05, (1,), 5, 0.0, 1.0, 0, 0)
        await win.send(win_req, loop.time())
        await win.drain()
        return win

    win = asyncio.run(go())
    (lead,), (rec,) = win.lead, win.records
    assert rec.req.idx == 1 and win.served == [lead, rec]
    assert len(lead.tokens) == 80 and len(rec.tokens) == 5
    for r in (lead, rec):
        assert r.in_window == sum(win.start <= t <= win.close
                                  for t in r.times)
    assert lead.times[0] < win.start and lead.in_window < 80


def test_open_loop_dues_fill_the_window():
    _, reqs = _gen("chat", 3, seconds=40, load={"rate_per_s": 2.0})
    dues = sorted(r.due for r in reqs)
    assert len(reqs) == 80 and dues[0] == 0.0 and dues[-1] < 40.0
    assert dues[-1] > 35.0
    greedy = sum(r.greedy for r in reqs)
    assert greedy == 20  # one in four
    sampled = [r for r in reqs if not r.greedy]
    assert all(r.temperature == 0.7 and r.top_p == 0.9 for r in sampled)


def test_cells_name_existing_parts():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        c = spec.cell(w["name"])
        assert c["config"] == w["config"] and c["traffic"] == w["traffic"]
        assert c["chips"] == w["chips"]
        parts = spec.parts(c)
        for mod, fns in ((parts.model, ("model_config", "make_weights")),
                         (parts.reference, ("arch_of", "readings",
                                            "control_tokens")),
                         (parts.loop, ("generate", "drive")),
                         (parts.stack, ("build",))):
            assert all(callable(getattr(mod, f)) for f in fns)
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            assert callable(spec.reader(m["name"]))
