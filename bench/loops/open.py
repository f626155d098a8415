"""Open loop: independent users, each request sent when it is due,
whether or not earlier ones have finished; its latency counts from when
it was due, so a stall delays the requests behind it and shows.

Mix parameters: `prompt_tokens`, `output_tokens`, `sampling`,
`greedy_every`, `block`, `period` (bench/traffic.py).  Load:
`rate_per_s`, the mean arrival rate, and `lead_s`, how long the same
traffic runs before the window opens (0 if not given), so that the
window opens on a server already under load rather than an idle one.
Gaps are exponential with mean 1 / rate, drawn as bench/traffic.py
draws lengths, and scaled so that the run's rate * (lead + seconds)
requests span the lead-in and the window exactly.  A request's `due`
counts from the window's opening: those of the lead-in are negative.
"""
import asyncio

import numpy as np

from bench import traffic


def generate(mix: dict, load: dict, seed: int, seconds: float,
             vocab: int):
    rate = load["rate_per_s"]
    lead = load.get("lead_s", 0.0)
    span = lead + seconds
    n = max(1, int(round(rate * span)))
    gaps = traffic.ordered({"dist": "exponential", "mean": 1.0 / rate}, n,
                           mix, traffic.rng_for(seed, 0))
    gaps = gaps * (span / gaps.sum())
    dues = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) - lead
    return traffic.requests(mix, n, dues, seed, vocab)


async def drive(win, reqs, load: dict):
    loop = asyncio.get_running_loop()
    win.begin()
    for req in sorted(reqs, key=lambda r: r.due):
        due = win.start + req.due
        if due >= win.close:
            break
        wait = due - loop.time()
        if wait > 0:
            await asyncio.sleep(wait)
        await win.send(req, due)
    await win.drain()
