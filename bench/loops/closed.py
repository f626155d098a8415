"""Closed loop: `clients` callers, each sending its next request when
its last one has finished (batch and agent jobs that wait for their
replies).  Latency counts from the send.

Mix parameters: `prompt_tokens`, `output_tokens`, `sampling`,
`greedy_every`, `block` (bench/traffic.py).  Load: `clients`.  The
clients take requests in order from a pool of POOL, more than any
window finishes.
"""
import asyncio

import numpy as np

from bench import traffic

POOL = 512


def generate(mix: dict, load: dict, seed: int, seconds: float,
             vocab: int):
    return traffic.requests(mix, POOL, np.zeros(POOL), seed, vocab)


async def drive(win, reqs, load: dict):
    loop = asyncio.get_running_loop()
    win.begin()
    pool = iter(reqs)

    async def client():
        for req in pool:
            now = loop.time()
            if now >= win.close:
                return
            _, task = await win.send(req, now)
            await asyncio.wait({task}, timeout=max(
                0.0, win.close + win.grace - loop.time()))

    await asyncio.gather(*(client() for _ in range(load["clients"])))
    await win.drain()
