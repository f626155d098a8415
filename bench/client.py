"""The client side of a run: a measured window over the serving
frontend, which submits requests, streams their replies and times every
token on the host clock.  When each request is sent is the loop's
business (`bench/loops/<loop>.py`); no loop submits once the window has
closed, and those already submitted are waited for, up to `grace`
seconds past the close.  A cell's load may give a lead-in (`lead_s`):
the loop starts sending that long before the window opens, and what
those requests receive inside the window counts as the window's work.
"""
from __future__ import annotations

import asyncio
import dataclasses
import math

INF = math.inf


@dataclasses.dataclass
class Record:
    req: object                    # traffic.Req
    due: float = INF               # loop clock, when it was due
    sent: float = INF              # loop clock, when submit() was called
    first: float | None = None     # first token received
    last: float | None = None      # last token received
    tokens: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)  # per token
    in_window: int = 0             # tokens received inside the window
    completion: object = None      # the frontend's Completion, once done
    error: str | None = None

    @property
    def ttft(self) -> float:
        return INF if self.first is None or self.completion is None \
            else self.first - self.due

    @property
    def tpot(self) -> float:
        n = len(self.tokens)
        if self.completion is None or n < 2:
            return INF
        return (self.last - self.first) / (n - 1)


def sampling_of(req):
    from repro.serving import SamplingParams

    if req.greedy:
        return None
    return SamplingParams(temperature=req.temperature, top_p=req.top_p,
                          top_k=req.top_k, seed=req.seed)


async def _consume(handle, rec: Record, start: float, close: float):
    loop = asyncio.get_running_loop()
    try:
        async for tok in handle:
            now = loop.time()
            if rec.first is None:
                rec.first = now
            rec.last = now
            rec.tokens.append(int(tok))
            rec.times.append(now)
            if start <= now <= close:
                rec.in_window += 1
        rec.completion = await handle.result()
    except Exception as e:  # the request failed: it counts as missing
        rec.error = f"{type(e).__name__}: {e}"


class Window:
    """One measured window over a running frontend (anything with an
    async `submit(prompt, max_new, sampling=)` returning a streaming
    handle).  `start` is the loop time the window opens at, `lead`
    seconds after the loop begins sending."""

    def __init__(self, frontend, seconds: float, grace: float,
                 lead: float = 0.0):
        self.fe = frontend
        self.seconds = seconds
        self.grace = grace
        self.lead_s = lead
        # every request due inside the window
        self.records: list[Record] = []
        # the lead-in's requests, due before the window opens
        self.lead: list[Record] = []
        self.tasks: list[asyncio.Task] = []
        self.start = self.close = None

    def begin(self):
        """Begin the lead-in now; the window opens when it ends."""
        self.start = asyncio.get_running_loop().time() + self.lead_s
        self.close = self.start + self.seconds

    async def send(self, req, due: float):
        """Submit `req`, due at loop time `due`, and stream its reply
        into a new record."""
        loop = asyncio.get_running_loop()
        rec = Record(req=req, due=due)
        (self.records if due >= self.start else self.lead).append(rec)
        rec.sent = loop.time()
        handle = await self.fe.submit(list(req.prompt), req.max_new,
                                      sampling=sampling_of(req))
        task = loop.create_task(_consume(handle, rec, self.start,
                                         self.close))
        self.tasks.append(task)
        return rec, task

    async def drain(self):
        """Wait for every submitted request, up to `grace` seconds past
        the close; cancel what is left."""
        loop = asyncio.get_running_loop()
        left = self.close + self.grace - loop.time()
        if self.tasks:
            _, pending = await asyncio.wait(self.tasks,
                                            timeout=max(left, 0.0))
            for t in pending:
                t.cancel()
            if pending:
                await asyncio.wait(pending)
        # the records outlive the run; the serving stack must not
        self.fe = None

    # ------------------------------------------------------- summaries

    @property
    def served(self) -> list[Record]:
        """Every request sent, the lead-in's too: all whose tokens can
        fall inside the window."""
        return self.lead + self.records

    def lateness(self) -> list[float]:
        """How late the generator submitted each request, in seconds."""
        return [r.sent - r.due for r in self.served]
