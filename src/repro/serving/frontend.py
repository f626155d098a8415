"""Async request-lifecycle frontend over the fused serving engine.

`ServingFrontend` turns the tick-driven `ContinuousBatcher` into an
asyncio service: callers `await submit(...)` and get back a
`RequestHandle` they can stream token-by-token (`async for tok in
handle`), await to completion (`await handle.result()`), or cancel at any
lifecycle stage.  One background task owns the engine and loops

    drain intake -> batcher.step() (ONE fused dispatch) -> pump emissions

yielding to the event loop between ticks, so streams, new submissions and
cancellations interleave with decode without threads (pass
``tick_in_thread=True`` to run each tick via ``asyncio.to_thread`` when
device ticks are long enough to starve the loop).

Lifecycle semantics:

- **backpressure**: the intake queue is bounded (``max_pending``);
  `submit` suspends the caller until the engine drains, instead of
  buffering unboundedly — the edge-serving posture: shed load at the
  front, don't fall over at the back.
- **streaming**: tokens are surfaced from each tick's emissions in
  arrival order; a preempted-and-resumed request never re-streams tokens
  it already delivered (the scheduler preserves emitted tokens across
  preemption, and the handle tracks its high-water mark).
- **cancellation**: `handle.cancel()` works mid-intake, mid-queue,
  mid-prefill and mid-decode; the scheduler reclaims the slot and every
  non-shared page immediately, no Completion is recorded, the token
  stream ends, and `result()` raises `asyncio.CancelledError`.
- **priority / deadlines**: ``priority=`` and ``deadline_ms=`` ride on
  the scheduler's `Request` and feed the lazy-allocation preemption
  policy (lowest priority, then latest/absent deadline, then most recent
  admission is preempted first).  Deadlines are converted to absolute
  loop-clock milliseconds and are ENFORCED: between ticks the engine
  task cancels every queued or running request whose deadline already
  passed, reclaiming its slot and pages, and fails its handle with
  `DeadlineExpired` — no tick is spent on tokens nobody will wait for.
- **best-of-n**: ``best_of=n`` prefills the prompt once, forks n-1
  copy-on-write branches in the paged engine, and streams ONLY the
  winning branch (highest cumulative logprob) — the stream stays quiet
  while branches race and delivers the winner's tokens at completion.
- **status**: ``handle.status`` walks "queued" -> "running" -> "done"
  (or "cancelled" / "error" / "migrated"); a preempted request shows
  "queued" again until it is re-admitted.
- **migration**: `extract(rid)` pulls a live request out as a
  `RecomputeRecipe` and `inject(recipe)` admits one — the
  `ReplicaRouter`'s transport for moving requests between replicas
  token-identically (see serving/router.py); a migrated-away handle
  terminates with status "migrated".
- **latency**: every completion books TTFT (arrival to first streamed
  token) and TPOT (mean inter-token time) samples; `stats()` reports
  their p50/p95.
- **telemetry**: the frontend records into the batcher's
  `serving.telemetry.Telemetry` sink when the `ServingConfig` carries
  one (a private sink is created otherwise, so latency stats always
  work): `serving_ttft_ms`/`serving_tpot_ms` histograms,
  `requests_intake_total` and `requests_total{outcome=...}` counters —
  every handle terminates in exactly one outcome (completed / cancelled
  / expired / failed / migrated), so intake == sum of outcomes — and
  the request-lifecycle span events it owns: "intake", "migrate_in" /
  "migrate_out" (the router boundary) and the terminal event, deduped
  against the batcher side via `Telemetry.last_event`.  With a
  profiling sink on the stack, each loop turn opens the host spans
  ``frontend.intake`` (cancels, deadline expiry, intake drain) and
  ``frontend.pump`` (streaming the tick's tokens).

Invalid requests (empty prompt, prompt >= capacity, infeasible page
budget, ...) fail their OWN handle — `result()` re-raises the
scheduler's ValueError — and never poison the intake batch.
"""
from __future__ import annotations

import asyncio

from repro.serving.sampling import SamplingParams
from repro.serving.scheduler import (Completion, DeadlineExpired,
                                     RecomputeRecipe, Request)
from repro.serving.telemetry import (NULL_SPAN, TERMINAL_EVENTS,
                                     Telemetry, percentile)

_END = object()  # stream terminator sentinel

# terminal outcome (the requests_total label) -> lifecycle span event
_OUTCOME_EVENTS = {"completed": "finished", "cancelled": "cancelled",
                   "expired": "expired", "failed": "failed",
                   "migrated": "migrate_out"}


class RequestHandle:
    """A live handle on one submitted request (created by
    `ServingFrontend.submit`, not directly)."""

    # set by ServingFrontend.inject on a migrated-in handle: the recipe
    # to admit through the recompute-resume path instead of plain submit
    _recipe: RecomputeRecipe | None = None

    def __init__(self, frontend: "ServingFrontend", rid: int,
                 request: Request):
        self.rid = rid
        self.request = request
        self.status = "queued"
        self.completion: Completion | None = None
        self.error: Exception | None = None
        self._frontend = frontend
        self._stream: asyncio.Queue = asyncio.Queue()
        self._finished = asyncio.Event()
        self._sent = 0  # tokens already pushed to the stream
        self._t0 = asyncio.get_running_loop().time()  # arrival (loop clock)
        self._t_first: float | None = None          # first streamed token

    # ------------------------------------------------------- consumer API

    def done(self) -> bool:
        """True once the request reached a terminal state (done /
        cancelled / error)."""
        return self._finished.is_set()

    def cancel(self) -> bool:
        """Drop the request at whatever stage it is in; its slot and pages
        are reclaimed immediately.  Returns False if it already reached a
        terminal state."""
        return self._frontend._cancel(self)

    async def result(self) -> Completion:
        """Wait for the terminal state; returns the Completion, re-raises
        the submit-time error, or raises CancelledError if cancelled."""
        await self._finished.wait()
        if self.error is not None:
            raise self.error
        if self.completion is None:
            raise asyncio.CancelledError(f"request {self.rid} cancelled")
        return self.completion

    def __aiter__(self):
        return self

    async def __anext__(self):
        tok = await self._stream.get()
        if tok is _END:
            raise StopAsyncIteration
        return tok

    # ------------------------------------------------- frontend plumbing

    def _push(self, emitted: list):
        if len(emitted) > self._sent and self._t_first is None:
            self._t_first = asyncio.get_running_loop().time()
        for tok in emitted[self._sent:]:
            self._stream.put_nowait(tok)
        self._sent = max(self._sent, len(emitted))

    def _finish(self, completion: Completion):
        self._push(completion.tokens)
        self.completion = completion
        self.status = "done"
        self._frontend._record_latency(self, completion)
        self._frontend._record_outcome(self, "completed")
        self._finished.set()
        self._stream.put_nowait(_END)

    def _fail(self, error: Exception):
        self.error = error
        self.status = "error"
        self._frontend._record_outcome(
            self, "expired" if isinstance(error, DeadlineExpired)
            else "failed")
        self._finished.set()
        self._stream.put_nowait(_END)

    def _cancelled(self):
        self.status = "cancelled"
        self._frontend._record_outcome(self, "cancelled")
        self._finished.set()
        self._stream.put_nowait(_END)

    def _detach(self):
        """The request migrated to another replica: this handle's stream
        ends (the router's wrapper handle keeps delivering from the
        destination frontend) and its terminal status records why."""
        self.status = "migrated"
        self._frontend._record_outcome(self, "migrated")
        self._finished.set()
        self._stream.put_nowait(_END)


class ServingFrontend:
    """Asyncio streaming frontend over a batcher (`ContinuousBatcher`;
    anything with submit/step/cancel/slot_req/slot_state/done works).

        batcher = ContinuousBatcher(cfg, params, ServingConfig(
            cache_layout="paged", allocation="lazy"))
        async with ServingFrontend(batcher, max_pending=32) as fe:
            handle = await fe.submit(prompt, max_new=64, priority=1,
                                     deadline_ms=2000)
            async for tok in handle:
                ...
            completion = await handle.result()
    """

    def __init__(self, batcher, *, max_pending: int = 64,
                 tick_in_thread: bool = False):
        self.batcher = batcher
        self.max_pending = max_pending
        self.tick_in_thread = tick_in_thread
        self._intake: asyncio.Queue = asyncio.Queue(maxsize=max_pending)
        self._handles: dict[int, RequestHandle] = {}
        self._cancels: list = []  # rids to drop, applied between ticks
        self._next_rid = 0
        self._done_seen = len(batcher.done)
        self._task: asyncio.Task | None = None
        # the stack-wide metrics/tracing sink: shared with the batcher
        # and engines when the ServingConfig carries one, private
        # otherwise — the frontend only records at request-lifecycle
        # boundaries (intake, first token, terminal outcome), never per
        # tick, so a private sink costs nothing on the engine hot path
        self.telemetry = getattr(batcher, "telemetry", None) or Telemetry()
        # the stack's own sink (None without one) opens the loop's host
        # spans; a private sink never profiles
        self._spans = getattr(batcher, "telemetry", None)

    # ---------------------------------------------------------- lifecycle

    def start(self):
        """Spawn the engine-driving task on the running event loop."""
        if self._task is None:
            self._task = asyncio.get_running_loop().create_task(self._run())

    async def stop(self):
        """Stop the engine task.  Pending work stays in the batcher; a
        later start() resumes it."""
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        self._apply_cancels()  # reclaim pages of late cancellations

    async def __aenter__(self):
        self.start()
        return self

    async def __aexit__(self, *exc):
        await self.stop()

    # ------------------------------------------------------------- intake

    async def submit(self, prompt, max_new: int, *,
                     sampling: SamplingParams | None = None,
                     priority: int = 0,
                     deadline_ms: float | None = None,
                     best_of: int = 1) -> RequestHandle:
        """Enqueue one request; suspends (backpressure) while
        ``max_pending`` submissions are already waiting for the engine.
        ``best_of=n`` races n copy-on-write branches off one prefill and
        resolves the handle with the winner (paged layouts only)."""
        rid = self._next_rid
        self._next_rid += 1
        deadline = None
        if deadline_ms is not None:
            deadline = asyncio.get_running_loop().time() * 1e3 + deadline_ms
        req = Request(rid=rid, prompt=list(prompt), max_new=max_new,
                      sampling=sampling, priority=priority,
                      deadline=deadline, best_of=best_of)
        handle = RequestHandle(self, rid, req)
        self._handles[rid] = handle
        self.telemetry.counter("requests_intake_total").inc()
        self.telemetry.trace(rid, "intake", prompt=len(req.prompt))
        try:
            await self._intake.put(handle)
        except asyncio.CancelledError:
            # the submitter gave up mid-backpressure (e.g. wait_for
            # timeout): the never-enqueued handle must not linger
            self._handles.pop(rid, None)
            handle._cancelled()
            raise
        return handle

    async def inject(self, recipe: RecomputeRecipe) -> RequestHandle:
        """Admit a RecomputeRecipe (router migration/failover — or a
        router's initial placement, which is just a recipe with no
        emitted tokens).  The rid is the recipe's: the router keeps rids
        globally unique across replicas.  Replayed tokens are never
        re-streamed (`_sent` starts past them); admission goes through
        the batcher's recompute-resume path, so the continuation is
        token-identical to the unmigrated run.  Backpressure applies as
        in `submit`."""
        req = recipe.to_request()
        handle = RequestHandle(self, recipe.rid, req)
        handle._recipe = recipe
        handle._sent = len(recipe.emitted)
        self._handles[recipe.rid] = handle
        # keep this frontend's own rid counter clear of injected rids
        self._next_rid = max(self._next_rid, recipe.rid + 1)
        self.telemetry.counter("requests_intake_total").inc()
        self.telemetry.trace(recipe.rid, "intake",
                             prompt=len(recipe.prompt))
        if recipe.emitted:
            # migrated in mid-generation (a fresh router placement is
            # just an intake): the span marks where the request landed
            self.telemetry.trace(recipe.rid, "migrate_in",
                                 replayed=len(recipe.emitted))
        try:
            await self._intake.put(handle)
        except asyncio.CancelledError:
            self._handles.pop(recipe.rid, None)
            handle._cancelled()
            raise
        return handle

    def extract(self, rid: int) -> RecomputeRecipe | None:
        """Pull a live request OUT of this frontend as a RecomputeRecipe
        (the other half of `inject`).  The request leaves the batcher
        entirely (running requests are host-side preempted first, so
        their emitted tokens ride along); the local handle flushes any
        not-yet-streamed tokens and terminates with status "migrated".
        Returns None when the rid is not migratable here: unknown,
        already terminal, or just completed (the completion is left for
        `_pump` to resolve normally).  Must run on the event-loop thread
        between ticks — the router calls it from its dispatcher task."""
        handle = self._handles.get(rid)
        if handle is None or handle.done():
            return None
        recipe = self.batcher.export_recipe(rid)
        if recipe is None:
            if any(c.rid == rid for c in self.batcher.done[self._done_seen:]):
                return None  # raced completion: _pump will finish it
            # still in intake, never admitted: recipe straight off the
            # request (the detached handle is skipped at drain time)
            recipe = RecomputeRecipe.from_request(
                handle.request, self.batcher.default_sampling)
        self._handles.pop(rid, None)
        if recipe.emitted:
            handle._push(list(recipe.emitted))
        handle._detach()
        return recipe

    def resident(self) -> int:
        """Open handles on this frontend (queued + running + in intake) —
        the router's load signal."""
        return len(self._handles)

    def _cancel(self, handle: RequestHandle) -> bool:
        if handle.done():
            return False
        # the handle's stream terminates NOW; the batcher-side drop
        # (queue removal / slot + page reclaim) is applied by the engine
        # task between ticks, so a cancel can never mutate scheduler
        # state while a tick runs in a worker thread (tick_in_thread)
        self._cancels.append(handle.rid)
        handle._cancelled()
        self._handles.pop(handle.rid, None)
        if self._task is None:
            self._apply_cancels()  # no engine task: reclaim right here
        return True

    def _apply_cancels(self):
        while self._cancels:
            self.batcher.cancel(self._cancels.pop())

    def _expire_deadlines(self):
        """Auto-cancel every queued or running request whose deadline has
        passed and fail its handle with DeadlineExpired (slot + pages are
        reclaimed by the batcher-side cancel)."""
        expire = getattr(self.batcher, "expire_deadlines", None)
        if expire is None:
            return
        now = asyncio.get_running_loop().time() * 1e3
        for rid in expire(now):
            handle = self._handles.pop(rid, None)
            if handle is not None and not handle.done():
                handle._fail(DeadlineExpired(
                    f"request {rid}: deadline passed before completion"))

    def _admit(self, handle: RequestHandle) -> bool:
        if handle.done():
            return False  # cancelled (or migrated) while still in intake
        try:
            if handle._recipe is not None and handle._recipe.emitted:
                # migrated-in mid-generation: recompute-resume admission
                self.batcher.submit_recipe(handle._recipe)
            else:
                self.batcher.submit([handle.request])
        except ValueError as e:
            # an invalid request fails its own handle only
            handle._fail(e)
            self._handles.pop(handle.rid, None)
            return False
        return True

    def _drain(self) -> int:
        """Move intake into the batcher queue — but only while the batcher
        holds fewer than max_pending waiters, so total admitted-but-unrun
        backlog stays bounded and submit() keeps suspending under
        sustained overload (the intake bound alone would reset each
        tick)."""
        n = 0
        while len(self.batcher.queue) < self.max_pending:
            try:
                handle = self._intake.get_nowait()
            except asyncio.QueueEmpty:
                break
            n += self._admit(handle)
        return n

    # ------------------------------------------------------------- status

    @property
    def ttft_ms(self) -> list:
        """Raw TTFT samples (ms) — a view of the `serving_ttft_ms`
        histogram's retained samples (compatibility with the pre-telemetry
        list attribute)."""
        h = self.telemetry.histograms.get("serving_ttft_ms")
        return h.samples if h is not None else []

    @property
    def tpot_ms(self) -> list:
        h = self.telemetry.histograms.get("serving_tpot_ms")
        return h.samples if h is not None else []

    def _record_latency(self, handle: RequestHandle,
                        completion: Completion):
        """Book TTFT/TPOT for a completed request (loop-clock ms) into
        the telemetry histograms.  A handle that streamed no token on
        THIS frontend (a migrated-in request whose replayed tokens
        covered everything it would ever deliver here) records nothing —
        the samples describe tokens this frontend actually surfaced."""
        if handle._t_first is None:
            return
        now = asyncio.get_running_loop().time()
        self.telemetry.histogram("serving_ttft_ms").observe(
            (handle._t_first - handle._t0) * 1e3)
        n_after_first = handle._sent - (len(handle._recipe.emitted)
                                        if handle._recipe else 0) - 1
        if n_after_first > 0:
            self.telemetry.histogram("serving_tpot_ms").observe(
                (now - handle._t_first) * 1e3 / n_after_first)

    def _record_outcome(self, handle: RequestHandle, outcome: str):
        """Book a handle's terminal outcome: the
        `requests_total{outcome=...}` counter ALWAYS increments (the
        drain invariant: intake == sum over outcomes), while the
        terminal span event is deduped against the batcher side —
        whichever of the two shares the sink and records first wins,
        so every rid carries exactly one terminal event."""
        tel = self.telemetry
        tel.counter("requests_total").inc(outcome=outcome)
        if tel.last_event(handle.rid) not in TERMINAL_EVENTS:
            tel.trace(handle.rid, _OUTCOME_EVENTS[outcome])

    @staticmethod
    def _pct(samples: list, q: float):
        # compatibility shim: the percentile math lives in
        # serving.telemetry (shared with the router and histograms)
        return percentile(samples, q)

    def stats(self) -> dict:
        """Operational snapshot of the batcher under this frontend —
        mesh-aware: cache bytes are reported globally AND per device, and
        occupancy per slot group (one group per data shard), so an
        operator sees both total state and the per-chip HBM/skew picture.
        Latency percentiles (TTFT = time to first streamed token, TPOT =
        mean inter-token time) cover requests COMPLETED here; both are
        None until the first completion.  A compatibility view over
        `Telemetry.snapshot()` — the full registry rides under
        ``"telemetry"``."""
        b = self.batcher
        mesh = getattr(b, "mesh", None)
        snap = self.telemetry.snapshot()
        hists = self.telemetry.histograms
        ttft = hists.get("serving_ttft_ms")
        tpot = hists.get("serving_tpot_ms")
        return {
            "n_slots": b.n_slots,
            "mesh": (None if mesh is None
                     else dict(zip(mesh.axis_names, mesh.devices.shape))),
            "slot_groups": getattr(b, "n_slot_groups", 1),
            "group_occupancy": [float(x) for x in b.group_occupancy()],
            "cache_bytes_global": b.cache_nbytes(),
            "cache_bytes_per_device": b.cache_nbytes_per_device(),
            "decode_ticks": b.decode_ticks,
            "decode_dispatches": b.decode_dispatches,
            "preemptions": b.preemptions,
            "pending": len(b.queue),
            "completed": ttft.count if ttft is not None else 0,
            "ttft_p50_ms": ttft.percentile(50) if ttft is not None else None,
            "ttft_p95_ms": ttft.percentile(95) if ttft is not None else None,
            "tpot_p50_ms": tpot.percentile(50) if tpot is not None else None,
            "tpot_p95_ms": tpot.percentile(95) if tpot is not None else None,
            "telemetry": snap,
        }

    # -------------------------------------------------------------- loop

    def _busy(self) -> bool:
        b = self.batcher
        return bool(b.queue) or any(r is not None for r in b.slot_req)

    async def _run(self):
        tel = self._spans
        try:
            while True:
                with (tel.span("frontend.intake") if tel is not None
                      else NULL_SPAN):
                    self._apply_cancels()
                    self._expire_deadlines()
                    self._drain()
                if not self._busy():
                    # idle: park until the next submission arrives
                    handle = await self._intake.get()
                    if not self._admit(handle):
                        continue
                if self.tick_in_thread:
                    await asyncio.to_thread(self.batcher.step)
                else:
                    self.batcher.step()
                with (tel.span("frontend.pump") if tel is not None
                      else NULL_SPAN):
                    self._apply_cancels()  # cancels raced the tick
                    self._pump()
                # one tick per loop turn: let consumers interleave
                await asyncio.sleep(0)
        except asyncio.CancelledError:
            raise
        except Exception as e:
            # an engine error must fail every open handle loudly, not
            # leave their streams/results hanging on a dead task
            for handle in list(self._handles.values()):
                if not handle.done():
                    handle._fail(e)
            self._handles.clear()
            raise

    def _pump(self):
        """Surface this tick's emissions: stream new tokens from live
        slots, resolve fresh completions, and mark preempted requests as
        queued again."""
        b = self.batcher
        running = set()
        for s in range(b.n_slots):
            req, st = b.slot_req[s], b.slot_state[s]
            if req is None:
                continue
            handle = self._handles.get(req.rid)
            if handle is None or handle.done():
                continue
            running.add(req.rid)
            handle.status = "running"
            if handle.request.best_of == 1:
                # best-of handles stay quiet while branches race — only
                # the winner streams, in one burst at completion
                handle._push(st["emitted"])
        finished = []
        for c in b.done[self._done_seen:]:
            handle = self._handles.get(c.rid)
            if handle is not None and not handle.done():
                handle._finish(c)
                finished.append(c.rid)
        self._done_seen = len(b.done)
        for rid in finished:
            self._handles.pop(rid, None)
        for rid, handle in self._handles.items():
            if (handle.status == "running" and rid not in running
                    and not handle.done()):
                handle.status = "queued"  # preempted back to the queue
