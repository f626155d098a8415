"""Host-side serving POLICY layer: admission, budgets, pages, accounting.

The serving stack is split in two:

- this module decides WHO runs: `Request` intake and validation, FIFO
  admission, per-request token budgets, and shared-until-written page
  ownership (`PageAllocator`: refcounted sharing, block-table forking,
  the copy-on-write transition — prompt-prefix sharing is one special
  case of it), slot assignment and release, completion records and
  utilization metrics.  Nothing here touches a device buffer.
- serving/engine.py decides HOW: each engine owns the device-resident
  decode state (stacked dense rings, the shared page pool + block tables,
  or the seed per-slot caches) and the jitted step functions, and
  guarantees one fused dispatch advances the whole slot pool by one token
  per tick.

Decoding policy is per request: `Request.sampling` (a
sampling.SamplingParams) selects greedy argmax (temperature 0, the
default) or temperature / top-k / top-p stochastic decode.  Sampling runs
INSIDE the fused dispatch — the policy layer only ships per-slot arrays
(base PRNG key, emit index, temperature, top_k, top_p) with each tick, so
sampled decode costs exactly one dispatch per tick and a request's tokens
are reproducible from its seed on every engine (dense, paged, per-slot).

Engine-level semantics (`ContinuousBatcher`, the fused engine):

  - every slot holds an independent sequence with its own position counter:
    one jitted dispatch advances the WHOLE pool by one token per engine
    tick, independent of n_slots;
  - a finished slot's lanes are reset by index inside the same dispatch
    (no host-side re-init_cache on refill);
  - prompt tokens take a chunked prefill fast path: blocks of prompt tokens
    are written into the slot's cache lanes in one call each, instead of
    being decoded one at a time.  Block sizes are power-of-two bucketed
    (bounded set of compiled shapes) and capped so a block never wraps a
    ring cache past entries its own earlier tokens still attend to; past
    the ring boundary prefill falls back to exact token-by-token feeding.

Cache layouts (`cache_layout=` on the fused engine):

  - "dense" (default): one (n_slots, capacity, KV, hd) ring per layer —
    every slot owns worst-case `capacity` entries for its whole lifetime;
  - "paged": ONE shared (n_pages, KV, page_size, hd) pool per layer plus
    per-slot block tables of page ids (vLLM-style).  A `PageAllocator`
    owns page lifetime host-side; a request whose worst case can NEVER
    fit the pool is rejected at submit() instead of stalling the queue
    head forever.  Pages are SHARED UNTIL WRITTEN: requests sharing a
    common prompt prefix refcount the same pages (with chunked prefill
    on pure-attention archs the sharer also SKIPS prefilling the shared
    tokens), and `Request.best_of=n` forks n-1 branches off one prefill
    whose block tables reference every prompt page — a slot about to
    write a page other holders still reference first copies it
    (in-dispatch, fused with the token scatter) and repoints only its
    own block-table entry.  Sharing turns itself off when the logical
    ring can wrap (a wrapped ring overwrites shared entries).  Recurrent
    archs (mamba2 / rwkv6) keep O(1) dense state; hybrid pages only its
    shared attention leaves.
    `kernel="pallas"` swaps the paged decode attention read for the
    Pallas paged-attention kernel (page tiles streamed through the block
    table in-kernel instead of an XLA ring gather); "xla" stays the
    default and the equivalence oracle.

Page admission policy (`allocation=` on the paged layout):

  - "worst_case" (default): admission reserves ceil((prompt + budget) /
    page_size) pages up front, so a request runs only when its whole
    sequence is guaranteed to fit — the queue stalls (FIFO) on pool
    exhaustion and admission resumes as finishing slots release pages;
  - "lazy": admission reserves only the prompt's pages and each decode
    page is acquired on demand when a slot's position crosses a page
    boundary.  On pool exhaustion the scheduler PREEMPTS the most
    preemptible running request — lowest `Request.priority` first, then
    latest/absent deadline, then most recently admitted — releasing its
    slot and non-shared pages and requeuing it at the queue head WITH its
    generated tokens, so the resume is a prefill of prompt + emitted
    (no token is ever re-sampled) and the completion is token-for-token
    what an unpreempted run produces.  Anti-thrash: a RESUME is admitted
    at its remaining worst case, so a preempted request comes back only
    when it can run to completion — it never grows again, never
    re-triggers preemption, and pays its recompute at most once per
    displacement instead of ping-ponging with the request that displaced
    it.  Preemption is host-side policy only: no extra device dispatch,
    the fused tick stays at 1.00 dispatch/tick.

Lifecycle controls shared by both layouts: `preempt(rid)` force-requeues
a running request through the same resume path, and `cancel(rid)` drops a
request at any stage (queued, mid-prefill, mid-decode), reclaiming its
slot and pages immediately and recording no Completion.

`PerSlotBatcher` drives the seed engine — one jitted batch-1 call per
active slot per tick — as the equivalence baseline and the bench's
"before" side.  Both batchers share intake, accounting and completion
semantics: a sequence (prompt + completion) occupies at most `capacity`
cache entries, and empty prompts are rejected unless a `bos_token` is
configured.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Iterable

import numpy as np

from repro.models.config import ModelConfig
from repro.serving.config import ServingConfig
from repro.serving.engine import DenseEngine, PagedEngine, PerSlotEngine
from repro.serving.sampling import (GREEDY, SamplingParams, SlotSampling,
                                    branch_key, key_zeros)
from repro.serving.telemetry import NULL_SPAN, TERMINAL_EVENTS


class DeadlineExpired(Exception):
    """A queued or running request's deadline passed before it finished:
    the scheduler cancelled it (slot + pages reclaimed) instead of
    burning ticks on tokens nobody will wait for."""


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list           # token ids (ints); audio: list of tuples
    max_new: int
    # decode policy; None falls back to the batcher's default_sampling
    # (greedy unless configured otherwise)
    sampling: SamplingParams | None = None
    # preemption policy inputs (lazy paged allocation): a LOWER priority
    # is preempted first; among equal priorities the request with the
    # latest (or no) deadline goes first.  Deadlines are opaque floats —
    # only their ordering matters (the async frontend passes absolute
    # milliseconds derived from deadline_ms)
    priority: int = 0
    deadline: float | None = None
    # best-of-n decoding (paged pure-attention layouts only): prefill the
    # prompt ONCE, fork n-1 extra branches that share every prompt page
    # (copy-on-write on divergence), decode all n, and record only the
    # winner by cumulative token logprob.  Branch b's sampling noise is
    # keyed by branch_key(seed, b), so each branch is token-identical to
    # an independent request with SamplingParams(seed=seed, branch=b)
    best_of: int = 1


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: list
    prompt_len: int
    # top1-top2 score gap per emitted token (raw logits when greedy,
    # Gumbel-perturbed scores when sampled): near-zero entries mark
    # numerical ties, where differently-compiled variants of the same
    # math may legitimately emit different tokens
    margins: list = dataclasses.field(default_factory=list)
    # per-token log-probability of the emitted token under the RAW
    # (unscaled) model distribution; best-of-n ranks branches by its sum
    logprobs: list = dataclasses.field(default_factory=list)


def completions_equivalent(a, b, tie_tol: float = 1e-3) -> bool:
    """Token-for-token equality of two completion sets, tolerating argmax
    ties: sequences may first diverge only at a step whose margin (in
    either engine) is below `tie_tol`; past a tie the trajectories
    legitimately separate, so comparison stops for that sequence."""
    by_a = {c.rid: c for c in a}
    by_b = {c.rid: c for c in b}
    if set(by_a) != set(by_b):
        return False
    for rid, ca in by_a.items():
        cb = by_b[rid]
        if ca.prompt_len != cb.prompt_len:
            return False
        for i, (ta, tb) in enumerate(zip(ca.tokens, cb.tokens)):
            if ta != tb:
                ma = ca.margins[i] if i < len(ca.margins) else float("inf")
                mb = cb.margins[i] if i < len(cb.margins) else float("inf")
                if min(ma, mb) > tie_tol:
                    return False
                break  # diverged at a tie — trajectories separate here
        else:
            if len(ca.tokens) != len(cb.tokens):
                return False
    return True


@dataclasses.dataclass(frozen=True)
class RecomputeRecipe:
    """The portable form of an in-flight request: everything a DIFFERENT
    replica needs to continue it token-identically, and nothing else.

    This is the PR 5 preempt/resume contract lifted onto the wire: prompt
    + already-emitted tokens + the effective sampling params (seed,
    branch).  The destination chunk-prefills prompt + emitted[:-1],
    re-feeds the last emitted token, and its next sample folds the SAME
    noise key (branch_key(seed, branch) fold emit-index) — nothing is
    re-sampled, the emit index never rewinds, so greedy streams lose no
    tokens and sampled streams stay seed-reproducible across migration.

    Shipping this instead of raw KV pages is the router's whole
    communication story: a recipe is a few bytes per token (`nbytes`)
    where a KV page transfer is 2*n_layers*n_kv_heads*head_dim*dtype
    bytes per token — orders of magnitude apart (`router_overhead_bytes`
    accounts both sides per link).

    `margins`/`logps` ride along so the migrated Completion keeps full
    fidelity (tie-tolerant parity checks, best-of ranking)."""

    rid: int
    prompt: tuple
    max_new: int
    sampling: SamplingParams | None = None
    priority: int = 0
    deadline: float | None = None
    best_of: int = 1
    emitted: tuple = ()
    margins: tuple = ()
    logps: tuple = ()

    def nbytes(self) -> int:
        """Wire-size estimate: int32 token ids (prompt + emitted), f32
        margin + f32 logprob per emitted token, plus a fixed scalar
        header (rid, max_new, priority, deadline, best_of, sampling
        seed/branch/temperature/top_k/top_p and framing)."""
        return (4 * (len(self.prompt) + len(self.emitted))
                + 8 * len(self.emitted) + 72)

    def to_request(self) -> Request:
        return Request(rid=self.rid, prompt=list(self.prompt),
                       max_new=self.max_new, sampling=self.sampling,
                       priority=self.priority, deadline=self.deadline,
                       best_of=self.best_of)

    @classmethod
    def from_request(cls, req: Request,
                     default_sampling: SamplingParams | None = None,
                     emitted=(), margins=(), logps=()) -> "RecomputeRecipe":
        """Capture `req` (queued or running) as a recipe.  The EFFECTIVE
        sampling is pinned (req.sampling, else the source replica's
        default): the destination may run a different default_sampling,
        and migration must not change the request's decode policy."""
        return cls(rid=req.rid, prompt=tuple(req.prompt),
                   max_new=req.max_new,
                   sampling=req.sampling or default_sampling,
                   priority=req.priority, deadline=req.deadline,
                   best_of=req.best_of, emitted=tuple(emitted),
                   margins=tuple(margins), logps=tuple(logps))


class PageAllocator:
    """Host-side manager of the shared KV page pool.

    Ownership model: a page is SHARED until written.  `share` takes one
    more reference on a live page; `fork` shares a whole block table's
    worth at a branch point (best-of-n forking); `ensure_private` is the
    copy-on-write transition — a holder about to WRITE into a page checks
    it, and if other holders remain it gives up its reference and gets a
    private replacement page instead (the engine then copies the page's
    contents in-dispatch and repoints only that holder's block-table
    entry).  Prompt-prefix sharing is the same path: full prompt pages
    are registered under a rolling prefix key (a chain of per-page token
    tuples) and a later request whose prompt starts with the same pages
    `share`s them instead of allocating copies — prefix pages are never
    written past the prompt, so they never reach the CoW transition.

    A page returns to the free list when its refcount reaches zero — a
    shared page therefore survives any one holder finishing as long as
    another still holds it — and its prefix registration is dropped at
    the same moment, so a later lookup can never hand out a reclaimed
    page id.  Page 0 is the reserved null page (idle lanes and
    unallocated block-table entries point at it) and is permanently
    pinned.

    `allocation` records the admission policy the pool is driven under:
    "worst_case" reserves a request's whole-sequence page budget at
    admission; "lazy" reserves only the prompt pages and acquires decode
    pages on demand at page boundaries (pool exhaustion then triggers
    scheduler preemption instead of an admission stall)."""

    def __init__(self, n_pages: int, page_size: int,
                 allocation: str = "worst_case"):
        if n_pages < 2:
            raise ValueError(
                f"n_pages={n_pages}: need at least the null page plus one "
                f"usable page")
        if allocation not in ("worst_case", "lazy"):
            raise ValueError(
                f"allocation={allocation!r}: accepted values are "
                f"('worst_case', 'lazy')")
        self.n_pages = n_pages
        self.page_size = page_size
        self.allocation = allocation
        self._free = list(range(n_pages - 1, 0, -1))  # pop() -> 1, 2, ...
        self.refcount = np.zeros((n_pages,), np.int32)
        self.refcount[0] = 1  # null page: never allocated, never freed
        self._prefix: dict = {}    # chain key -> live page id
        self._page_key: dict = {}  # page id -> chain key (for dereg)
        self.peak_in_use = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        """Allocated pages (null page excluded)."""
        return self.n_pages - 1 - len(self._free)

    def alloc(self) -> int:
        pid = self._free.pop()
        self.refcount[pid] = 1
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return pid

    def share(self, pid: int):
        """Take another reference on a live page (prefix sharing and
        block-table forking both route through here)."""
        assert self.refcount[pid] > 0, f"page {pid} is not live"
        self.refcount[pid] += 1

    def fork(self, pages):
        """Share every page of a block table at a branch point: the new
        branch holds one reference on each, and a write into any of them
        while other holders remain goes through `ensure_private` first."""
        for pid in pages:
            self.share(pid)

    def ensure_private(self, pid: int, reserved: int | None = None):
        """Copy-on-write transition for a holder about to WRITE page
        `pid`: returns ``(page, copied)``.  Sole holder -> (pid, False),
        write in place.  Other holders remain -> this holder gives up its
        reference (the page stays live for them, so no dereg/free edge
        can fire) and receives a private replacement — `reserved` if the
        caller pre-allocated one (worst-case admission), else a fresh
        page — and (new_pid, True) tells the caller to queue the
        in-dispatch page copy and repoint its own block-table entry."""
        assert pid != 0, "the null page is never written"
        assert self.refcount[pid] > 0, f"page {pid} is not live"
        if self.refcount[pid] == 1:
            return pid, False
        new = reserved if reserved is not None else self.alloc()
        self.refcount[pid] -= 1
        return new, True

    def release(self, pid: int):
        if pid == 0:
            return
        self.refcount[pid] -= 1
        assert self.refcount[pid] >= 0, f"page {pid} over-released"
        if self.refcount[pid] == 0:
            key = self._page_key.pop(pid, None)
            if key is not None and self._prefix.get(key) == pid:
                del self._prefix[key]
            self._free.append(pid)

    def lookup_prefix(self, key):
        return self._prefix.get(key)

    def register_prefix(self, key, pid: int):
        """Publish a full prompt page for sharing (first writer wins).  A
        page publishes at most one key — the one `release` withdraws when
        the page is freed; a second key would outlive the page."""
        if key not in self._prefix and pid not in self._page_key:
            self._prefix[key] = pid
            self._page_key[pid] = key


class _BatcherBase:
    """Shared intake / accounting / loop for both batchers.  Device state
    and dispatch live in self.engine (serving/engine.py)."""

    # configuration is keyword-only: the seed signature carried a `greedy`
    # positional (now subsumed by per-request SamplingParams), and silently
    # re-binding old positional call sites would be worse than a TypeError
    def __init__(self, cfg: ModelConfig, params, *, n_slots: int = 4,
                 capacity: int = 256, bos_token: int | None = None,
                 default_sampling: SamplingParams | None = None,
                 telemetry=None):
        assert cfg.num_codebooks == 1, "scheduler covers text archs"
        self.cfg = cfg
        self.params = params
        # serving.telemetry.Telemetry sink, or None — every recording
        # call below is guarded at the call site, so None is a true
        # zero-overhead no-op on the per-tick hot path
        self.telemetry = telemetry
        self.n_slots = n_slots
        self.capacity = capacity
        self.bos_token = bos_token
        self.default_sampling = default_sampling or GREEDY
        self.slot_req: list = [None] * n_slots     # active Request per slot
        self.slot_state: list = [None] * n_slots   # {"emitted", "fed", ...}
        self.queue: list = []
        self.done: list = []
        self.active_slot_steps = 0    # slot-steps that carried a sequence
        self.total_slot_steps = 0     # slot-step capacity offered so far
        self.preemptions = 0          # running requests forced back to queue
        self.decode_ticks = 0         # fused decode ticks driven so far
        self.decode_active_slots = 0  # live slots summed over decode ticks
        # mesh accounting (overridden by mesh-aware batchers): the slot
        # pool splits into n_slot_groups contiguous groups, one per data
        # shard; group_active counts live slots per group per tick
        self.mesh = None
        self.n_slot_groups = 1
        self.group_active = np.zeros((1,), np.int64)
        # preempted requests awaiting re-admission: id(request) ->
        # (emitted, margins); resume prefills prompt + emitted instead of
        # re-sampling anything
        self._resume: dict = {}
        self._admit_seq = 0           # admission order, for victim choice

    # ---------------------------------------------------------- telemetry

    def _trace(self, rid: int, event: str, **attrs):
        """Record a lifecycle transition (no-op without a telemetry
        sink).  Off-hot-path convenience — per-tick code guards inline
        instead so `telemetry=None` allocates nothing per tick."""
        if self.telemetry is not None:
            self.telemetry.trace(rid, event, **attrs)

    # ------------------------------------------------- engine delegation

    @property
    def decode_dispatches(self) -> int:
        return self.engine.decode_dispatches

    @property
    def prefill_dispatches(self) -> int:
        return self.engine.prefill_dispatches

    def cache_nbytes(self) -> int:
        """GLOBAL device bytes of the engine's decode state (all devices)."""
        return self.engine.cache_nbytes()

    def cache_nbytes_per_device(self) -> int:
        """Max addressable decode-state bytes on any one device (== global
        when unsharded) — keeps paged-vs-dense byte ratios meaningful on a
        mesh."""
        return self.engine.cache_nbytes_per_device()

    def group_occupancy(self) -> list:
        """Per-slot-group occupancy (live slot fraction per data shard per
        decode tick) — a skewed list means one shard decodes dead lanes
        while another queues."""
        spg = max(1, self.n_slots // self.n_slot_groups)
        return [self.group_active[g] / max(1, self.decode_ticks * spg)
                for g in range(self.n_slot_groups)]

    # ------------------------------------------------------------- intake

    def submit(self, reqs: Iterable[Request]):
        accepted = []
        for req in reqs:
            if not req.prompt:
                if self.bos_token is None:
                    raise ValueError(
                        f"request {req.rid}: empty prompt — configure "
                        "bos_token to decode from BOS, or send >= 1 token "
                        "(the engine never fabricates a token)")
                req = dataclasses.replace(req, prompt=[self.bos_token])
            if len(req.prompt) >= self.capacity:
                raise ValueError(
                    f"request {req.rid}: prompt of {len(req.prompt)} tokens "
                    f"leaves no room to generate within capacity "
                    f"{self.capacity}")
            if req.max_new < 1:
                raise ValueError(f"request {req.rid}: max_new must be >= 1")
            if req.best_of < 1:
                raise ValueError(f"request {req.rid}: best_of must be >= 1")
            self._admission_check(req)
            accepted.append(req)
        # atomic: a batch with an invalid request enqueues nothing
        self.queue.extend(accepted)
        if self.telemetry is not None:
            for req in accepted:
                self.telemetry.trace(req.rid, "queued",
                                     prompt=len(req.prompt))

    def _admission_check(self, req: Request):
        """Hook: layout-specific submit-time feasibility check."""

    def _budget(self, req: Request) -> int:
        """Tokens this request may emit: the whole sequence (prompt +
        completion) must fit in `capacity` cache entries."""
        return min(req.max_new, self.capacity - len(req.prompt))

    def _new_slot_state(self, req: Request, fed0: int = 0) -> dict:
        sp = req.sampling or self.default_sampling
        self._admit_seq += 1
        return {"emitted": [], "fed": fed0, "margins": [], "logps": [],
                "sp": sp, "admit_seq": self._admit_seq,
                # decode ticks run since this (re)admission — a slot is
                # preemption-eligible only past min_quantum of them
                "ran": 0,
                # base PRNG key, derived once per request from its seed
                # and branch index (branch 0 == the plain seed key);
                # greedy requests never consume randomness
                "key": branch_key(sp.seed, sp.branch)
                if sp.temperature > 0 else key_zeros()}

    # ----------------------------------------------------- sampling state

    def _sampling_row(self, s: int) -> SlotSampling:
        """Scalar-leaf SlotSampling for slot s (chunked-prefill dispatch).

        `step` is the request's emit index — the fold_in counter that makes
        token i of a request see the same noise on every engine."""
        st = self.slot_state[s]
        sp = st["sp"]
        return SlotSampling(
            key=st["key"], step=np.int32(len(st["emitted"])),
            temperature=np.float32(sp.temperature),
            top_k=np.int32(sp.top_k), top_p=np.float32(sp.top_p))

    def _sampling_batch(self) -> SlotSampling:
        """Per-slot sampling arrays for one fused decode tick (idle slots
        ride along as greedy don't-cares)."""
        n = self.n_slots
        kz = key_zeros()
        key = np.zeros((n,) + kz.shape, kz.dtype)
        step = np.zeros((n,), np.int32)
        temp = np.zeros((n,), np.float32)
        top_k = np.zeros((n,), np.int32)
        top_p = np.ones((n,), np.float32)
        for s in range(n):
            st = self.slot_state[s]
            if st is None:
                continue
            sp = st["sp"]
            key[s] = st["key"]
            step[s] = len(st["emitted"])
            temp[s] = sp.temperature
            top_k[s] = sp.top_k
            top_p[s] = sp.top_p
        return SlotSampling(key, step, temp, top_k, top_p)

    # ---------------------------------------------------------- lifecycle

    def _finish_if_done(self, s: int):
        req, st = self.slot_req[s], self.slot_state[s]
        if len(st["emitted"]) >= self._budget(req):
            self._complete(req, Completion(
                rid=req.rid, tokens=list(st["emitted"]),
                prompt_len=len(req.prompt),
                margins=list(st["margins"]),
                logprobs=list(st["logps"])))
            self._release_slot(s)
            self.slot_req[s] = None
            self.slot_state[s] = None

    def _complete(self, req: Request, c: Completion):
        """Hook: record a finished sequence (best-of-n group members are
        intercepted by the paged batcher's winner selection)."""
        self.done.append(c)
        self._trace(c.rid, "finished", tokens=len(c.tokens))

    def _release_slot(self, s: int):
        """Hook: layout-specific reclaim when slot s's sequence finishes."""

    def cancel(self, rid: int, *, _outcome: str | None = "cancelled") \
            -> bool:
        """Drop request `rid` at whatever lifecycle stage it is in —
        queued (including preempted-and-requeued), mid-prefill or
        mid-decode.  Its slot and pages are reclaimed immediately and no
        Completion is recorded.  A best-of-n request drops EVERY live
        branch (queued and running members share the rid).  Returns False
        when the rid is unknown (never submitted, already finished, or
        already cancelled).  `_outcome` names the terminal span event to
        trace ("cancelled" / "expired"; None suppresses it — migration
        paths trace their own)."""
        hit = False
        for i in range(len(self.queue) - 1, -1, -1):
            req = self.queue[i]
            if req.rid == rid:
                self.queue.pop(i)
                self._resume.pop(id(req), None)
                hit = True
        for s in range(self.n_slots):
            req = self.slot_req[s]
            if req is not None and req.rid == rid:
                self._release_slot(s)
                self.slot_req[s] = None
                self.slot_state[s] = None
                hit = True
        if hit:
            self._drop_group(rid)
            # skip when a frontend already traced this rid's terminal
            # event (its handle closes before the batcher-side drop)
            if _outcome is not None and self.telemetry is not None \
                    and self.telemetry.last_event(rid) \
                    not in TERMINAL_EVENTS:
                self.telemetry.trace(rid, _outcome)
        return hit

    def _drop_group(self, rid: int):
        """Hook: forget a cancelled best-of-n group's partial results."""

    def expire_deadlines(self, now: float) -> list:
        """Cancel every queued or running request whose deadline has
        already passed (deadlines and `now` are on the same opaque clock
        — the async frontend uses absolute loop milliseconds).  Slots and
        pages are reclaimed immediately and no Completion is recorded;
        the caller fails the expired handles (DeadlineExpired).  Returns
        the expired rids."""
        expired = []
        for req in list(self.queue):
            if req.deadline is not None and req.deadline <= now \
                    and req.rid not in expired:
                expired.append(req.rid)
        for s in range(self.n_slots):
            req = self.slot_req[s]
            if req is not None and req.deadline is not None \
                    and req.deadline <= now and req.rid not in expired:
                expired.append(req.rid)
        for rid in expired:
            self.cancel(rid, _outcome="expired")
        return expired

    # --------------------------------------------------------------- loop

    def step(self):
        """One engine tick.  With a telemetry sink attached, the tick is
        timed and annotated (seconds waiting on the device, active slots,
        dispatches, CoW copies, page growths, preemptions, and the pages
        the Pallas decode dispatch streamed against its block table's)
        and the pool gauge is refreshed; ``telemetry=None`` falls straight
        through to the layout-specific `_step_inner` — zero per-tick
        overhead."""
        tel = self.telemetry
        if tel is None:
            return self._step_inner()
        eng = self.engine
        t0 = tel.now()
        w0 = tel.waited_s
        d0 = eng.decode_dispatches + eng.prefill_dispatches
        a0 = self.decode_active_slots
        c0 = getattr(self, "cow_copies", 0)
        g0 = getattr(self, "page_growths", 0)
        p0 = self.preemptions
        k0 = getattr(eng, "kv_pages", 0)
        kt0 = getattr(eng, "kv_pages_table", 0)
        out = self._step_inner()
        tel.tick(
            t0, tel.now() - t0,
            wait_s=tel.waited_s - w0,
            active=self.decode_active_slots - a0,
            dispatches=eng.decode_dispatches + eng.prefill_dispatches - d0,
            cow_copies=getattr(self, "cow_copies", 0) - c0,
            page_growths=getattr(self, "page_growths", 0) - g0,
            preemptions=self.preemptions - p0,
            kv_pages=getattr(eng, "kv_pages", 0) - k0,
            kv_pages_table=getattr(eng, "kv_pages_table", 0) - kt0)
        alloc = getattr(self, "allocator", None)
        if alloc is not None:
            tel.gauge("pool_pages_in_use").set(alloc.in_use)
        return out

    def run(self, max_steps: int = 10_000):
        """Drive the engine until queue and slots drain (or max_steps).

        Returns (completions finished during THIS call, steps) — a second
        run() on the same batcher reports only its own completions.
        `self.done` keeps the cumulative archive across calls."""
        start = len(self.done)
        steps = 0
        while (self.queue or any(r is not None for r in self.slot_req)) \
                and steps < max_steps:
            self.step()
            steps += 1
        return self.done[start:], steps

    # ------------------------------------------------------------ metrics

    def utilization(self) -> float:
        """Fraction of offered slot-step capacity that carried a sequence.

        Every prompt token counts one active slot-step whether it was fed
        through a decode tick or written by a chunked-prefill block (a
        size-S batch-1 block books S slot-steps of work and S slot-steps
        of offered capacity), so chunked and decode prefill modes report
        consistent figures on the same workload.  (The legacy `steps`
        argument — already ignored and deprecated — is gone: passing it
        is a TypeError.)"""
        return self.active_slot_steps / max(1, self.total_slot_steps)

    def mean_occupancy(self) -> float:
        """Mean fraction of the slot pool holding a live request per
        decode tick — the concurrency the admission policy actually
        sustained (worst-case page reservation caps this well below 1.0
        on an overloaded pool; lazy allocation admits on prompt pages and
        rides closer to full)."""
        return self.decode_active_slots / max(1, self.decode_ticks
                                              * self.n_slots)


_UNSET = object()  # sentinel: distinguishes "kwarg not passed" from None


class ContinuousBatcher(_BatcherBase):
    """Fused slot-batched continuous batching: one jitted dispatch per
    engine tick drives the whole slot pool (see module docstring).

    Construction: ``ContinuousBatcher(cfg, params, ServingConfig(...))``
    is the primary path — all cross-field validation lives in
    `ServingConfig.__post_init__` / `.resolve`.  The historical loose
    kwargs (n_slots=..., cache_layout=..., ...) still work for one
    release through a `DeprecationWarning` shim that packs them into a
    ServingConfig; mixing `config` with legacy kwargs is an error."""

    def __init__(self, cfg: ModelConfig, params,
                 config: ServingConfig | None = None, *,
                 n_slots=_UNSET, capacity=_UNSET, bos_token=_UNSET,
                 prefill_chunk=_UNSET, prefill_mode=_UNSET,
                 use_pallas=_UNSET, cache_layout=_UNSET, page_size=_UNSET,
                 n_pages=_UNSET, share_prefix=_UNSET, kernel=_UNSET,
                 allocation=_UNSET, default_sampling=_UNSET,
                 min_quantum=_UNSET, mesh=_UNSET):
        legacy = {k: v for k, v in dict(
            n_slots=n_slots, capacity=capacity, bos_token=bos_token,
            prefill_chunk=prefill_chunk, prefill_mode=prefill_mode,
            use_pallas=use_pallas, cache_layout=cache_layout,
            page_size=page_size, n_pages=n_pages,
            share_prefix=share_prefix, kernel=kernel,
            allocation=allocation, default_sampling=default_sampling,
            min_quantum=min_quantum, mesh=mesh).items() if v is not _UNSET}
        if legacy:
            if config is not None:
                raise ValueError(
                    f"pass either a ServingConfig or legacy kwargs, not "
                    f"both (got config= plus {sorted(legacy)})")
            warnings.warn(
                "ContinuousBatcher(cfg, params, n_slots=..., ...) legacy "
                "kwargs are deprecated — construct a serving.ServingConfig "
                "and pass ContinuousBatcher(cfg, params, config)",
                DeprecationWarning, stacklevel=2)
            config = ServingConfig(**legacy)
        elif config is None:
            config = ServingConfig()
        sc = config.resolve(cfg)  # model-dependent coercions + validation
        self.config = sc
        super().__init__(cfg, params, n_slots=sc.n_slots,
                         capacity=sc.capacity, bos_token=sc.bos_token,
                         default_sampling=sc.default_sampling,
                         telemetry=sc.telemetry)
        self.cache_layout = sc.cache_layout
        self.allocation = sc.allocation
        self.prefill_mode = sc.prefill_mode
        self.prefill_chunk = sc.prefill_chunk
        # minimum-run quantum: a freshly admitted/resumed request cannot
        # be chosen as a preemption victim until it has run this many
        # decode ticks (0 = off) — high-priority arrival bursts can't
        # starve a victim before its first page of progress
        self.min_quantum = sc.min_quantum
        # best-of-n fork bookkeeping: live groups by parent rid, archived
        # per-branch completions (group_results), page-sharing counters
        self._groups: dict = {}
        self.group_results: dict = {}
        self._cow_reserve: list = [[] for _ in range(sc.n_slots)]
        self.cow_copies = 0         # in-dispatch CoW page copies queued
        self.fork_shared_pages = 0  # pages shared across all forks
        self.page_growths = 0       # lazy on-demand decode pages acquired
        if sc.cache_layout == "dense":
            self.engine = DenseEngine(cfg, params, n_slots=sc.n_slots,
                                      capacity=sc.capacity,
                                      use_pallas=sc.use_pallas,
                                      mesh=sc.mesh,
                                      telemetry=sc.telemetry)
        else:
            self.engine = PagedEngine(cfg, params, n_slots=sc.n_slots,
                                      capacity=sc.capacity,
                                      page_size=sc.page_size,
                                      n_pages=sc.n_pages,
                                      use_pallas=sc.use_pallas,
                                      kernel=sc.kernel, mesh=sc.mesh,
                                      telemetry=sc.telemetry)
            self.allocator = PageAllocator(self.engine.n_pages,
                                           sc.page_size, sc.allocation)
            self.slot_pages: list = [[] for _ in range(sc.n_slots)]
            logical = self.engine.ring_cap
            # sharing is sound only while the logical ring never wraps (a
            # wrapped ring overwrites the shared prefix entries)
            self._share = sc.share_prefix and logical >= sc.capacity
            # skipping the shared tokens outright needs (a) chunked prefill
            # (the pages are fully written at the sharee's admission) and
            # (b) no recurrent state to rebuild (pure attention)
            self._share_skip = (self._share
                                and sc.prefill_mode == "chunked"
                                and cfg.block_kind == "attention")
        # prefill block chunking bound (logical ring under paged layout)
        self._ring_cap = self.engine.ring_cap
        self.mesh = self.engine.mesh
        self.n_slot_groups = self.engine.n_slot_groups
        self.group_active = np.zeros((self.n_slot_groups,), np.int64)

    # ------------------------------------------------ engine delegation

    @property
    def cache(self):
        return self.engine.cache

    @property
    def block_table(self):
        return self.engine.block_table

    @property
    def slot_pos(self):
        return self.engine.slot_pos

    @property
    def page_size(self) -> int:
        return self.engine.page_size

    @property
    def n_pages(self) -> int:
        return self.engine.n_pages

    @property
    def pages_per_slot(self) -> int:
        return self.engine.pages_per_slot

    # ------------------------------------------------------------- intake

    def _worst_case_pages(self, req: Request) -> int:
        total = min(len(req.prompt) + self._budget(req), self._ring_cap)
        return -(-total // self.engine.page_size)

    def _fork_page(self, req: Request) -> int:
        """Block-table index of the fork page: the page holding the last
        prompt token, which every forked branch re-writes on its first
        tick (re-feeding prompt[-1] to sample its own first token) and
        therefore always copies-on-write; pages before it stay shared for
        the group's whole lifetime."""
        return (len(req.prompt) - 1) // self.engine.page_size

    def _group_pages(self, req: Request) -> int:
        """Worst-case pages of a whole best_of=n group: the primary's W,
        plus per branch its private tail past the fork page and one CoW
        reserve for the fork page itself, plus the primary's own CoW
        reserve when its first decode write lands in the (shared) fork
        page (p % page_size != 0)."""
        W = self._worst_case_pages(req)
        lw = self._fork_page(req)
        rsv = 1 if len(req.prompt) % self.engine.page_size else 0
        return W + (req.best_of - 1) * (W - lw) + rsv

    def _admission_check(self, req: Request):
        """Reject at submit() a request whose worst-case page budget can
        NEVER fit the pool — queued, it would stall the FIFO head forever
        and run() would spin to max_steps completing nothing.  best_of>1
        additionally requires a forkable layout: shared pages are the
        fork substrate, so dense rings and O(1) recurrent state are
        rejected here rather than silently degraded."""
        if req.best_of > 1:
            if self.cache_layout != "paged" \
                    or self.cfg.block_kind != "attention":
                raise ValueError(
                    f"request {req.rid}: best_of={req.best_of} needs the "
                    f"paged pure-attention layout — dense rings and "
                    f"recurrent O(1) state cannot fork pages")
            if self._ring_cap < self.capacity:
                raise ValueError(
                    f"request {req.rid}: best_of>1 is unsupported when "
                    f"the logical ring ({self._ring_cap}) can wrap within "
                    f"capacity {self.capacity} — a wrapped ring would "
                    f"overwrite the shared fork pages")
            if self.prefill_mode != "chunked":
                raise ValueError(
                    f"request {req.rid}: best_of>1 needs "
                    f"prefill_mode='chunked' (the fork point is the end "
                    f"of the primary's prefill)")
            if req.best_of > self.n_slots:
                raise ValueError(
                    f"request {req.rid}: best_of={req.best_of} exceeds "
                    f"the {self.n_slots}-slot pool — branches decode "
                    f"concurrently, one slot each")
            sp = req.sampling or self.default_sampling
            if sp.branch != 0:
                raise ValueError(
                    f"request {req.rid}: best_of>1 derives branch keys "
                    f"itself — submit with sampling.branch=0")
        if self.cache_layout != "paged":
            return
        need = self._group_pages(req) if req.best_of > 1 \
            and self.allocation == "worst_case" else \
            self._worst_case_pages(req)
        if need > self.engine.n_pages - 1:
            raise ValueError(
                f"request {req.rid}: needs {need} pages but the pool holds "
                f"{self.engine.n_pages - 1} — raise n_pages or lower "
                f"capacity")

    def _feed_tokens(self, req: Request) -> list:
        """Tokens whose K/V the slot must hold before normal decode can
        (re)start: the prompt, plus — on a preemption resume — every
        already-generated token except the last (the last one is the next
        decode tick's input, exactly as if no preemption had happened)."""
        rs = self._resume.get(id(req))
        if rs is None:
            return req.prompt
        return list(req.prompt) + rs[0][:-1]

    def _fill_slots(self):
        while self.queue:
            if self.queue[0].best_of > 1:
                if not self._admit_group(self.queue[0]):
                    break  # not enough slots/pages yet: FIFO stall
                continue
            s = next((i for i in range(self.n_slots)
                      if self.slot_req[i] is None), None)
            if s is None:
                break
            fed0 = 0
            if self.cache_layout == "paged":
                admitted = self._admit_paged(s)
                if admitted is None:
                    break  # pool exhausted: FIFO stall until reclaim
                req, fed0 = admitted
            else:
                req = self.queue.pop(0)
            self._place(s, req, fed0)

    def _place(self, s: int, req: Request, fed0: int):
        """Install an admitted request in slot s and run its prefill."""
        feed = self._feed_tokens(req)
        rs = self._resume.pop(id(req), None)
        self.slot_req[s] = req
        st = self._new_slot_state(req, fed0)
        if rs is not None:
            st["emitted"], st["margins"], st["logps"] = rs
        self.slot_state[s] = st
        tel = self.telemetry
        if tel is not None:
            # a zero-emitted preemption leaves no resume stash, so pair
            # the preempt off the span log instead
            if rs is not None or tel.last_event(req.rid) == "preempt":
                tel.trace(req.rid, "resume", slot=s,
                          replayed=len(st["emitted"]))
            tel.trace(req.rid, "prefill", slot=s, feed=len(feed) - fed0)
        if self.prefill_mode == "chunked":
            self._prefill_slot(s, feed, fresh=rs is None)
            if tel is not None and self.slot_req[s] is req:
                tel.trace(req.rid, "decode", slot=s)
        else:
            # prompt (and, on resume, the replayed generated
            # tokens) will be fed through decode ticks; zero the
            # slot's lanes inside the next fused dispatch
            self.engine.mark_reset(s)
            if tel is not None:
                tel.trace(req.rid, "decode", slot=s)

    def _admit_group(self, head: Request) -> bool:
        """Admit a best_of=n request: prefill the prompt ONCE into a
        primary slot, then fork n-1 branch slots whose block tables share
        every prompt page.  Each member is a best_of=1 clone with its own
        branch-folded sampling key, so downstream lifecycle — decode,
        preemption, recompute-resume, completion — treats branches as
        ordinary requests; only completion recording regroups them
        (winner by cumulative logprob).  Returns False (FIFO stall) while
        fewer than n slots are free or, under worst-case allocation, the
        pool cannot yet hold the whole group's page budget."""
        n = head.best_of
        free = [s for s in range(self.n_slots) if self.slot_req[s] is None]
        if len(free) < n:
            return False
        p = len(head.prompt)
        ps = self.engine.page_size
        W = self._worst_case_pages(head)
        lw = self._fork_page(head)
        if self.allocation == "worst_case":
            # atomic: the whole group's worst case must be free up front
            # (prefix sharing may make the primary cheaper — this check
            # is conservative, never unsafe)
            if self.allocator.n_free < self._group_pages(head):
                return False
        sp = head.sampling or self.default_sampling
        members = [dataclasses.replace(
            head, best_of=1, sampling=dataclasses.replace(sp, branch=b))
            for b in range(n)]
        self._groups[head.rid] = {"n": n, "members": members,
                                  "completions": {}, "head": head}
        self.queue[0] = members[0]
        admitted = self._admit_paged(free[0])
        if admitted is None:  # lazy pool can't hold the prompt pages yet
            self.queue[0] = head
            del self._groups[head.rid]
            return False
        s0 = free[0]
        prim, fed0 = admitted
        # fork BEFORE the primary's prefill: branches only take page
        # REFERENCES here — the prefill below writes the shared pages'
        # contents before any branch's first tick reads them.  (This also
        # keeps a budget-1 primary sound: it may finish during prefill,
        # but the branches' refcounts already pin the shared pages.)
        shared = list(self.slot_pages[s0][:lw + 1])
        if self.allocation == "worst_case" and p % ps:
            # the primary's first decode write lands in the shared fork
            # page: pre-allocate its CoW replacement
            self._cow_reserve[s0] = [self.allocator.alloc()]
        for b in range(1, n):
            sb = free[b]
            self.allocator.fork(shared)
            self.fork_shared_pages += len(shared)
            tail = [self.allocator.alloc() for _ in range(W - 1 - lw)] \
                if self.allocation == "worst_case" else []
            self._cow_reserve[sb] = [self.allocator.alloc()] \
                if self.allocation == "worst_case" else []
            self.slot_pages[sb] = shared + tail
            self.engine.fork_slot(s0, sb)
            for i, pid in enumerate(tail):
                self.engine.set_page(sb, lw + 1 + i, pid)
            # the branch re-feeds the last prompt token at position p-1:
            # its first tick recomputes the fork logits and samples its
            # OWN first token (branch key) inside the fused dispatch —
            # writing the fork page, which triggers the CoW copy
            self.engine.set_pos(sb, p - 1)
            self.slot_req[sb] = members[b]
            self.slot_state[sb] = self._new_slot_state(members[b],
                                                       fed0=p - 1)
        self._place(s0, prim, fed0)
        return True

    # ------------------------------------------------- paged-pool admission

    def _prefix_chain(self, prompt, n_pages: int):
        """Rolling prefix keys of the first n_pages full prompt pages."""
        ps, chain, keys = self.engine.page_size, (), []
        for k in range(n_pages):
            chain = (chain, tuple(prompt[k * ps:(k + 1) * ps]))
            keys.append(chain)
        return keys

    def _admit_paged(self, s: int):
        """Try to admit the queue head into slot s, sharing refcounted
        prefix pages where the index has them.  Worst-case allocation
        reserves every page the whole sequence (prompt + budget) can
        touch; lazy allocation reserves only the pages the prefill will
        write (prompt — plus replayed generated tokens on a resume) and
        leaves decode pages to on-demand growth.  Returns (request,
        first-unshared-token) or None when the pool can't hold it yet."""
        req = self.queue[0]
        ps = self.engine.page_size
        feed = self._feed_tokens(req)
        if self.allocation == "lazy" and id(req) not in self._resume:
            need = -(-min(len(feed), self._ring_cap) // ps)
        else:
            # worst case — always for allocation="worst_case", and as the
            # anti-thrash rule for a lazy RESUME: a preempted request is
            # re-admitted only when it can run to completion, so it never
            # grows (never re-triggers preemption) and the recompute
            # prefill is paid at most once per displacement instead of
            # ping-ponging with the request that displaced it
            need = self._worst_case_pages(req)
        # infeasible requests are rejected at submit(); anything queued
        # can always be admitted once enough pages are reclaimed
        assert need <= self.engine.n_pages - 1, req.rid
        shared: list = []
        full_pages = len(feed) // ps
        keys = self._prefix_chain(feed, full_pages) if self._share \
            else []
        # skip mode must leave >= 1 token to feed (a fresh admission
        # samples its first generated token from the last fed logits)
        limit = min(full_pages, (len(feed) - 1) // ps) \
            if self._share_skip else full_pages
        for key in keys[:limit]:
            pid = self.allocator.lookup_prefix(key)
            if pid is None:
                break
            shared.append(pid)
        if self.allocator.n_free < need - len(shared):
            return None
        self.queue.pop(0)
        for pid in shared:
            self.allocator.share(pid)
        pages = shared + [self.allocator.alloc()
                          for _ in range(need - len(shared))]
        self.slot_pages[s] = pages
        # publish this request's own full prefill pages for later sharers
        if self._share:
            for k in range(len(shared), full_pages):
                self.allocator.register_prefix(keys[k], pages[k])
        fed0 = len(shared) * ps if self._share_skip else 0
        self.engine.admit(s, pages, fed0)
        return req, fed0

    def _release_slot(self, s: int):
        if self.cache_layout != "paged":
            return
        # reclaim is fused with slot release: one refcount sweep frees
        # every non-shared page (an unused CoW reserve included); the
        # block-table row falls back to the null page so the idle lane's
        # scatter lands nowhere live
        for pid in self.slot_pages[s]:
            self.allocator.release(pid)
        for pid in self._cow_reserve[s]:
            self.allocator.release(pid)
        self.slot_pages[s] = []
        self._cow_reserve[s] = []
        self.engine.release(s)

    # -------------------------------------------------- best-of-n groups

    def _complete(self, req: Request, c: Completion):
        """Group members detour through their group's collector; when the
        last branch finishes, the winner by cumulative logprob (ties to
        the lowest branch index) is recorded under the parent rid and the
        per-branch completions archived in `group_results`."""
        g = self._groups.get(c.rid)
        if g is None or not any(m is req for m in g["members"]):
            self.done.append(c)
            self._trace(c.rid, "finished", tokens=len(c.tokens))
            return
        g["completions"][req.sampling.branch] = c
        if len(g["completions"]) == g["n"]:
            by_branch = dict(g["completions"])
            winner = min(by_branch.items(),
                         key=lambda kv: (-sum(kv[1].logprobs), kv[0]))[1]
            self.group_results[c.rid] = by_branch
            del self._groups[c.rid]
            self.done.append(winner)
            self._trace(c.rid, "finished", tokens=len(winner.tokens),
                        branches=g["n"])

    def _drop_group(self, rid: int):
        self._groups.pop(rid, None)

    # ------------------------------------------------------- preemption

    def preempt(self, rid: int) -> bool:
        """Force the running request `rid` back to the queue head with its
        generated tokens (the on-demand page-growth path uses the same
        mechanism when the pool exhausts).  Works on both layouts; returns
        False when rid is not currently in a slot."""
        for s in range(self.n_slots):
            req = self.slot_req[s]
            if req is not None and req.rid == rid:
                self._preempt(s)
                return True
        return False

    def _preempt(self, s: int, reason: str = "forced"):
        """Host-side only: release slot s's pages/lane, stash its emitted
        tokens for a resume prefill, requeue it at the head.  `reason`
        labels the preemption ("forced" — the public `preempt()`;
        "pool_exhausted" — lazy growth; "migrate" — recipe export)."""
        req, st = self.slot_req[s], self.slot_state[s]
        self.preemptions += 1
        if self.telemetry is not None:
            self.telemetry.counter("sched_preemptions_total").inc(
                reason=reason)
            self.telemetry.trace(req.rid, "preempt", reason=reason,
                                 slot=s, emitted=len(st["emitted"]))
        if st["emitted"]:
            self._resume[id(req)] = (list(st["emitted"]),
                                     list(st["margins"]),
                                     list(st["logps"]))
        self._release_slot(s)
        self.slot_req[s] = None
        self.slot_state[s] = None
        self.queue.insert(0, req)

    # ------------------------------------------------ migration (router)

    def export_recipe(self, rid: int) -> RecomputeRecipe | None:
        """Extract request `rid` from this batcher as a RecomputeRecipe —
        the router's migration/failover primitive.  The request leaves
        this replica entirely (slot + pages reclaimed, queue entry
        dropped); `submit_recipe` on another replica continues it
        token-identically.  A running request goes through the host-side
        preempt path first, so its emitted tokens ride along in the
        recipe.  A live best-of-n group exports as a RESTART of the
        parent request (emitted=()): branches share pages on THIS pool
        and no branch token has been surfaced to the client yet, so the
        destination re-forks from scratch and — by branch-key determinism
        — elects the same winner.  Returns None when the rid is unknown
        here (already finished, cancelled, or never submitted)."""
        g = self._groups.get(rid)
        if g is not None:
            head = g["head"]
            # drops every queued/running branch + pages; _outcome=None —
            # the request is migrating, not cancelled (the router traces
            # migrate_out/migrate_in at the frontend boundary)
            self.cancel(rid, _outcome=None)
            return RecomputeRecipe.from_request(head, self.default_sampling)
        for s in range(self.n_slots):
            req = self.slot_req[s]
            if req is not None and req.rid == rid:
                self._preempt(s, reason="migrate")  # stash, requeue at head
                break
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                self.queue.pop(i)
                rs = self._resume.pop(id(req), None) or ((), (), ())
                return RecomputeRecipe.from_request(
                    req, self.default_sampling,
                    emitted=rs[0], margins=rs[1], logps=rs[2])
        return None

    def submit_recipe(self, recipe: RecomputeRecipe) -> Request:
        """Admit a migrated-in recipe: normal submit-time validation,
        then — when tokens were already emitted — the resume stash is
        seeded so admission runs the PR 5 recompute-prefill path (which
        also means worst-case page reservation, the anti-thrash rule: a
        migrated request never grows post-admission, so it cannot
        immediately bounce to a third replica under lazy allocation).
        Returns the enqueued Request."""
        if len(recipe.prompt) + len(recipe.emitted) >= self.capacity:
            raise ValueError(
                f"request {recipe.rid}: recipe carries "
                f"{len(recipe.prompt)} prompt + {len(recipe.emitted)} "
                f"emitted tokens — does not fit capacity {self.capacity}")
        self.submit([recipe.to_request()])
        req = self.queue[-1]  # submit may rewrite an empty prompt to BOS
        if recipe.emitted:
            self._resume[id(req)] = (list(recipe.emitted),
                                     list(recipe.margins),
                                     list(recipe.logps))
        return req

    def prefix_affinity(self, prompt) -> int:
        """Leading prompt tokens already resident in this replica's
        shared-prefix registry (0 on dense layouts or with sharing off).
        The router's locality signal: admitting here shares those pages
        instead of recomputing them."""
        if self.cache_layout != "paged" or not self._share:
            return 0
        ps = self.engine.page_size
        hits = 0
        for key in self._prefix_chain(prompt, len(prompt) // ps):
            if self.allocator.lookup_prefix(key) is None:
                break
            hits += 1
        return hits * ps

    def _victim_order(self, s: int):
        """Sort key: the MOST preemptible running request first — lowest
        priority, then latest (or no) deadline, then most recently
        admitted."""
        req, st = self.slot_req[s], self.slot_state[s]
        dl = req.deadline if req.deadline is not None else float("inf")
        return (req.priority, -dl, -st["admit_seq"])

    def _alloc_with_preemption(self, s: int) -> bool:
        """Make sure the pool has a free page for slot s, preempting the
        most preemptible running request (possibly slot s itself, which
        then simply leaves the tick) while it is exhausted.  Slots inside
        their minimum-run quantum are skipped as victims unless EVERY
        live slot is (liveness: the pool must yield a page).  Returns
        False when slot s yielded itself."""
        while self.allocator.n_free == 0:
            live = [v for v in range(self.n_slots)
                    if self.slot_req[v] is not None]
            ripe = [v for v in live
                    if self.slot_state[v]["ran"] >= self.min_quantum]
            victim = min(ripe or live, key=self._victim_order)
            self._preempt(victim, reason="pool_exhausted")
            if victim == s:
                return False  # the grower was the weakest: it yielded
        return self.slot_req[s] is not None

    def _secure_slot_pages(self):
        """Before the fused tick, make sure every live slot PRIVATELY
        owns the page its next token's K/V lands in:

        - lazy growth (PR 5): at a page boundary, append a fresh page,
          preempting the most preemptible running request on pool
          exhaustion;
        - copy-on-write (the fork path): a slot about to write into a
          page other holders still reference trades its reference for a
          private replacement (allocator.ensure_private — drawn from the
          slot's fork-time reserve under worst-case allocation, from the
          free list with the same preemption escape under lazy), queues
          an in-dispatch page-to-page copy on the engine, and repoints
          only its OWN block-table entry.  Prefix-shared prompt pages
          never reach this transition: decode writes always land past
          the full prompt pages.

        Pure host-side bookkeeping either way — the fused tick stays at
        exactly one dispatch (fork-free ticks queue no copies and the
        step's whole-batch cond skips the copy compute)."""
        if self.cache_layout != "paged":
            return
        ps = self.engine.page_size
        for s in range(self.n_slots):
            if self.slot_req[s] is None:
                continue
            pos = int(self.engine.slot_pos[s])
            idx = (pos % self._ring_cap) // ps
            if idx >= len(self.slot_pages[s]):
                if self.allocation != "lazy":
                    continue  # worst case owns every page up front
                assert idx == len(self.slot_pages[s]), (s, pos, idx)
                if not self._alloc_with_preemption(s):
                    continue
                pid = self.allocator.alloc()
                self.slot_pages[s].append(pid)
                self.engine.set_page(s, idx, pid)
                self.page_growths += 1
                if self.telemetry is not None:
                    self.telemetry.counter(
                        "pool_page_growths_total").inc()
                continue
            pid = self.slot_pages[s][idx]
            if pid == 0 or self.allocator.refcount[pid] <= 1:
                continue  # sole holder (or ring-wrap don't-care): write
            reserved = None
            if self._cow_reserve[s]:
                reserved = self._cow_reserve[s].pop()
            elif not self._alloc_with_preemption(s):
                continue  # the writer itself yielded mid-reclaim
            new, copied = self.allocator.ensure_private(pid, reserved)
            assert copied, (s, pid)
            self.slot_pages[s][idx] = new
            self.engine.set_page(s, idx, new)
            self.engine.queue_copy(s, pid, new)
            self.cow_copies += 1
            if self.telemetry is not None:
                self.telemetry.counter("engine_cow_copies_total").inc()

    # ------------------------------------------------------------ prefill

    def _chunk_size(self, pos: int, remaining: int) -> int:
        """Prefill block size: <= prefill_chunk, power-of-two bucketed (so
        the compiled-shape set stays O(log chunk)), and never wrapping a
        ring cache — past the ring boundary blocks degrade to 1 token,
        which is the exact seed-equivalent ring write."""
        size = min(self.prefill_chunk, remaining)
        if self._ring_cap is not None and pos + size > self._ring_cap:
            size = self._ring_cap - pos if pos < self._ring_cap else 1
        p = 1
        while p * 2 <= size:
            p *= 2
        return p

    def _prefill_slot(self, s: int, feed, fresh: bool = True):
        """Write `feed` into slot s in blocks.  On a fresh admission feed
        is the prompt and the last block's logits give the first generated
        token (sampled in-dispatch); on a preemption resume feed is
        prompt + already-emitted tokens (minus the last) and the block
        outputs are discarded — the resumed request's next token is
        already known, nothing is re-sampled.  Starts at st["fed"] —
        nonzero when a refcount-shared prefix was skipped (paged
        layout)."""
        st = self.slot_state[s]
        tokens = np.asarray(feed, np.int32)
        n, off, reset = len(tokens), st["fed"], True
        row = self._sampling_row(s)
        tok = margin = logp = None
        while off < n:
            size = self._chunk_size(off, n - off)
            tok, margin, logp = self.engine.prefill_block(
                s, tokens[None, off:off + size], off, reset, row)
            reset = False
            off += size
        # a size-S block books S slot-steps of work and S slot-steps of
        # offered capacity (a batch-1 prefill dispatch offers nothing to
        # the other lanes), so utilization agrees with decode-mode prefill
        self.active_slot_steps += n - st["fed"]
        self.total_slot_steps += n - st["fed"]
        self.engine.set_pos(s, n)
        st["fed"] = n
        if fresh:
            st["emitted"].append(tok)
            st["margins"].append(margin)
            st["logps"].append(logp)
            self._finish_if_done(s)

    # --------------------------------------------------------------- step

    def _step_inner(self):
        """One engine tick: a SINGLE fused dispatch advances every active
        slot by one token (prompt feed in decode prefill mode, replayed
        tokens on a decode-mode resume, or generated — sampled or greedy
        per the slot's SamplingParams).  The tick first secures private
        ownership of each live slot's write page — lazy growth and
        copy-on-write reclaim, preempting on exhaustion — still exactly
        one device dispatch.  With a profiling sink, each phase runs
        under its host span: admission and prefill (``sched.admit``),
        page securing (``sched.pages``), the dispatch's inputs
        (``sched.inputs``) and the bookkeeping after it
        (``sched.commit``)."""
        tel = self.telemetry
        with (tel.span("sched.admit") if tel is not None else NULL_SPAN):
            self._fill_slots()
        with (tel.span("sched.pages") if tel is not None else NULL_SPAN):
            self._secure_slot_pages()
        active = [s for s in range(self.n_slots)
                  if self.slot_req[s] is not None]
        if not active:
            return False
        with (tel.span("sched.inputs") if tel is not None else NULL_SPAN):
            toks = np.zeros((self.n_slots, 1), np.int32)
            emit = np.zeros((self.n_slots,), bool)
            for s in active:
                req, st = self.slot_req[s], self.slot_state[s]
                p = len(req.prompt)
                if st["fed"] < p:
                    toks[s, 0] = req.prompt[st["fed"]]
                else:
                    toks[s, 0] = st["emitted"][st["fed"] - p]
                # this feed produces a NEW token only when it is the last
                # known one; earlier feeds are prompt tokens or a resume
                # replay, whose outputs are already known and discarded
                emit[s] = st["fed"] == p + len(st["emitted"]) - 1
            active_mask = np.zeros((self.n_slots,), bool)
            active_mask[active] = True
            sampling = self._sampling_batch()
        nxt, margins, logps = self.engine.decode(toks, active_mask,
                                                 sampling)
        with (tel.span("sched.commit") if tel is not None else NULL_SPAN):
            self.decode_ticks += 1
            self.decode_active_slots += len(active)
            spg = max(1, self.n_slots // self.n_slot_groups)
            for s in active:
                self.group_active[s // spg] += 1
            self.active_slot_steps += len(active)
            self.total_slot_steps += self.n_slots
            for s in active:
                st = self.slot_state[s]
                st["fed"] += 1
                st["ran"] += 1
                if emit[s]:
                    st["emitted"].append(int(nxt[s]))
                    st["margins"].append(float(margins[s]))
                    st["logps"].append(float(logps[s]))
                    self._finish_if_done(s)
        return True


class PerSlotBatcher(_BatcherBase):
    """Seed baseline: one jitted batch-1 decode call per active slot per
    tick (n_slots dispatches/tick).  Kept as the equivalence reference and
    the bench's before-side; shares intake/accounting with the fused
    engine and supports the same per-request sampling."""

    def __init__(self, cfg: ModelConfig, params, *, n_slots: int = 4,
                 capacity: int = 256, bos_token: int | None = None,
                 default_sampling: SamplingParams | None = None,
                 telemetry=None):
        super().__init__(cfg, params, n_slots=n_slots, capacity=capacity,
                         bos_token=bos_token,
                         default_sampling=default_sampling,
                         telemetry=telemetry)
        self.engine = PerSlotEngine(cfg, params, n_slots=n_slots,
                                    capacity=capacity, telemetry=telemetry)

    @property
    def caches(self):
        return self.engine.caches

    def _admission_check(self, req: Request):
        if req.best_of > 1:
            raise ValueError(
                f"request {req.rid}: best_of={req.best_of} needs the paged "
                f"engine's shared page pool — per-slot caches cannot fork")

    def _fill_slots(self):
        for s in range(self.n_slots):
            if self.slot_req[s] is None and self.queue:
                req = self.queue.pop(0)
                self.slot_req[s] = req
                self.slot_state[s] = self._new_slot_state(req)
                self.engine.reset_slot(s)
                if self.telemetry is not None:
                    self._trace(req.rid, "prefill", slot=s,
                                feed=len(req.prompt))
                    self._trace(req.rid, "decode", slot=s)

    def _step_inner(self):
        """One engine step: each active slot consumes one token (prompt feed
        or generated) and produces at most one new token."""
        self._fill_slots()
        any_active = False
        for s in range(self.n_slots):
            req = self.slot_req[s]
            if req is None:
                continue
            any_active = True
            self.active_slot_steps += 1
            st = self.slot_state[s]
            if st["fed"] < len(req.prompt):
                tok = int(req.prompt[st["fed"]])
            else:
                tok = st["emitted"][-1]
            nxt, margin, logp = self.engine.step(s, tok,
                                                 self._sampling_row(s))
            st["fed"] += 1
            if st["fed"] >= len(req.prompt):
                st["emitted"].append(nxt)
                st["margins"].append(margin)
                st["logps"].append(logp)
                self._finish_if_done(s)
            self.decode_active_slots += 1
            self.group_active[0] += 1
        if any_active:
            self.total_slot_steps += self.n_slots
            self.decode_ticks += 1
        return any_active
