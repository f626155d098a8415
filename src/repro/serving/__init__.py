"""Serving stack: four layers — a fleet ROUTER over async
REQUEST-LIFECYCLE frontends over a host-side POLICY scheduler over
device-facing ENGINES.

Construction contract: `ServingConfig` (``config``) is the single
validated construction surface for a replica.  ALL cross-field rules —
accepted enum values for prefill_mode/cache_layout/kernel/allocation,
pallas-needs-paged, dense-forces-worst-case, and the model-dependent
recurrent-forces-dense coercion (`resolve`) — live in its
`__post_init__`/`resolve`, fail as `ValueError`s naming the accepted
values, and fire at config time rather than deep inside an engine.
`ContinuousBatcher(cfg, params, ServingConfig(...))` is the primary
constructor; the historical loose kwargs survive one release behind a
`DeprecationWarning` shim.

Observability contract: ``telemetry`` is the stack-wide substrate every
layer reports through — a `Telemetry` sink carried on
``ServingConfig(telemetry=...)`` and shared by the batcher, its engine
and its frontend (the router holds its own plus a `merged_telemetry()`
view over the fleet).  Four facets, all on one clock (`Telemetry.now`
is `time.monotonic`, the asyncio loop's clock):

- **metrics registry** — `Counter` / `Gauge` / `Histogram` (fixed
  buckets, retained samples, p50/p95/p99, mergeable across replicas)
  under Prometheus-style names with a ``layer_noun_unit`` convention:
  the frontend owns ``serving_ttft_ms`` / ``serving_tpot_ms`` /
  ``requests_intake_total`` / ``requests_total{outcome=...}`` (every
  handle ends in exactly ONE outcome, so intake == sum over outcomes);
  the scheduler owns ``sched_preemptions_total{reason=...}``,
  ``engine_cow_copies_total``, ``pool_page_growths_total`` and
  ``pool_pages_in_use``; the router owns
  ``router_migrations_total`` / ``router_failovers_total`` and the
  per-link byte ledger ``router_recipe_bytes_total{link="src->dst"}``
  / ``router_kv_page_bytes_total``.  The fused tick's one-dispatch
  rule reads off the batcher's own counters
  (``decode_dispatches / decode_ticks``).
- **request-lifecycle tracer** — every rid carries a span log of
  timestamped transitions: intake -> queued -> (resume ->) prefill ->
  decode <-> preempt{reason} -> migrate_out / migrate_in -> exactly one
  terminal event (finished / cancelled / expired / failed /
  migrate_out).  The frontend and scheduler dedupe terminal events
  through `Telemetry.last_event`; per-tick engine spans
  (`Telemetry.tick`) record dispatch wall time, the seconds the tick
  waited on the device (``wait_s``), and CoW / page-growth /
  preemption annotations.  A migrated request's spans live on BOTH
  replicas' sinks and interleave by timestamp under
  `Telemetry.merged`.
- **host spans and device scopes** — `Telemetry.span(name)` opens a
  `jax.profiler.TraceAnnotation` when ``Telemetry(profile=True)`` and
  returns the shared no-op `NULL_SPAN` otherwise.  The names are
  `HOST_SPANS`: ``frontend.intake`` / ``frontend.pump`` (the frontend's
  loop turn), ``sched.admit`` / ``sched.pages`` / ``sched.inputs`` /
  ``sched.commit`` (the tick's phases), the engines' dispatch spans
  (``paged.decode``, ``paged.prefill``, ``dense.*``,
  ``per_slot.step``) and ``engine.wait`` (a blocking result fetch).
  Inside the compiled step programs the three `DEVICE_SCOPES`
  (``attn``, ``kv_pool``, ``sample``) are `jax.named_scope`s: metadata
  on every op, so a device trace splits a step by layer, while the ops
  themselves are unchanged.
- **exporters** — `Telemetry.snapshot()` (nested dict; both `stats()`
  methods are compatibility views over it) and Chrome/Perfetto
  trace_event JSON (`perfetto_trace` / `write_trace`,
  ``--trace out.json`` on launch/serve.py: one process track per
  replica, engine ticks on thread 0, one thread per request).

Zero-overhead rule: ``telemetry=None`` (the default) must add NOTHING
to the hot path — every scheduler/engine call site guards with a plain
``is not None`` check (a span site falls to the shared `NULL_SPAN`),
recording is host-side only, and the fused tick stays at exactly 1.00
dispatch whether or not a sink is attached (the
``serving_telemetry_overhead`` bench row gates overhead <= 5% in CI).
The frontend keeps a private sink when the config carries none — it
records only at request-lifecycle boundaries, never per tick, and
opens spans only through the stack's own sink.
Placement feedback closes the loop: the router's `_score` demotes
replicas whose ``serving_ttft_ms`` p95 trails the fleet's best.

Layer split (where requests go vs who may run vs who runs vs how it
runs):

- ``router`` — fleet placement.  `ReplicaRouter` fronts N independent
  frontend+batcher replicas (a ``list[ServingConfig]`` — heterogeneous
  pool sizes, layouts, kernels) behind one ``submit()`` queue.  It
  scores replicas by load and prefix-cache affinity for admission,
  MIGRATES queued/preempted requests between replicas by shipping the
  recompute recipe (`RecomputeRecipe`: prompt + emitted tokens +
  sampling seed/emit-index — the preempt/resume contract on the wire,
  so migrated runs stay token-identical, greedy and sampled) instead of
  raw KV pages, and drains a failed replica (`fail_replica`) onto
  survivors through the same path.  Every inter-replica byte is
  accounted per link (`router_overhead_bytes`, crosspod-style) against
  the counterfactual KV-page transfer.
- ``frontend`` — request lifecycle.  `ServingFrontend` is an asyncio
  service over a batcher: ``await submit(...)`` returns a
  `RequestHandle` that streams tokens per tick (``async for tok in
  handle``), resolves to a `Completion` (``await handle.result()``), and
  cancels at any stage (intake, queued, mid-prefill, mid-decode) with
  immediate slot/page reclaim.  Intake is a bounded queue — `submit`
  suspends callers for backpressure instead of buffering unboundedly —
  and per-request ``priority=`` / ``deadline_ms=`` ride the scheduler's
  `Request` into the preemption policy.  Deadlines are also enforced:
  between ticks the engine task auto-cancels every queued or running
  request whose deadline passed and fails its handle with
  `DeadlineExpired`.  ``best_of=n`` resolves the handle with the
  winning branch only (the stream stays quiet while branches race).
- ``scheduler`` — policy.  `Request` / `SamplingParams` intake and
  validation, FIFO admission, per-request token budgets, slot
  assignment/release, `preempt(rid)` / `cancel(rid)` /
  `expire_deadlines(now)`, completion records, utilization/occupancy
  metrics.  Touches no device buffers.  Page OWNERSHIP lives here in
  `PageAllocator` under one rule — a page is SHARED UNTIL WRITTEN:
  `share` refcounts a live page, `fork` shares a whole block table at a
  branch point, and `ensure_private` is the copy-on-write transition (a
  holder about to write a page other holders still reference gives up
  its reference and gets a private replacement; the engine copies the
  page in-dispatch and only that holder's block-table entry is
  repointed).  Prompt-prefix sharing and best-of-n forking are both
  special cases of this rule; prefix pages are never written past the
  prompt, so they never reach the CoW transition.  `Request.best_of=n`
  prefills a prompt once, forks n-1 branches that share every prompt
  page, decodes all n concurrently (branch b's noise keyed by
  `branch_key(seed, b)`), and records only the winner by cumulative
  token logprob (per-branch results in `group_results`).
  Paged admission has two modes (``allocation=``): "worst_case"
  (default) reserves a request's whole-sequence page budget up front and
  stalls the FIFO queue on exhaustion; "lazy" admits on the prompt's
  pages only, acquires each decode page on demand at page boundaries,
  and on pool exhaustion preempts the most preemptible running request
  (lowest priority, then latest/absent deadline, then most recent
  admission; slots inside their ``min_quantum`` of decode ticks are
  passed over while any riper victim exists) — its slot and non-shared
  pages are released and it is
  requeued WITH its generated tokens, so the resume is a recompute
  prefill of prompt + emitted (never a re-sample) and completions are
  token-for-token what an unpreempted run produces; a resume is
  re-admitted at its remaining worst case, so a once-preempted request
  returns only when it can run to completion (anti-thrash).  A request whose
  worst case can NEVER fit the pool is still rejected at submit().
  Preemption, lazy growth and the CoW transition are host-side
  bookkeeping only: the fused tick stays at exactly one dispatch.
- ``engine`` — dispatch.  `DenseEngine` (stacked dense rings, device
  `pos` vector, in-dispatch slot reset), `PagedEngine` (ONE shared page
  pool per layer, host-owned block tables + positions, `set_page` for
  lazy growth, `fork_slot` to clone a block table at a branch point,
  `queue_copy` to ride a CoW page copy into the next fused tick),
  `PerSlotEngine` (seed batch-1 baseline).  Each owns its
  decode state and jitted step functions and advances the whole slot
  pool in ONE dispatch per tick.  `PagedEngine` takes a
  ``kernel="xla"|"pallas"`` knob (also on `ContinuousBatcher`): "xla" —
  the default and the equivalence oracle — gathers each lane's logical
  ring and scatters the new K/V rows with an XLA `.at[].set`; "pallas"
  runs the paged-attention v2 kernel (repro.kernels.paged_attention),
  which streams K/V page tiles through the block table in-kernel
  (scalar-prefetch index maps, flash-style online softmax, GQA grouping,
  position-validity masking) AND fuses the new rows' pool scatter into
  the same pass (`paged_attention_update` aliases the pools in-place —
  no separate scatter dispatch, verified by an HLO oracle in tests).
  The kernel takes S>=1 query blocks with per-row causal/window masking,
  so chunked prefill and preemption resume-recompute run through it too;
  it falls back to the XLA path only for M-RoPE, chunked-local
  attention masking, mesh sharding, or blocks longer than the ring.
  Ordering contract with CoW: `cow_copy_pages` runs BEFORE the forward
  inside the same fused tick, and `ensure_private` guarantees every
  page written in a tick is private to one slot — so the in-kernel
  write never races a copy or another slot's read.  Both kernels stay
  inside the same single fused dispatch per tick and are
  token-equivalent (greedy, sampled, and best-of fork trajectories).
- ``sampling`` — the decode-policy kernel.  Per-slot temperature /
  top-k / top-p sampling expressed as Gumbel-max over filtered scaled
  logits, fused INSIDE the engine dispatch: per-slot base PRNG keys and
  emit indices ride through every step as batched arrays, with the noise
  key `fold_in`-derived per (request seed, emit index) — so sampled
  decode costs exactly one dispatch per tick, temperature 0 recovers the
  greedy path bit-for-bit, and same-seed runs reproduce token-for-token
  across the dense, paged, and per-slot engines AND across a
  preempt/resume cycle (the emit index never rewinds).
- ``kvcache`` / ``serve_step`` — decode-state construction (dense +
  paged layouts, slot ops) and the jitted step functions both engine
  kinds compile.
- ``sharding`` — mesh placement.  Dense and Paged engines (and
  `ContinuousBatcher`) take ``mesh=``: a jax.sharding.Mesh (axes from
  ``("pod", "data", "model")``, as launch/mesh.py builds) or a prebuilt
  `ShardingPlan`.  Placement contract: params are tensor-parallel over
  ``"model"`` via the training logical-axis rules (GQA-aware — KV heads
  replicate when n_kv does not divide the model axis); slot/batch dims —
  dense rings, paged block tables, per-dispatch token/mask/sampling rows
  — shard over the data axes, so each data shard owns a contiguous SLOT
  GROUP; the paged pool shards its KV-head axis on ``"model"`` and
  replicates pages over data.  Params and caches are `jax.device_put` at
  engine construction and the jitted steps pin ``in_shardings`` /
  ``out_shardings`` (cache donated shard-for-shard), so the whole pool
  still advances in ONE fused dispatch — the dispatch/tick contract
  reads 1.00 per MESH tick, not per device.  Guarantees: ``mesh=None``
  is today's single-device path bit-for-bit; a ``(1, 1)`` mesh traces
  the identical program (constraints no-op on one device) and is
  token-identical; the Pallas kernels are single-device and rejected
  with a mesh.  Host-side layers (scheduler/frontend) stay device-free
  but mesh-aware: per-slot-group occupancy accounting and
  ``cache_nbytes_per_device()`` (max addressable bytes on any one
  device) next to the global ``cache_nbytes()``.

Sampling contract: a request's decode policy is `Request.sampling`
(falling back to the batcher's `default_sampling`, greedy).  The chosen
token is always `argmax(scores)` where scores are raw fp32 logits
(greedy) or Gumbel-perturbed filtered logits (sampled); the per-token
top1-top2 score gap is recorded as the tie margin `completions_equivalent`
uses to compare differently-compiled engines, and the per-token
log-probability under the RAW distribution (`token_logprob`) rides every
completion — best-of-n's ranking signal.

Fork-parity contract: branch b of a `best_of=n` run is token-identical
to an independent request submitted with
``SamplingParams(seed=seed, branch=b)`` — forking changes WHERE K/V
bytes live (shared pages + CoW copies), never WHAT any branch computes.
"""
from repro.serving.kvcache import (  # noqa: F401
    DEFAULT_PAGE_SIZE,
    init_cache,
    init_paged_cache,
    cache_bytes,
    constrain_cache,
    cow_copy_pages,
    dense_cache_shardings,
    paged_attn_layout,
    paged_cache_bytes,
    paged_cache_shardings,
    reset_slots,
    slot_slice,
    slot_update,
)
from repro.serving.sharding import (  # noqa: F401
    ShardingPlan,
    tree_device_nbytes,
)
from repro.serving.sampling import (  # noqa: F401
    GREEDY,
    SamplingParams,
    SlotSampling,
    argmax_with_margin,
    batched_scores,
    branch_key,
    sampled_scores,
    token_logprob,
)
from repro.serving.serve_step import (  # noqa: F401
    make_serve_step,
    make_prefill_step,
    make_engine_step,
    make_paged_engine_step,
    make_slot_prefill_step,
    make_paged_prefill_step,
    greedy_generate,
)
from repro.serving.engine import (  # noqa: F401
    DenseEngine,
    PagedEngine,
    PerSlotEngine,
)
from repro.serving.config import (  # noqa: F401
    ServingConfig,
)
from repro.serving.scheduler import (  # noqa: F401
    ContinuousBatcher,
    DeadlineExpired,
    PageAllocator,
    PerSlotBatcher,
    RecomputeRecipe,
    Request,
    Completion,
    completions_equivalent,
)
from repro.serving.frontend import (  # noqa: F401
    RequestHandle,
    ServingFrontend,
)
from repro.serving.router import (  # noqa: F401
    ReplicaRouter,
    RouterHandle,
)
from repro.serving.telemetry import (  # noqa: F401
    DEVICE_SCOPES,
    HOST_SPANS,
    NULL_SPAN,
    TERMINAL_EVENTS,
    Counter,
    Gauge,
    Histogram,
    Telemetry,
    percentile,
    perfetto_trace,
    write_trace,
)
