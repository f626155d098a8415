"""Paged KV pool: PageAllocator admission / exhaustion / refcounted prefix
sharing, and cache-byte accounting of the paged layout."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import params as Pm
from repro.serving.kvcache import (DEFAULT_PAGE_SIZE, cache_bytes,
                                   paged_attn_layout, paged_cache_bytes)
from repro.serving.sampling import SamplingParams
from repro.serving.scheduler import (ContinuousBatcher, PageAllocator,
                                     Request, completions_equivalent)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen3_0_6b")
    params, _ = Pm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


# ------------------------------------------------------------- allocator


def test_allocator_alloc_release_roundtrip():
    al = PageAllocator(n_pages=5, page_size=16)
    assert al.n_free == 4  # page 0 reserved as null
    pages = [al.alloc() for _ in range(4)]
    assert 0 not in pages and al.n_free == 0
    for p in pages:
        al.release(p)
    assert al.n_free == 4 and al.in_use == 0
    al.release(0)  # null page release is a no-op
    assert al.n_free == 4


def test_allocator_refcounted_prefix_pages():
    al = PageAllocator(n_pages=6, page_size=4)
    key = ((), (1, 2, 3, 4))
    pid = al.alloc()
    al.register_prefix(key, pid)
    assert al.lookup_prefix(key) == pid
    al.share(pid)          # a second sharer
    al.release(pid)          # first sharer finishes
    # the page survives and stays shareable while one sharer holds it
    assert al.refcount[pid] == 1 and al.lookup_prefix(key) == pid
    al.release(pid)          # last sharer finishes
    assert al.lookup_prefix(key) is None and pid in al._free


def test_allocator_page_publishes_one_prefix_key():
    """A page registered under a second key keeps only the first: freeing
    the page must leave no key that serves it (the falsifying example
    hypothesis found for test_page_allocator_invariants)."""
    al = PageAllocator(n_pages=2, page_size=4)
    first, second = ((), (0,)), ((), (1,))
    pid = al.alloc()
    al.register_prefix(first, pid)
    al.register_prefix(second, pid)
    assert al.lookup_prefix(first) == pid
    assert al.lookup_prefix(second) is None
    al.release(pid)
    assert al.lookup_prefix(first) is None and pid in al._free


def test_pool_exhaustion_stalls_then_resumes(setup):
    """With a pool that fits one request at a time the queue must stall
    (not crash) and admission must resume as finished slots reclaim."""
    cfg, params = setup
    eng = ContinuousBatcher(cfg, params, n_slots=2, capacity=32,
                            cache_layout="paged", n_pages=3,
                            share_prefix=False)  # 2 usable pages
    # prompt 3 + budget 20 = 23 tokens -> 2 pages: one request at a time
    reqs = [Request(rid=i, prompt=[1 + i, 2, 3], max_new=20)
            for i in range(3)]
    eng.submit(reqs)
    stalled = False
    steps = 0
    while eng.queue or any(r is not None for r in eng.slot_req):
        eng.step()
        steps += 1
        # only one slot can hold pages at a time => the other stays empty
        assert sum(r is not None for r in eng.slot_req) <= 1
        stalled = stalled or bool(eng.queue)
        assert steps < 500
    assert stalled
    assert sorted(c.rid for c in eng.done) == [0, 1, 2]
    assert eng.allocator.in_use == 0  # everything reclaimed


def test_oversized_request_rejected_at_submit(setup):
    """A request whose worst-case page budget can NEVER fit the pool must
    be rejected at submit() — queued, it would stall the FIFO head forever
    and run() would spin to max_steps completing nothing."""
    cfg, params = setup
    eng = ContinuousBatcher(cfg, params, n_slots=1, capacity=64,
                            cache_layout="paged", n_pages=2)
    with pytest.raises(ValueError, match="pages"):
        eng.submit([Request(rid=0, prompt=list(range(1, 40)), max_new=30)])
    # submit is atomic: a batch with one infeasible request enqueues
    # nothing, and the engine still serves feasible traffic afterwards
    with pytest.raises(ValueError, match="pages"):
        eng.submit([Request(rid=1, prompt=[1, 2], max_new=3),
                    Request(rid=2, prompt=list(range(1, 40)), max_new=30)])
    assert not eng.queue
    eng.submit([Request(rid=3, prompt=[1, 2], max_new=3)])
    done, steps = eng.run()
    assert [c.rid for c in done] == [3] and steps < 100


def test_allocator_over_release_asserts():
    al = PageAllocator(n_pages=4, page_size=16)
    pid = al.alloc()
    al.release(pid)
    with pytest.raises(AssertionError, match="over-released"):
        al.release(pid)
    # acquiring a dead page is refused too (it is no longer shareable)
    with pytest.raises(AssertionError, match="not live"):
        al.share(pid)


def test_prefix_registry_never_hands_out_reclaimed_pages():
    """After the LAST sharer frees a shared prompt page, its prefix entry
    must die with it: a later lookup_prefix must miss (or see a LIVE page
    a new writer re-registered), never a reclaimed/recycled page id."""
    al = PageAllocator(n_pages=3, page_size=4)
    key = ((), (1, 2, 3, 4))
    pid = al.alloc()
    al.register_prefix(key, pid)
    al.share(pid)          # second sharer
    al.release(pid)          # first sharer done — page must stay indexed
    assert al.lookup_prefix(key) == pid
    al.release(pid)          # last sharer done — entry must die
    assert al.lookup_prefix(key) is None
    # the recycled page now backs a DIFFERENT prompt: the old key must
    # not resolve to it
    other = ((), (9, 9, 9, 9))
    reused = al.alloc()
    al.register_prefix(other, reused)
    assert reused == pid  # same physical page recycled
    assert al.lookup_prefix(key) is None
    assert al.lookup_prefix(other) == reused
    # and a new writer re-registering the ORIGINAL key under a fresh page
    # serves that live page
    fresh = al.alloc()
    al.register_prefix(key, fresh)
    assert al.lookup_prefix(key) == fresh


def test_allocator_interleaved_release_keeps_pages_distinct():
    """Exhaust the pool, release in interleaved (non-LIFO) order, then
    re-exhaust: every handed-out page must be live-unique, and free
    accounting must stay exact through the interleaving."""
    al = PageAllocator(n_pages=7, page_size=16)
    pages = [al.alloc() for _ in range(6)]
    assert al.n_free == 0
    for pid in pages[::2]:       # release evens first,
        al.release(pid)
    for pid in pages[1::2]:      # then odds
        al.release(pid)
    assert al.n_free == 6 and al.in_use == 0
    again = [al.alloc() for _ in range(6)]
    assert sorted(again) == sorted(pages)  # same physical pool
    assert len(set(again)) == 6            # no page handed out twice


def test_exhaustion_stall_resumes_in_fifo_order(setup):
    """Pool exhaustion must stall admission FIFO and resume it in FIFO
    order as interleaved releases reclaim pages: budgets are staggered so
    slots free at different ticks, and every resume must admit the oldest
    queued request."""
    cfg, params = setup
    eng = ContinuousBatcher(cfg, params, n_slots=3, capacity=32,
                            cache_layout="paged", n_pages=5,
                            share_prefix=False)  # 4 usable pages
    # each request reserves 2 pages (prompt 3 + budget 20/29 tokens), so
    # the POOL caps concurrency at 2 although 3 slots exist; staggered
    # budgets make the two in-flight sequences finish at different ticks
    reqs = [Request(rid=i, prompt=[1 + i, 2, 3], max_new=20 + 9 * (i % 2))
            for i in range(6)]
    eng.submit(reqs)
    admitted = []
    seen = set()
    stalled = False
    steps = 0
    while eng.queue or any(r is not None for r in eng.slot_req):
        eng.step()
        steps += 1
        # the pool (not the slot count) is the binding constraint
        assert sum(r is not None for r in eng.slot_req) <= 2
        for r in eng.slot_req:
            if r is not None and r.rid not in seen:
                seen.add(r.rid)
                admitted.append(r.rid)
        stalled = stalled or bool(eng.queue)
        assert steps < 1000
    assert stalled
    assert admitted == sorted(admitted), admitted  # FIFO resume order
    assert sorted(c.rid for c in eng.done) == list(range(6))
    assert eng.allocator.in_use == 0 and eng.allocator.n_free == 4


# -------------------------------------------------------- prefix sharing


def _shared_prompt_reqs(n=3, plen=36, max_new=4):
    sysp = list(range(1, plen + 1))
    return [Request(rid=i, prompt=sysp + [50 + i], max_new=max_new)
            for i in range(n)]


def test_prefix_sharing_saves_pages_and_matches_dense(setup):
    cfg, params = setup
    shared = ContinuousBatcher(cfg, params, n_slots=3, capacity=64,
                               cache_layout="paged")
    unshared = ContinuousBatcher(cfg, params, n_slots=3, capacity=64,
                                 cache_layout="paged", share_prefix=False)
    dense = ContinuousBatcher(cfg, params, n_slots=3, capacity=64)
    outs = {}
    for tag, eng in [("shared", shared), ("unshared", unshared),
                     ("dense", dense)]:
        eng.submit(_shared_prompt_reqs())
        outs[tag] = eng.run()[0]
    assert completions_equivalent(outs["shared"], outs["dense"]), \
        [(c.tokens, c.margins) for c in outs["shared"]]
    assert completions_equivalent(outs["unshared"], outs["dense"])
    # the 36-token common prefix spans 2 full pages refcounted once
    assert shared.allocator.peak_in_use < unshared.allocator.peak_in_use
    # skipping the shared tokens also skips their prefill work
    assert shared.active_slot_steps < unshared.active_slot_steps
    for eng in (shared, unshared):
        assert eng.allocator.in_use == 0


def test_prefix_pages_survive_one_sharer_finishing(setup):
    """A prefix page shared by two live requests must survive the first
    sharer finishing, and the survivor must decode correctly past it."""
    cfg, params = setup
    sysp = list(range(1, 33))  # 2 full pages at page_size=16
    eng = ContinuousBatcher(cfg, params, n_slots=2, capacity=64,
                            cache_layout="paged")
    short = Request(rid=0, prompt=sysp + [40], max_new=4)
    long = Request(rid=1, prompt=sysp + [41], max_new=10)
    eng.submit([short, long])
    eng.step()  # both prefilled; prefix pages now refcounted by both
    prefix_pages = [p for p in eng.slot_pages[0] if p in eng.slot_pages[1]]
    assert len(prefix_pages) == 2
    for p in prefix_pages:
        assert eng.allocator.refcount[p] == 2
    saw_survivor = False
    while any(r is not None for r in eng.slot_req) or eng.queue:
        eng.step()
        if eng.slot_req[0] is None and eng.slot_req[1] is not None:
            # short finished, long still running: shared pages live on
            saw_survivor = True
            for p in prefix_pages:
                assert eng.allocator.refcount[p] == 1
    assert saw_survivor
    done = {c.rid: c for c in eng.done}
    assert len(done[1].tokens) == 10
    assert eng.allocator.in_use == 0

    fresh = ContinuousBatcher(cfg, params, n_slots=2, capacity=64)
    fresh.submit([Request(rid=1, prompt=sysp + [41], max_new=10)])
    want = {c.rid: c for c in fresh.run()[0]}
    assert completions_equivalent([done[1]], [want[1]])


def test_sharing_disabled_when_ring_wraps(setup):
    cfg, _ = setup
    cfg = cfg.replace(sliding_window=16)
    params, _ = Pm.init_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatcher(cfg, params, n_slots=2, capacity=64,
                            cache_layout="paged")
    assert not eng._share  # a wrapped ring would overwrite prefix entries


# ------------------------------------------------------- byte accounting


def test_paged_cache_bytes_agrees_with_layout(setup):
    cfg, params = setup
    n_slots, capacity, n_pages, ps = 4, 64, 9, DEFAULT_PAGE_SIZE
    eng = ContinuousBatcher(cfg, params, n_slots=n_slots, capacity=capacity,
                            cache_layout="paged", n_pages=n_pages)
    pages_per_slot, _ = paged_attn_layout(cfg, capacity, ps)
    # exact layout contract: L x {k,v} pools of (n_pages, ps, KV, hd)
    # entries plus the (n_slots, pages_per_slot) int32 table and int32 pos
    def expect(itemsize):
        pool = (cfg.n_layers * 2 * n_pages * ps * cfg.n_kv_heads
                * cfg.head_dim * itemsize)
        return pool + n_slots * pages_per_slot * 4 + n_slots * 4

    # the live engine holds f32 pools (CPU tests); the quote uses cfg.dtype
    assert eng.cache_nbytes() == expect(4)
    assert paged_cache_bytes(cfg, n_slots, capacity, n_pages) == \
        expect(np.dtype(np.float16).itemsize if cfg.dtype == "bfloat16"
               else np.dtype(cfg.dtype).itemsize)


def test_paged_beats_dense_bytes_at_skewed_capacity(setup):
    """Provisioning for a rare long request: dense pays (n_slots, capacity)
    everywhere; the paged pool pays only the pages the mix actually
    needs."""
    cfg, _ = setup
    n_slots, capacity = 8, 256
    pages_per_slot, _ = paged_attn_layout(cfg, capacity)
    # pool sized for a mostly-short mix: 1/4 of full provisioning
    n_pages = 1 + n_slots * pages_per_slot // 4
    dense = cache_bytes(cfg, n_slots, capacity)
    paged = paged_cache_bytes(cfg, n_slots, capacity, n_pages)
    assert paged < 0.5 * dense


def test_paged_engine_equivalent_on_skewed_mix(setup):
    """The under-provisioned pool of the bytes test still serves a skewed
    prompt mix to the same tokens as the dense engine."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size,
                                        4 if i % 4 else 40).tolist(),
                    max_new=int(rng.integers(2, 6)))
            for i in range(8)]
    pages_per_slot, _ = paged_attn_layout(cfg, 64)
    paged = ContinuousBatcher(cfg, params, n_slots=4, capacity=64,
                              cache_layout="paged",
                              n_pages=1 + 4 * pages_per_slot // 2)
    dense = ContinuousBatcher(cfg, params, n_slots=4, capacity=64)
    outs = {}
    for tag, eng in [("paged", paged), ("dense", dense)]:
        eng.submit([Request(r.rid, list(r.prompt), r.max_new)
                    for r in reqs])
        outs[tag] = eng.run()[0]
    assert completions_equivalent(outs["paged"], outs["dense"])
    assert paged.cache_nbytes() < dense.cache_nbytes()
    assert DEFAULT_PAGE_SIZE == paged.page_size


# -------------------------------------------------- copy-on-write forking


def test_allocator_fork_and_ensure_private():
    """The CoW ownership rule at the allocator: fork refcounts a block
    table's worth of pages; ensure_private is identity for a sole holder
    and swaps reference-for-replacement when other holders remain."""
    al = PageAllocator(n_pages=8, page_size=16)
    pages = [al.alloc(), al.alloc()]
    al.fork(pages)  # a branch now shares both
    assert all(al.refcount[p] == 2 for p in pages)
    # shared write triggers the copy transition: the writer gives up its
    # reference, the page stays live for the other holder
    new, copied = al.ensure_private(pages[0])
    assert copied and new not in pages
    assert al.refcount[pages[0]] == 1 and al.refcount[new] == 1
    # sole holder writes in place — no page churn
    same, copied = al.ensure_private(pages[0])
    assert same == pages[0] and not copied
    # a caller-reserved replacement page is honored (worst-case admission
    # pre-allocates the CoW reserve)
    al.fork([pages[1]])
    rsv = al.alloc()
    got, copied = al.ensure_private(pages[1], reserved=rsv)
    assert copied and got == rsv
    # the null page is never written
    with pytest.raises(AssertionError, match="never written"):
        al.ensure_private(0)


def test_fork_shares_pages_and_leaks_nothing(setup):
    """A best_of group must share all full prompt pages (one physical
    copy, n references), copy only on write, and return the pool to empty
    when the group finishes."""
    cfg, params = setup
    ps = DEFAULT_PAGE_SIZE
    prompt = list(range(1, 2 * ps + 4))  # 2 full pages + a partial
    eng = ContinuousBatcher(cfg, params, n_slots=4, capacity=64,
                            cache_layout="paged")
    free0 = eng.allocator.n_free
    eng.submit([Request(rid=0, prompt=prompt, max_new=6,
                        sampling=SamplingParams(temperature=0.9, seed=9),
                        best_of=3)])
    eng.step()  # admit (prefill once, fork twice) + first decode tick
    prim, b1, b2 = eng.slot_pages[0], eng.slot_pages[1], eng.slot_pages[2]
    # the fork page (holding the last prompt token) is already re-written
    # — and so copied — by the first tick; the FULL prompt pages before it
    # stay physically shared for the group's whole lifetime
    full = len(prompt) // ps
    shared = prim[:full]
    assert b1[:full] == shared == b2[:full]
    for p in shared:
        assert eng.allocator.refcount[p] == 3
    # past the fork point every branch owns a private page
    assert len({prim[full], b1[full], b2[full]}) == 3
    assert eng.prefill_dispatches > 0
    pre = eng.prefill_dispatches
    eng.run()
    assert eng.prefill_dispatches == pre  # branches never re-prefilled
    # full prefix pages stayed shared for the whole run: only the fork
    # page (and decode-growth pages) were ever copied
    assert eng.cow_copies >= 2
    assert eng.allocator.in_use == 0 and eng.allocator.n_free == free0


@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_fork_parity_oracle(setup, temperature):
    """Greedy AND sampled: every branch of a forked run token-matches an
    independent request carrying that branch's key (see
    test_serving_batched.py for the cross-allocation variant)."""
    import dataclasses
    cfg, params = setup
    sp = SamplingParams(temperature=temperature, top_k=8, seed=321)
    prompt = list(range(2, 22))
    fork = ContinuousBatcher(cfg, params, n_slots=3, capacity=48,
                             cache_layout="paged")
    fork.submit([Request(rid=5, prompt=list(prompt), max_new=6,
                         sampling=sp, best_of=3)])
    fork.run()
    branches = fork.group_results[5]
    solo = ContinuousBatcher(cfg, params, n_slots=3, capacity=48,
                             cache_layout="paged", share_prefix=False)
    solo.submit([Request(rid=b, prompt=list(prompt), max_new=6,
                         sampling=dataclasses.replace(sp, branch=b))
                 for b in range(3)])
    want = {c.rid: c for c in solo.run()[0]}
    for b in range(3):
        assert completions_equivalent(
            [dataclasses.replace(branches[b], rid=0)],
            [dataclasses.replace(want[b], rid=0)]), b
