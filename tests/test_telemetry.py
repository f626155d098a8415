"""Unified telemetry layer: registry semantics (counters, labeled
series, mergeable histograms), request-lifecycle span invariants across
scheduler / engine / frontend / router, Perfetto trace export, and the
``telemetry=None`` zero-overhead contract."""
import asyncio
import json
import time
import tracemalloc

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import params as Pm
from repro.serving.config import ServingConfig
from repro.serving.frontend import ServingFrontend
from repro.serving.router import ReplicaRouter
from repro.serving.scheduler import ContinuousBatcher, Request
from repro.serving.telemetry import (HOST_SPANS, TERMINAL_EVENTS, Histogram,
                                     Telemetry, percentile, perfetto_trace,
                                     write_trace)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen3_0_6b")
    params, _ = Pm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reqs(cfg, n=3, plen=4, max_new=6, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab_size, plen).tolist(),
                    max_new=max_new)
            for i in range(n)]


# ----------------------------------------------------- metrics registry


def test_counter_labels_and_totals():
    tel = Telemetry()
    c = tel.counter("sched_preemptions_total")
    c.inc(reason="forced")
    c.inc(2, reason="pool_exhausted")
    c.inc(reason="pool_exhausted")
    assert c.total == 4
    assert c.value(reason="pool_exhausted") == 3
    assert c.value(reason="migrate") == 0
    assert c.as_dict() == {"reason=forced": 1, "reason=pool_exhausted": 3}
    assert tel.counter("sched_preemptions_total") is c  # get-or-create
    u = tel.counter("engine_cow_copies_total")
    u.inc()
    u.inc(4)
    assert u.as_dict() == 5  # unlabeled series snapshot as a bare number


def test_histogram_percentiles_and_merge():
    a, b = Histogram("serving_ttft_ms"), Histogram("serving_ttft_ms")
    for x in range(1, 51):
        a.observe(float(x))
    for x in range(51, 101):
        b.observe(float(x))
    a.merge_from(b)
    assert a.count == 100 and a.sum == pytest.approx(5050.0)
    # merged percentiles are exact — identical to the helper every
    # stats() path delegates to
    want = np.arange(1, 101)
    assert a.percentile(50) == percentile(want, 50)
    assert a.percentile(95) == percentile(want, 95)
    d = a.as_dict()
    assert d["min"] == 1.0 and d["max"] == 100.0
    assert sum(d["buckets"].values()) == 100
    assert percentile([], 95) is None
    with pytest.raises(ValueError, match="mismatched buckets"):
        a.merge_from(Histogram("other", buckets=(1.0, 2.0)))


# ------------------------------------------------ lifecycle span traces


def test_span_ordering_through_the_scheduler(setup):
    """Every request's span log reads queued -> prefill -> decode ->
    finished with non-decreasing timestamps, and the tick log + gauges
    agree with the engine's own dispatch accounting."""
    cfg, params = setup
    tel = Telemetry()
    eng = ContinuousBatcher(cfg, params, ServingConfig(
        n_slots=2, capacity=64, telemetry=tel))
    reqs = _reqs(cfg)
    eng.submit(reqs)
    done, steps = eng.run()
    assert len(done) == len(reqs)
    for r in reqs:
        evs = tel.spans[r.rid]
        assert [e for _, e, _ in evs] == ["queued", "prefill", "decode",
                                          "finished"]
        ts = [t for t, _, _ in evs]
        assert ts == sorted(ts)
    assert len(tel.ticks) == steps
    # the fused tick's one-dispatch rule, from the batcher's counters
    assert eng.decode_dispatches == eng.decode_ticks
    snap = tel.snapshot()
    assert snap["requests_traced"] == len(reqs)
    assert snap["ticks"]["count"] == steps


def test_preempt_resume_spans_balanced(setup):
    """Under pool exhaustion every preempt span is matched by a later
    resume on the same rid (the drain leaves no one parked), and the
    sched_preemptions_total counter agrees with both the span log and
    the engine's own tally."""
    cfg, params = setup
    tel = Telemetry()
    # 3 usable pages; each request worst-cases 2 (prompt 4 + budget 24)
    eng = ContinuousBatcher(cfg, params, ServingConfig(
        n_slots=2, capacity=64, cache_layout="paged", n_pages=4,
        allocation="lazy", telemetry=tel))
    eng.submit(_reqs(cfg, max_new=24))
    done, _ = eng.run()
    assert len(done) == 3 and eng.preemptions > 0
    n_pre = n_res = 0
    for rid, evs in tel.spans.items():
        parked = 0
        for _, event, attrs in evs:
            if event == "preempt":
                assert attrs["reason"] == "pool_exhausted"
                parked += 1
                n_pre += 1
            elif event == "resume":
                assert parked > 0  # a resume always follows a preempt
                parked -= 1
                n_res += 1
        assert parked == 0  # balanced: nobody left parked after drain
        assert evs[-1][1] == "finished"
    assert n_pre == n_res == eng.preemptions
    assert tel.counter("sched_preemptions_total").total == n_pre
    assert tel.counter("sched_preemptions_total") \
        .value(reason="pool_exhausted") == n_pre


def test_migrated_request_carries_spans_from_both_replicas(setup):
    """A mid-flight migration leaves migrate_out on the source replica's
    telemetry and migrate_in .. finished on the destination's; the
    merged fleet view interleaves them chronologically with exactly one
    final terminal."""
    cfg, params = setup
    tels = [Telemetry(), Telemetry()]
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, 5).tolist()
               for _ in range(3)]

    async def go():
        configs = [ServingConfig(n_slots=2, capacity=96,
                                 telemetry=tels[0]),
                   ServingConfig(n_slots=2, capacity=96,
                                 cache_layout="paged", n_pages=16,
                                 allocation="lazy", telemetry=tels[1])]
        async with ReplicaRouter(cfg, params, configs,
                                 migrate_auto=False) as router:
            handles = [await router.submit(p, 8) for p in prompts]
            h = handles[0]
            while h._delivered < 2 and not h.done():
                await asyncio.sleep(0)
            migrated = False
            if not h.done():
                migrated = await router.migrate(0, 1 - h.replica)
            results = [await hh.result() for hh in handles]
            return results, migrated, router.merged_telemetry()

    results, migrated, merged = asyncio.run(go())
    assert len(results) == 3 and migrated
    src = 0 if any(e == "migrate_out"
                   for _, e, _ in tels[0].spans.get(0, [])) else 1
    src_names = [e for _, e, _ in tels[src].spans[0]]
    dst_names = [e for _, e, _ in tels[1 - src].spans[0]]
    assert src_names[-1] == "migrate_out"  # source track ENDS at the exit
    assert "migrate_in" in dst_names and dst_names[-1] == "finished"
    names = [e for _, e, _ in merged.spans[0]]
    assert names.index("migrate_out") < names.index("migrate_in")
    assert names[-1] == "finished"
    # exactly the handoff pair of terminals, nothing double-booked
    assert [n for n in names if n in TERMINAL_EVENTS] == \
        ["migrate_out", "finished"]
    # fleet outcome accounting: 2 completed-only + 1 migrated-then-done
    snap = merged.snapshot()
    assert snap["counters"]["requests_total"] == \
        {"outcome=completed": 3, "outcome=migrated": 1}
    assert snap["counters"]["requests_intake_total"] == 4  # 3 + 1 inject


# ------------------------------------------------------ Perfetto export


def test_perfetto_trace_valid_json_and_monotonic(setup, tmp_path):
    cfg, params = setup
    tel = Telemetry()
    eng = ContinuousBatcher(cfg, params, ServingConfig(
        n_slots=2, capacity=64, telemetry=tel))
    eng.submit(_reqs(cfg, n=2, max_new=5))
    eng.run()
    path = tmp_path / "trace.json"
    doc = write_trace(str(path), tel, names=["replica0"])
    assert doc == json.loads(path.read_text())  # valid, round-trips
    assert doc == perfetto_trace(tel, names=["replica0"])
    tracks: dict = {}
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("M", "X", "i")
        if ev["ph"] == "M":
            continue
        assert ev["ts"] >= 0.0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0
        tracks.setdefault((ev["pid"], ev["tid"]), []).append(ev["ts"])
    for ts in tracks.values():  # per-track timestamps monotonic
        assert ts == sorted(ts)
    # one thread per traced rid (tid 0 is the engine-tick track) and at
    # least one tick span on it
    rids = {tid - 1 for _, tid in tracks if tid > 0}
    assert rids == set(tel.spans)
    assert (0, 0) in tracks and len(tracks[(0, 0)]) == len(tel.ticks)


# -------------------------------------------------- zero-overhead rule


def test_disabled_telemetry_is_free(setup):
    """telemetry=None (the default) is the true no-op: token-, tick- and
    dispatch-identical to a traced run, with ZERO Python allocations
    attributed to telemetry.py while the untraced engine drains."""
    cfg, params = setup
    tel = Telemetry()
    on = ContinuousBatcher(cfg, params, ServingConfig(
        n_slots=2, capacity=64, telemetry=tel))
    off = ContinuousBatcher(cfg, params, ServingConfig(
        n_slots=2, capacity=64))
    for eng in (on, off):  # warm: compile every dispatch shape
        eng.submit(_reqs(cfg, seed=99))
        eng.run()
    d_on, d_off = on.decode_dispatches, off.decode_dispatches
    on.submit(_reqs(cfg, n=4, seed=13))
    on_done, on_ticks = on.run()
    tracemalloc.start()
    off.submit(_reqs(cfg, n=4, seed=13))
    off_done, off_ticks = off.run()
    snap = tracemalloc.take_snapshot().filter_traces(
        [tracemalloc.Filter(True, "*telemetry.py")])
    tracemalloc.stop()
    assert snap.statistics("filename") == []  # no telemetry code ran
    assert {c.rid: c.tokens for c in off_done} == \
        {c.rid: c.tokens for c in on_done}
    assert off_ticks == on_ticks
    assert off.decode_dispatches - d_off == on.decode_dispatches - d_on
    assert tel.snapshot()["span_events"] > 0  # the traced arm did record


# ---------------------------------------- host spans, clock, wait time


class _Raise:
    def __init__(self, name):
        raise AssertionError(f"span {name!r} opened without a sink")


def test_untraced_tick_opens_no_span(setup, monkeypatch):
    """A telemetry=None stack opens no host span: TraceAnnotation is
    never constructed through a frontend-driven tick."""
    cfg, params = setup
    eng = ContinuousBatcher(cfg, params, ServingConfig(
        n_slots=2, capacity=64, cache_layout="paged"))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Raise)

    async def serve():
        async with ServingFrontend(eng) as fe:
            hs = [await fe.submit(r.prompt, r.max_new) for r in _reqs(cfg)]
            return [await h.result() for h in hs]

    assert len(asyncio.run(serve())) == 3
    assert eng.decode_ticks > 0


def test_profiled_stack_opens_declared_spans(setup, monkeypatch):
    """With profiling on, every span a tick opens is declared in
    HOST_SPANS, and the frontend, scheduler and engine each open theirs."""
    cfg, params = setup
    tel = Telemetry(profile=True)
    eng = ContinuousBatcher(cfg, params, ServingConfig(
        n_slots=2, capacity=64, cache_layout="paged", telemetry=tel))
    opened = []

    class Record:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Record)

    async def serve():
        async with ServingFrontend(eng) as fe:
            hs = [await fe.submit(r.prompt, r.max_new) for r in _reqs(cfg)]
            return [await h.result() for h in hs]

    asyncio.run(serve())
    assert set(opened) <= set(HOST_SPANS)
    assert {"frontend.intake", "frontend.pump", "sched.admit",
            "sched.pages", "sched.inputs", "sched.commit", "paged.decode",
            "paged.prefill", "engine.wait"} <= set(opened)
    # one engine.wait per dispatch: the result fetch after each
    assert opened.count("engine.wait") == \
        eng.decode_dispatches + eng.prefill_dispatches


def test_timestamps_on_the_monotonic_clock(setup):
    """Lifecycle events and ticks are stamped on time.monotonic, the
    asyncio loop's clock: every stamp lies between readings of it
    taken around the run."""
    cfg, params = setup
    assert Telemetry.now is time.monotonic
    tel = Telemetry()
    eng = ContinuousBatcher(cfg, params, ServingConfig(
        n_slots=2, capacity=64, telemetry=tel))
    before = time.monotonic()
    eng.submit(_reqs(cfg))
    eng.run()
    after = time.monotonic()
    stamps = [t for evs in tel.spans.values() for t, _, _ in evs]
    stamps += [t0 for t0, _, _ in tel.ticks]
    stamps += [t0 + d for t0, d, _ in tel.ticks]
    assert len(stamps) > 3 * len(tel.ticks) // 2
    assert all(before <= t <= after for t in stamps)


def test_tick_records_its_wait(setup):
    """Every tick books wait_s: the seconds spent fetching dispatch
    results, within the tick's wall time and nonzero when it
    dispatched; the ticks' waits add up to the sink's total."""
    cfg, params = setup
    tel = Telemetry()
    eng = ContinuousBatcher(cfg, params, ServingConfig(
        n_slots=2, capacity=64, telemetry=tel))
    eng.submit(_reqs(cfg))
    eng.run()
    assert tel.ticks
    for _, dur, attrs in tel.ticks:
        assert 0.0 <= attrs["wait_s"] <= dur
        if attrs["dispatches"]:
            assert attrs["wait_s"] > 0.0
    assert sum(a["wait_s"] for _, _, a in tel.ticks) == \
        pytest.approx(tel.waited_s)


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_tick_counts_streamed_kv_pages(setup, kernel):
    """Each tick books the pages its Pallas decode dispatch streams,
    sum over the lanes of min(P, ceil((pos + 1) / page_size)) at the
    dispatch, against its block table's n_slots * P; both are 0 on the
    XLA path and on a tick with no decode dispatch."""
    cfg, params = setup
    tel = Telemetry()
    eng = ContinuousBatcher(cfg, params, ServingConfig(
        n_slots=2, capacity=64, cache_layout="paged", kernel=kernel,
        allocation="lazy", telemetry=tel))
    pe = eng.engine
    ps, P = pe.page_size, pe.pages_per_slot
    seen = []
    decode = pe.decode

    def spy(*a, **k):
        seen.append(sum(min(P, -(-(int(p) + 1) // ps))
                        for p in pe.slot_pos))
        return decode(*a, **k)

    pe.decode = spy
    eng.submit(_reqs(cfg, n=3, plen=20, max_new=6))
    eng.run()
    assert seen and len(seen) == eng.decode_ticks
    ticks = [a for _, _, a in tel.ticks]
    if kernel == "xla":
        assert all(a["kv_pages"] == a["kv_pages_table"] == 0 for a in ticks)
        return
    decoded = [a for a in ticks if a["kv_pages_table"]]
    assert [a["kv_pages"] for a in decoded] == seen
    assert all(a["kv_pages_table"] == 2 * P for a in decoded)
    assert sum(a["kv_pages"] for a in ticks) < sum(
        a["kv_pages_table"] for a in ticks)
