"""The split by layer (bench/scopes.py) on a small trace with known
answers, on compiled programs of a tiny engine, and the layers tool's
traced run on the CPU."""
import types

import pytest

from bench import scopes, trace

SCOPES = ("attn", "kv_pool", "sample")

# one compiled program: a fusion whose root is an attention op (the
# fusion itself carries no metadata), a pool copy, a sampler conditional
# whose branch holds a second conditional (holding a fusion) and a copy
# the compiler added, without metadata, and a fusion outside every scope
HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(f32[8]{0} %param_0, f32[8]{0} %param_0), \
metadata={op_name="jit(step)/while/body/attn/add" source_line=3}
}

%fused_computation.5 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %sort.1 = f32[8]{0} sort(f32[8]{0} %param_0.1), \
metadata={op_name="jit(step)/sample/cond/branch_1_fun/sort"}
}

%branch.4 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %fusion.5 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, \
calls=%fused_computation.5
}

%branch.3 (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  %conditional.4 = f32[8]{0} conditional(f32[8]{0} %q), \
branch_computations={%branch.4}, metadata={op_name="jit(step)/sample/cond"}
  ROOT %copy.7 = f32[8]{0} copy(f32[8]{0} %conditional.4)
}

%fused_computation.6 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  ROOT %dynamic-update-slice.1 = f32[8]{0} dynamic-update-slice(\
f32[8]{0} %param_0.2), metadata={op_name="jit(step)/while/body/dus"}
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %Arg_0.1), kind=kLoop, \
calls=%fused_computation.1
  %copy.2 = f32[8]{0} copy(f32[8]{0} %fusion.1), \
metadata={op_name="jit(step)/kv_pool/cond/copy"}
  %conditional.3 = f32[8]{0} conditional(f32[8]{0} %copy.2), \
branch_computations={%branch.3}, metadata={op_name="jit(step)/sample/cond"}
  ROOT %fusion.6 = f32[8]{0} fusion(f32[8]{0} %conditional.3), kind=kLoop, \
calls=%fused_computation.6
}
"""

_OPS = {1: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %Arg_0.1), kind=kLoop, "
           "calls=%fused_computation.1",
        2: "%copy.2 = f32[8]{0} copy(f32[8]{0} %fusion.1)",
        3: "%conditional.3 = f32[8]{0} conditional(f32[8]{0} %copy.2)",
        4: "%conditional.4 = f32[8]{0} conditional(f32[8]{0} %copy.2)",
        5: "%fusion.5 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
        6: "%fusion.6 = f32[8]{0} fusion(f32[8]{0} %conditional.3)",
        7: "%copy.7 = f32[8]{0} copy(f32[8]{0} %conditional.4)"}


def _ev(meta, start_ns, dur_ns):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


# host: a tick span (0-10 us) holding a decode dispatch, the wait for its
# result, the bookkeeping after it (over the device's idle gap, 7-9 us)
# and the next decode dispatch; chip: the decode program twice, 1-7 us
# and 9-10 us.  In the first run, conditional.3 (3-6 us) holds
# conditional.4 (3-5.5 us), which holds fusion.5 (3.5-5 us), and then
# copy.7 (5.5-6 us).
XSPACE = f"""
planes {{
  id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {_ev(1, 0, 10000)} {_ev(2, 0, 100)} {_ev(3, 1000, 6200)}
    {_ev(4, 7300, 1200)} {_ev(2, 8600, 100)} {_ev(5, 0, 1)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.step" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "paged.decode" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "engine.wait" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "sched.commit" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "bench.clock" }} }}
}}
planes {{
  id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {_ev(1, 1000, 6000)} {_ev(1, 9000, 1000)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {_ev(11, 1000, 1000)} {_ev(12, 2000, 1000)} {_ev(13, 3000, 3000)}
    {_ev(14, 3000, 2500)} {_ev(15, 3500, 1500)} {_ev(17, 5500, 500)}
    {_ev(16, 6000, 1000)} {_ev(11, 9000, 1000)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_step(22)" }} }}
""" + "".join(
    f'  event_metadata {{ key: {10 + k} value {{ id: {10 + k} '
    f'name: "{name}" }} }}\n' for k, name in _OPS.items()) + "}\n"


@pytest.fixture(scope="module")
def loaded():
    from jax.profiler import ProfileData

    pd = ProfileData.from_text_proto(XSPACE)
    return pd, trace.summarize(pd)


def test_op_names_take_a_fusions_root():
    names = scopes.op_names(HLO)
    assert names["fusion.1"] == "jit(step)/while/body/attn/add"
    assert names["fusion.5"] == "jit(step)/sample/cond/branch_1_fun/sort"
    assert names["copy.2"] == "jit(step)/kv_pool/cond/copy"
    assert names["copy.7"] == ""
    assert scopes.scope_of(names["fusion.6"], SCOPES) == scopes.NONE
    assert scopes.scope_of("jit(step)/jvp(attn)/kv_pool/x", SCOPES) == \
        "kv_pool"
    assert scopes.scope_of("jit(step)/attn_like/x", SCOPES) == scopes.NONE


def test_self_time_is_counted_once_by_scope(loaded):
    pd, summary = loaded
    split = scopes.split(pd, summary, [scopes.op_names(HLO)], SCOPES)
    (row,) = split.values()
    assert set(split) == {"paged.decode"}
    got = row["scopes"]
    assert got["attn"] == pytest.approx(2e-6)   # both runs' fusion.1
    assert got["kv_pool"] == pytest.approx(1e-6)
    # 0 + 1.0 + 1.5 + 0.5 us of self time: the conditionals' 3 us
    # interval once, where the summed op durations read 7.5 us; the copy
    # without metadata counts under the conditional that holds it
    assert got["sample"] == pytest.approx(3e-6)
    assert summary.ops["%conditional.3 = f32[8]"] == pytest.approx(3e-6)
    assert row["ops"][("%copy.7 = f32[8]", "sample")] == \
        pytest.approx(0.5e-6)
    assert got[scopes.NONE] == pytest.approx(1e-6)
    # scopes + (none) = the program's device time; every op was found
    assert sum(got.values()) == pytest.approx(row["device_s"])
    assert row["device_s"] == pytest.approx(7e-6)
    assert row["mapped_s"] == pytest.approx(row["device_s"])
    assert row["ops"][("%conditional.4 = f32[8]", "sample")] == \
        pytest.approx(1e-6)


def test_self_time_allows_for_rounding_to_whole_ns():
    """A child that ends a ns past its parent, or a sibling that starts a
    ns before its elder ends, still counts once."""
    ev = lambda s, e: types.SimpleNamespace(  # noqa: E731
        start_ns=s, end_ns=e, duration_ns=e - s)
    ops = [ev(0, 1000), ev(0, 400), ev(399, 1001), ev(500, 600)]
    own = {(e.start_ns, e.end_ns): o for e, o, _ in scopes._nest(ops)}
    assert own[(0, 1000)] == pytest.approx(-2e-9)
    assert own[(399, 1001)] == pytest.approx(502e-9)
    assert sum(own.values()) == pytest.approx(1000e-9)


def test_unknown_program_counts_under_none(loaded):
    pd, summary = loaded
    (row,) = scopes.split(pd, summary, [], SCOPES).values()
    assert row["scopes"] == {scopes.NONE: pytest.approx(7e-6)}
    assert row["mapped_s"] == 0


def test_gap_goes_to_the_innermost_program_span(loaded):
    pd, summary = loaded
    # the harness's own reduction sees only its tick span there
    assert summary.gaps == {"bench.step": pytest.approx(2e-6)}
    gaps = scopes.gaps(pd, ("sched.commit", "engine.wait"))
    assert gaps == {"sched.commit": pytest.approx(2e-6)}


def test_tick_host_time_and_queue_wait():
    ticks = [(0.0, 0.10, {"wait_s": 0.06}), (0.1, 0.12, {"wait_s": 0.10})]
    assert scopes.tick_host_s(ticks) == pytest.approx(0.03)
    assert scopes.tick_host_s([(0.0, 0.1, {})]) is None
    rec = lambda rid: types.SimpleNamespace(  # noqa: E731
        completion=None if rid is None else types.SimpleNamespace(rid=rid))
    life = {1: [(1.0, "intake", {}), (1.5, "queued", {}),
                (4.0, "prefill", {}), (4.2, "decode", {})],
            2: [(2.0, "queued", {}), (2.5, "resume", {}),
                (2.5, "prefill", {})],
            3: [(3.0, "queued", {})]}
    waits = scopes.queue_waits(life, [rec(1), rec(2), rec(3), rec(None)])
    assert waits == [pytest.approx(2.5), pytest.approx(0.5)]


def test_recorder_lowers_each_shape_once():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("sample"):
            return jnp.sort(x) * 2

    engine = types.SimpleNamespace(_decode=jax.jit(f), _prefill=jax.jit(f))
    plain = engine._decode
    rec = scopes.Recorder()
    rec.install(engine)
    for n in (4, 4, 8):
        engine._decode(jnp.ones((n,)))
    engine._prefill(jnp.ones((4,)))
    rec.remove()
    assert engine._decode is plain
    assert len(rec.lowered) == 3
    names = scopes.op_names(
        next(iter(rec.lowered.values())).compile().as_text())
    assert any(scopes.scope_of(n, SCOPES) == "sample"
               for n in names.values())


def test_layers_tool_traced_run_on_cpu(monkeypatch):
    """The tool's hooks on a tiny cell: the steps' programs are captured
    during the warm-up only, the readers' context and the stack's
    telemetry come back, and every hook is undone.  (The CPU has no
    published peaks: the roofline readers get the v5e's.)"""
    import jax

    from bench import roofline, run, spec
    from bench.tests import tiny

    monkeypatch.setattr(roofline, "peaks",
                        lambda kind: roofline.PEAKS["TPU v5 lite"])
    tool = spec.load("bench/tools/layers.py")
    cell = tiny.cell("qwen3-0.6b.chat")
    reader, reduce = spec.reader, trace.reduce
    res, ctx, tel, pd, texts = tool.traced_run(cell, 2**31 + 77, 3.0,
                                               require_chip=False)
    assert spec.reader is reader and trace.reduce is reduce
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    assert res["correct"]
    assert texts and all("op_name=" in t for t in texts)
    assert ctx.window.records and ctx.ticks
    assert all("wait_s" in a for _, _, a in ctx.ticks)
    waits = scopes.queue_waits(tel.spans, ctx.window.records)
    assert len(waits) == sum(r.completion is not None
                             for r in ctx.window.records)
    assert all(w >= 0 for w in waits)
    assert run.nearest_rank(waits, 50) >= 0
