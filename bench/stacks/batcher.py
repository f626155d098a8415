"""One serving replica on one chip: a ContinuousBatcher behind a
ServingFrontend, built from the cell's `serving` fields (ServingConfig).

A stack module gives `build(cfg, weights, cell, trace)`, which returns
an object with:

- `frontend(**kw)`: an async context manager over the stack's entry
  point, whose `submit(prompt, max_new, sampling=)` returns a streaming
  handle;
- `warm(reqs)`: a coroutine that serves the warm-up requests
  (bench.traffic.Req) so that every program the window runs is compiled
  or loaded, on every replica the stack holds;
- `counters()`: the program's counts, summed over the stack;
- `ticks`: the tick log of the profiling telemetry (with `trace`), or
  None;
- `pool_itemsize`: the element size of the KV pool as it is held;
- `target`: what a planted fault patches (tests and tools);
- `annotate()`: wraps each engine tick in the host span `bench.step`.
"""
import jax


class Stack:
    def __init__(self, cfg, weights, cell: dict, trace: bool):
        from repro.serving import ServingConfig
        from repro.serving.scheduler import ContinuousBatcher
        from repro.serving.telemetry import Telemetry

        self.telemetry = Telemetry(profile=True) if trace else None
        self.batcher = ContinuousBatcher(
            cfg, weights, ServingConfig(**cell["serving"],
                                        telemetry=self.telemetry))
        self.pool_itemsize = max(jax.tree.leaves(self.batcher.engine.cache),
                                 key=lambda a: a.size).dtype.itemsize
        self.target = self.batcher

    @property
    def ticks(self):
        return None if self.telemetry is None else self.telemetry.ticks

    def frontend(self, **kw):
        from repro.serving import ServingFrontend

        return ServingFrontend(self.batcher, **kw)

    async def warm(self, reqs):
        from bench.client import sampling_of

        async with self.frontend() as fe:
            handles = [await fe.submit(list(r.prompt), r.max_new,
                                       sampling=sampling_of(r))
                       for r in reqs]
            for h in handles:
                await h.result()

    def counters(self) -> dict:
        b = self.batcher
        return {"decode_ticks": b.decode_ticks,
                "prefill_dispatches": b.prefill_dispatches,
                "preemptions": b.preemptions,
                "decode_active_slots": b.decode_active_slots,
                "active_slot_steps": b.active_slot_steps,
                "completed": len(b.done)}

    def annotate(self):
        step = self.batcher.step

        def annotated():
            with jax.profiler.TraceAnnotation("bench.step"):
                return step()

        self.batcher.step = annotated


def build(cfg, weights, cell: dict, trace: bool):
    return Stack(cfg, weights, cell, trace)
