"""The Pallas paged-attention kernel's share of its roofline in the
decode step (layer: kernels): the least time the chip needs for the
work the decode calls do, max(FLOPs / peak, bytes / HBM bandwidth),
over the kernel's device time inside the decode program.

The work is that of the slots the decode ticks of the traced window
advanced: per layer, each reads the pages that hold its context, in the
pool's dtype as the engine holds it
(bench/roofline.paged_attention_need).  A decode token i of
a request of prompt P attends to P + i positions."""
from bench import roofline

SPANS = ("paged.decode", "dense.decode")


def read(ctx):
    progs = [p for s in SPANS for p in ctx.trace.label(s)]
    kernel_s = sum(p["kernel_s"] for p in progs)
    if kernel_s <= 0:
        return None
    t0, t1 = ctx.marks["trace_t0"], ctx.marks["trace_t1"]
    contexts = []
    for r in ctx.window.served:
        P = len(r.req.prompt)
        contexts += [P + i for i, ts in enumerate(r.times[1:], start=1)
                     if t0 <= ts <= t1]
    if not contexts:
        return None
    flops, nbytes = roofline.paged_attention_need(
        ctx.arch, contexts, ctx.cell["serving"].get("page_size", 16),
        pool_itemsize=ctx.pool_itemsize)
    L = ctx.arch["layers"]
    bound = max(L * flops / ctx.peaks["flops_bf16"],
                L * nbytes / ctx.peaks["hbm_bw"])
    return 100.0 * bound / kernel_s
