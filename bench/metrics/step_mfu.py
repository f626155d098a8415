"""The whole step's share of the chip's peak (layer: device): the
operations the served tokens needed inside the traced window, over the
window, the chips and the bf16 peak.

A decode token at context c needs 2 FLOPs per multiply-add of the layer
weights and the head, plus 4 * layers * heads * head_dim * c for
attention; a prompt needs the same per token at its own position, and
the head only for its last token.  Tokens are placed in the window by
when the client received them (a prompt by its first token)."""
from bench import roofline


def read(ctx):
    t0, t1 = ctx.marks["trace_t0"], ctx.marks["trace_t1"]
    c = ctx.cfg
    mp = roofline.matmul_params(ctx.arch, c.d_model, c.d_ff, c.vocab_size)
    attn = 4.0 * ctx.arch["layers"] * ctx.arch["heads"] * ctx.arch[
        "head_dim"]
    flops = 0.0
    for r in ctx.window.served:
        P = len(r.req.prompt)
        if r.first is not None and t0 <= r.first <= t1:
            flops += 2.0 * mp["layers"] * P + attn * P * (P + 1) / 2 \
                + 2.0 * mp["head"]
        for i, ts in enumerate(r.times[1:], start=1):
            if t0 <= ts <= t1:
                flops += roofline.token_flops(ctx.arch, mp, P + i, True)
    if flops <= 0:
        return None
    return 100.0 * flops / ((t1 - t0) * ctx.chips * ctx.peaks["flops_bf16"])
