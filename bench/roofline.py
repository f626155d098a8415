"""Peaks of the chips the benchmark runs on, and the operations and
bytes the model step and the paged-attention call need.

The counts are what the algorithm needs, worked out from shapes and
dtypes: a kernel that moves more than this shows a share below 100% of
its roofline, and no count here can exceed what any correct
implementation must do.
"""
from __future__ import annotations

# Published per-chip peaks, keyed by jax's `device_kind`.  Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9},
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of `device_kind`; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def matmul_params(arch: dict, d_model: int, d_ff: int, vocab: int) -> dict:
    """Parameters that multiply a token's activations: per layer the
    attention projections and the MLP, and the output head (the input
    embedding is a lookup, not a product)."""
    hd, H, KV = arch["head_dim"], arch["heads"], arch["kv_heads"]
    per_layer = d_model * hd * (2 * H + 2 * KV) + 3 * d_model * d_ff
    return {"layers": arch["layers"] * per_layer, "head": d_model * vocab}


def token_flops(arch: dict, mp: dict, context: int, logits: bool) -> float:
    """FLOPs one token needs at `context` attended positions: 2 per
    multiply-add of the layer weights (and of the head when the token's
    logits are needed), and 4 * layers * heads * head_dim * context for
    the scores and the weighted sum of attention."""
    f = 2.0 * mp["layers"] + 4.0 * arch["layers"] * arch["heads"] \
        * arch["head_dim"] * context
    return f + (2.0 * mp["head"] if logits else 0.0)


def paged_attention_need(arch: dict, contexts, page_size: int,
                         pool_itemsize: int, act_itemsize: int = 2):
    """(FLOPs, bytes) one decode-step paged-attention call per layer
    needs for slots at the given contexts (positions attended, the new
    token's included): each slot reads the pages that hold its context,
    K and V, in the pool's dtype, writes its new K/V row, reads its
    query and writes its output."""
    H, KV, hd = arch["heads"], arch["kv_heads"], arch["head_dim"]
    flops = 0.0
    nbytes = 0.0
    for c in contexts:
        pages = -(-c // page_size)
        flops += 4.0 * H * hd * c
        nbytes += 2.0 * pages * page_size * KV * hd * pool_itemsize
        nbytes += 2.0 * KV * hd * pool_itemsize          # new K/V row
        nbytes += 2.0 * H * hd * act_itemsize            # query, output
    return flops, nbytes
