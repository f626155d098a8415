"""Plain float32 reference of the dense decoders the benchmark serves.

Written from the published architecture (Qwen2 / Qwen3 decoder layers),
not from the program: token embedding; per layer RMSNorm, attention with
rotary positions (optional q/k/v bias, optional per-head RMSNorm of q
and k), a residual, RMSNorm, a SwiGLU MLP, a residual; a final RMSNorm
and the (tied or untied) output head.  Every product runs in float32 at
`highest` precision over the bf16 weights read as float32, layer by
layer, so only one layer's float32 copy lives at a time.

`readings` scores what a served request emitted against this model:
at every position it returns the reference's best logit, the logit of
the token that was served there, and the probability mass at the
request's temperature of the tokens that outscore it.
`control_tokens` computes the same model with every product in float8
(e4m3, scaled per row and per column), the precision below bf16, and
returns the token it ranks first at each position: put in the served
tokens' place, its float32 gap is the control's reading.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _dot(a, b, fp8: bool):
    """a @ b over the last / first axis, float32 at highest precision;
    with fp8, both operands first rounded to e4m3 (a per row, b per
    column, each scaled to the format's range)."""
    a, b = a.astype(F32), b.astype(F32)
    if fp8:
        sa = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / E4M3_MAX
        sb = jnp.max(jnp.abs(b), axis=0, keepdims=True) / E4M3_MAX
        sa, sb = jnp.maximum(sa, 1e-30), jnp.maximum(sb, 1e-30)
        a = (a / sa).astype(jnp.float8_e4m3fn).astype(F32) * sa
        b = (b / sb).astype(jnp.float8_e4m3fn).astype(F32) * sb
    return jnp.matmul(a, b, precision=HI)


def _rms(x, g, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


def _rope(x, pos, theta):
    """x: (L, H, hd), rotate-half convention (HF Qwen)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(h, p, arch: dict, fp8: bool):
    L = h.shape[0]
    H, KV, hd = arch["heads"], arch["kv_heads"], arch["head_dim"]
    eps = arch["eps"]
    x = _rms(h, p["ln1"], eps)
    q, k, v = (_dot(x, p["attn"][w], fp8) for w in ("wq", "wk", "wv"))
    if arch["qkv_bias"]:
        q = q + p["attn"]["bq"].astype(F32)
        k = k + p["attn"]["bk"].astype(F32)
        v = v + p["attn"]["bv"].astype(F32)
    q, k, v = q.reshape(L, H, hd), k.reshape(L, KV, hd), v.reshape(L, KV, hd)
    if arch["qk_norm"]:
        q, k = _rms(q, p["attn"]["q_norm"], eps), _rms(k, p["attn"]["k_norm"],
                                                      eps)
    pos = jnp.arange(L)
    q, k = _rope(q, pos, arch["theta"]), _rope(k, pos, arch["theta"])
    g = H // KV
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)  # (L, H, hd)
    qh, kh, vh = (jnp.swapaxes(t, 0, 1) for t in (q, k, v))     # (H, L, hd)
    if fp8:
        s = jax.vmap(lambda a, b: _dot(a, b.T, True))(qh, kh)
    else:
        s = jnp.einsum("hqd,hkd->hqk", qh, kh, precision=HI)
    s = s / hd ** 0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    if fp8:
        o = jax.vmap(lambda a, b: _dot(a, b, True))(pr, vh)
    else:
        o = jnp.einsum("hqk,hkd->hqd", pr, vh, precision=HI)
    h = h + _dot(jnp.swapaxes(o, 0, 1).reshape(L, H * hd), p["attn"]["wo"],
                 fp8)
    x = _rms(h, p["ln2"], eps)
    gate = _dot(x, p["mlp"]["w_gate"], fp8)
    up = _dot(x, p["mlp"]["w_up"], fp8)
    return h + _dot(jax.nn.silu(gate) * up, p["mlp"]["w_down"], fp8)


def arch_of(conf: dict) -> dict:
    """The architecture constants the reference needs, from a
    configuration file."""
    c, a = conf["config"], conf["architecture"]
    return {"layers": c["num_hidden_layers"],
            "heads": c["num_attention_heads"],
            "kv_heads": c["num_key_value_heads"],
            "head_dim": c.get("head_dim", c["hidden_size"]
                              // c["num_attention_heads"]),
            "eps": float(c["rms_norm_eps"]), "theta": float(c["rope_theta"]),
            "tied": bool(c["tie_word_embeddings"]),
            "qkv_bias": bool(a["qkv_bias"]), "qk_norm": bool(a["qk_norm"])}


def hidden(w, tokens, arch: dict, fp8: bool = False):
    """(L,) token ids -> (L, D) float32 final-normed hidden states."""
    h = w["embed"]["tok"][tokens].astype(F32)

    def body(h, p):
        return _layer(h, p, arch, fp8), None

    h, _ = jax.lax.scan(body, h, w["layers"])
    return _rms(h, w["final_norm"], arch["eps"])


def _head(w, arch):
    return w["embed"]["tok"].T if arch["tied"] else w["lm_head"]


@functools.partial(jax.jit, static_argnames=("arch_items",))
def _readings(w, tokens, targets, temps, arch_items):
    arch = dict(arch_items)
    logits = _dot(hidden(w, tokens, arch), _head(w, arch), False)  # (L, V)
    best = jnp.max(logits, -1)
    tgt = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    t = jnp.where(temps > 0, temps, 1.0)[:, None]
    pr = jax.nn.softmax(logits / t, -1)
    above = jnp.sum(jnp.where(logits > tgt[:, None], pr, 0.0), -1)
    return best, tgt, above


@functools.partial(jax.jit, static_argnames=("arch_items",))
def _control(w, tokens, arch_items):
    arch = dict(arch_items)
    low = _dot(hidden(w, tokens, arch, True), _head(w, arch), True)
    return jnp.argmax(low, -1)


def readings(w, arch: dict, tokens, targets, temps):
    """tokens (L,) fed, targets (L,) the token served after each, temps
    (L,) the temperature it was drawn at (0: greedy).  Returns float32
    arrays (best logit, served token's logit, mass above it)."""
    return _readings(w, tokens, targets, temps, tuple(sorted(arch.items())))


def control_tokens(w, arch: dict, tokens):
    """Per position of `tokens` (L,): the token that the model computed
    in float8 puts first."""
    return _control(w, tokens, tuple(sorted(arch.items())))
