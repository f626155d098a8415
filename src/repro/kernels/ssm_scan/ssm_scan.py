"""Chunked gated-linear-attention scan — Pallas TPU kernel.

One kernel serves both recurrent mixers (see models/ssm.py):
  Mamba2:  scalar per-head decay broadcast over Dk, y_t reads s_t
  RWKV6:   per-channel decay, u-bonus read of the current token, y_t reads
           s_{t-1}

TPU mapping: grid (batch, heads, n_chunks); the chunk axis is sequential
("arbitrary") and the (Dk, Dv) state matrix lives in VMEM scratch across
chunk steps — the TPU-native replacement for the GPU kernel's
shared-memory/warp-level state of the original papers.  Within a chunk the
intra-block term is a (C, C) MXU matmul, so C defaults to 128 for lane
alignment; Dk/Dv are 64/128 for all assigned archs.

Numerics match models/ssm.py's gla_chunked: decays composed in log space,
per-chunk cumulative sums clamped at -30 before exponentiation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

_CLAMP = -30.0


SUB = 16  # inner sub-chunk: pairwise decays computed directly (stable)


def _kernel(q_ref, k_ref, v_ref, ld_ref, u_ref, y_ref, state_ref,
            *, chunk: int, bonus: bool, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    u = u_ref[0].astype(jnp.float32) if bonus else None  # (1, Dk)
    sub = min(SUB, chunk)
    n_sub = chunk // sub
    ii = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    causal = (jj < ii) if bonus else (jj <= ii)
    # inclusive prefix sum along time as a lower-triangular matmul (Mosaic
    # has no cumsum); HIGHEST keeps the f32 log-decays at f32 precision
    tril = (jj <= ii).astype(jnp.float32)
    ones_col = jnp.ones((sub, 1), jnp.float32)

    s = state_ref[...]                     # (Dk, Dv) fp32
    for b in range(n_sub):                 # static unroll: mini-scan with
        sl = slice(b * sub, (b + 1) * sub)  # the state held in VMEM
        q = q_ref[0, 0, sl].astype(jnp.float32)    # (sub, Dk)
        k = k_ref[0, 0, sl].astype(jnp.float32)
        v = v_ref[0, 0, sl].astype(jnp.float32)    # (sub, Dv)
        ld = ld_ref[0, 0, sl].astype(jnp.float32)
        cum = jnp.dot(tril, ld, precision=jax.lax.Precision.HIGHEST)  # (sub, Dk)
        # bonus (RWKV) reads s_{t-1}: query-side decay excludes step t
        cum_q = cum - ld if bonus else cum
        # intra: pairwise exp(cum_i - cum_j) has exponent <= 0 for j <= i —
        # stable for any decay strength (the qd/kd matmul factorization
        # overflows fp32 beyond |cum| ~ 40).  One key row j at a time, so
        # every operand stays a 2-D (sub, Dk) tile: column j of the (sub,
        # sub) score matrix times value row j
        y = (q * jnp.exp(cum_q)) @ s               # exp(cum_q) <= 1
        for j in range(sub):
            row = slice(j, j + 1)
            diff = jnp.where(causal[:, row], cum_q - cum[row], -jnp.inf)
            a_j = jnp.sum(q * k[row] * jnp.exp(diff), axis=1, keepdims=True)
            y = y + a_j * v[row]
        if bonus:
            y = y + jnp.sum(q * u * k, axis=1, keepdims=True) * v
        total = cum[sub - 1:]                      # (1, Dk)
        # the same total as a (Dk, 1) column, to decay the state's rows
        total_col = jax.lax.dot_general(
            ld, ones_col, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST)
        k_carry = k * jnp.exp(total - cum)         # exponent <= 0
        s = (s * jnp.exp(total_col)
             + jax.lax.dot_general(k_carry, v, (((0,), (0,)), ((), ()))))
        y_ref[0, 0, sl] = y.astype(y_ref.dtype)
    state_ref[...] = s


@functools.partial(jax.jit, static_argnames=("chunk", "bonus", "interpret"))
def ssm_scan_bhsd(q, k, v, ld, u, *, chunk: int = 128, bonus: bool = False,
                  interpret: bool):
    """q/k/ld: (B, H, S, Dk), v: (B, H, S, Dv), u: (H, Dk) (ignored unless
    `bonus`).  interpret: run the Pallas interpreter instead of lowering to
    Mosaic (CPU tests).  Returns y (B, H, S, Dv)."""
    B, H, S, Dk = q.shape
    Dv = v.shape[-1]
    C = min(chunk, S)
    assert S % C == 0, (S, C)
    n_chunks = S // C

    kernel = functools.partial(_kernel, chunk=C, bonus=bonus,
                               n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, C, Dk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, Dk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, Dv), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, Dk), lambda b, h, c: (b, h, c, 0)),
            # one head's bonus row as a (1, Dk) tile: a block's last two
            # dims must be (8, 128)-aligned or whole, so u rides as (H, 1, Dk)
            pl.BlockSpec((1, 1, Dk), lambda b, h, c: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C, Dv), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, Dv), v.dtype),
        scratch_shapes=[pltpu.VMEM((Dk, Dv), jnp.float32)],
        interpret=interpret,
    )(q, k, v, ld, u.reshape(H, 1, Dk))
