"""jit'd wrapper: model-layout (B, S, H, D*) chunked GLA scan.

Note: the kernel returns y only; the final state (needed when training
chunks of a longer stream) is recovered by the jnp path — serving uses the
O(1) decode recurrence, so the kernel path is the training/prefill hot loop
where y is what's consumed."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.ssm_scan.ssm_scan import ssm_scan_bhsd


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssm_scan(q, k, v, ld, u=None, state=None, chunk: int = 128):
    """q/k/ld: (B, S, H, Dk), v: (B, S, H, Dv), u: (H, Dk) or None.

    Returns (y (B, S, H, Dv), final_state (B, H, Dk, Dv)).  `state` must be
    None (the kernel owns the scan from zero state)."""
    assert state is None, "kernel path starts from zero state"
    B, S, H, Dk = q.shape
    Dv = v.shape[-1]
    bonus = u is not None
    uu = u if bonus else jnp.zeros((H, Dk), jnp.float32)
    tr = lambda a: jnp.transpose(a, (0, 2, 1, 3))
    y = ssm_scan_bhsd(tr(q), tr(k), tr(v), tr(ld), uu, chunk=chunk,
                      bonus=bonus, interpret=not on_tpu())
    # final state: one closed-form pass (exact, cheap relative to the scan)
    f32 = jnp.float32
    cum = jnp.cumsum(ld.astype(f32), axis=1)
    total = cum[:, -1]  # (B, H, Dk)
    k_carry = k.astype(f32) * jnp.exp(
        jnp.maximum(total[:, None] - cum, -30.0))
    final = jnp.einsum("bshk,bshv->bhkv", k_carry, v.astype(f32))
    return jnp.transpose(y, (0, 2, 1, 3)), final
