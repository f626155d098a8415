"""Kernel microbenchmarks: Pallas (interpret) vs jnp reference wall-time and
agreement.  On CPU the interpret-mode timing is NOT a TPU performance claim —
the derived column carries the max-abs error (the correctness payload) plus
the jnp-path timing that the dry-run roofline actually models."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def _timeit(fn, *args, n=3):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / n * 1e6


def run(quick: bool = False):
    from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
    from repro.kernels.greedy_scores import ops as gs_ops, ref as gs_ref
    from repro.kernels.ssm_scan import ops as ss_ops, ref as ss_ref

    rows = []
    key = jax.random.PRNGKey(0)

    # flash attention
    B, S, H, KV, hd = 1, 512, 4, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    tr = lambda a: jnp.transpose(a, (0, 2, 1, 3))
    ref_fn = jax.jit(lambda q, k, v: fa_ref.reference_attention(
        tr(q), jnp.repeat(tr(k), H // KV, 1), jnp.repeat(tr(v), H // KV, 1)))
    us_k = _timeit(fa_ops.flash_attention, q, k, v)
    us_r = _timeit(ref_fn, q, k, v)
    err = float(jnp.max(jnp.abs(
        fa_ops.flash_attention(q, k, v) - jnp.transpose(ref_fn(q, k, v),
                                                        (0, 2, 1, 3)))))
    rows.append(("kernel_flash_attention_512", us_k,
                 f"ref_us={us_r:.0f};max_err={err:.1e}"))

    # ssm scan
    B, S, H, Dk, Dv = 1, 512, 4, 64, 64
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, S, H, Dk))
    kk = jax.random.normal(ks[1], (B, S, H, Dk))
    v = jax.random.normal(ks[2], (B, S, H, Dv))
    ld = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H, Dk)))
    us_k = _timeit(lambda *a: ss_ops.ssm_scan(*a)[0], q, kk, v, ld)
    ref_fn = jax.jit(lambda *a: ss_ref.reference_scan(*a)[0])
    us_r = _timeit(ref_fn, q, kk, v, ld)
    err = float(jnp.max(jnp.abs(ss_ops.ssm_scan(q, kk, v, ld)[0]
                                - ref_fn(q, kk, v, ld))))
    rows.append(("kernel_ssm_scan_512", us_k,
                 f"ref_us={us_r:.0f};max_err={err:.1e}"))

    # greedy scores (gram + fused scoring)
    m, n = 512, 1024
    Z = jax.random.normal(key, (m, n))
    us_k = _timeit(gs_ops.gram, Z)
    ref_fn = jax.jit(gs_ref.reference_gram)
    us_r = _timeit(ref_fn, Z)
    err = float(jnp.max(jnp.abs(gs_ops.gram(Z) - ref_fn(Z))))
    rows.append(("kernel_gram_512x1024", us_k,
                 f"ref_us={us_r:.0f};max_err={err:.1e}"))

    corr = jax.random.normal(key, (n,))
    diag = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (n,))) + 0.1
    sel = jnp.zeros((n,))
    us_k = _timeit(lambda c, d, s: gs_ops.scores_argmax(c, d, s, 0.5)[0],
                   corr, diag, sel)
    rows.append(("kernel_greedy_scores_1024", us_k, "fused scoring+argmax"))

    # paged attention v2: tile-factor sweep and pages-per-slot scaling,
    # each point vs the jitted XLA ring-gather oracle (ref.py) — kernel
    # perf tracked independently of end-to-end serving noise
    from repro.kernels.paged_attention import ops as pa_ops, ref as pa_ref

    B, H, KV, hd, psz = 4, 8, 2, 64, 16
    import numpy as np
    for P in ((4,) if quick else (4, 16)):
        n_pages = 1 + B * P
        ks = jax.random.split(jax.random.fold_in(key, P), 3)
        q = jax.random.normal(ks[0], (B, 1, H, hd))
        kp = jax.random.normal(ks[1], (n_pages, KV, psz, hd))
        vp = jax.random.normal(ks[2], (n_pages, KV, psz, hd))
        bt = jnp.asarray(np.random.default_rng(P).permutation(
            np.arange(1, n_pages)).reshape(B, P), jnp.int32)
        last = jnp.asarray(np.random.default_rng(P + 1).integers(
            psz, P * psz, B), jnp.int32)
        ref_fn = jax.jit(lambda q, kp, vp: pa_ref.reference_paged_attention(
            q[:, 0], kp, vp, bt, last))
        us_r = _timeit(ref_fn, q, kp, vp)
        want = ref_fn(q, kp, vp)
        for tk in (1, 2, 4):
            fn = lambda q, kp, vp: pa_ops.paged_attention(
                q, kp, vp, bt, last, tile_k=tk)
            us_k = _timeit(fn, q, kp, vp)
            err = float(jnp.max(jnp.abs(fn(q, kp, vp)[:, 0] - want)))
            rows.append((f"kernel_paged_attn_p{P}_k{tk}", us_k,
                         f"ref_us={us_r:.0f};ratio={us_r / us_k:.2f}x;"
                         f"max_err={err:.1e}"))
        # fused in-kernel scatter vs the XLA scatter-then-attend oracle
        S = 4
        ks = jax.random.split(jax.random.fold_in(key, 100 + P), 3)
        qb = jax.random.normal(ks[0], (B, S, H, hd))
        kn = jax.random.normal(ks[1], (B, S, KV, hd))
        vn = jax.random.normal(ks[2], (B, S, KV, hd))
        upd = lambda qb, kn, vn, kp, vp: pa_ops.paged_attention_update(
            qb, kn, vn, kp, vp, bt, last)[0]
        ref_upd = jax.jit(lambda qb, kn, vn, kp, vp:
                          pa_ref.reference_paged_update(
                              qb, kn, vn, kp, vp, bt, last)[0])
        us_k = _timeit(upd, qb, kn, vn, kp, vp)
        us_r = _timeit(ref_upd, qb, kn, vn, kp, vp)
        err = float(jnp.max(jnp.abs(upd(qb, kn, vn, kp, vp)
                                    - ref_upd(qb, kn, vn, kp, vp))))
        rows.append((f"kernel_paged_attn_update_p{P}_s{S}", us_k,
                     f"ref_us={us_r:.0f};ratio={us_r / us_k:.2f}x;"
                     f"max_err={err:.1e}"))
    return rows
