"""Pallas paged-attention decode kernel vs the pure-jnp oracle (interpret
mode on CPU): GQA head-group ratios, ragged per-slot positions, page-
boundary lengths, ring wrap, sliding windows, and null-page masking."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import ops as pa_ops
from repro.kernels.paged_attention import ref as pa_ref

PSZ = 16


def _pool_setup(key, B, H, KV, hd, pages_per_slot, n_pages, psz=PSZ):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    k_pool = jax.random.normal(ks[1], (n_pages, KV, psz, hd))
    v_pool = jax.random.normal(ks[2], (n_pages, KV, psz, hd))
    return q, k_pool, v_pool


def _check(q, k_pool, v_pool, bt, last, window=0, tol=2e-6):
    out = pa_ops.paged_attention(q, k_pool, v_pool, bt, last, window=window)
    want = pa_ref.reference_paged_attention(q[:, 0], k_pool, v_pool, bt,
                                            last, window=window)
    np.testing.assert_allclose(np.asarray(out[:, 0], np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 5)


@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (8, 2), (4, 1)])
@pytest.mark.parametrize("hd", [64, 128])
def test_gqa_ratios(H, KV, hd):
    """Every GQA grouping (incl. MHA and MQA) matches the oracle."""
    B, P, n_pages = 3, 4, 13
    q, kp, vp = _pool_setup(jax.random.PRNGKey(0), B, H, KV, hd, P, n_pages)
    bt = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, 13)).reshape(B, P), jnp.int32)
    last = jnp.array([37, 5, 60], jnp.int32)
    _check(q, kp, vp, bt, last)


def test_ragged_positions():
    """Each slot attends exactly to its own prefix — per-slot positions
    are fully independent (the slot-batched serving shape)."""
    B, H, KV, hd, P = 5, 4, 2, 64, 3
    q, kp, vp = _pool_setup(jax.random.PRNGKey(1), B, H, KV, hd, P, 16)
    bt = jnp.arange(1, 1 + B * P, dtype=jnp.int32).reshape(B, P)
    last = jnp.array([0, 1, 15, 16, 40], jnp.int32)
    _check(q, kp, vp, bt, last)


@pytest.mark.parametrize("last", [PSZ - 1, PSZ, 2 * PSZ - 1, 2 * PSZ])
def test_page_boundary_lengths(last):
    """Sequence lengths straddling page boundaries (the off-by-one zone of
    the page-tile masking)."""
    B, H, KV, hd, P = 1, 4, 2, 64, 3
    q, kp, vp = _pool_setup(jax.random.PRNGKey(2), B, H, KV, hd, P, 8)
    bt = jnp.array([[2, 5, 7]], jnp.int32)
    _check(q, kp, vp, bt, jnp.array([last], jnp.int32))


def test_ring_wrap():
    """last >= T: the logical ring has wrapped and older entries were
    overwritten — validity must admit exactly the most recent T
    positions."""
    B, H, KV, hd, P = 2, 4, 2, 64, 2
    T = P * PSZ
    q, kp, vp = _pool_setup(jax.random.PRNGKey(3), B, H, KV, hd, P, 8)
    bt = jnp.array([[1, 2], [3, 4]], jnp.int32)
    _check(q, kp, vp, bt, jnp.array([T, 3 * T + 7], jnp.int32))


@pytest.mark.parametrize("window", [8, 20, 31])
def test_sliding_window(window):
    """Windows that are not page-aligned: masking happens mid-tile (the
    paged logical ring rounds the window UP to whole pages, so in-kernel
    window masking is load-bearing, not redundant)."""
    B, H, KV, hd, P = 2, 4, 2, 64, 3
    q, kp, vp = _pool_setup(jax.random.PRNGKey(4), B, H, KV, hd, P, 9)
    bt = jnp.array([[1, 2, 3], [4, 5, 6]], jnp.int32)
    _check(q, kp, vp, bt, jnp.array([45, 12], jnp.int32), window=window)


def test_null_page_masking():
    """Unallocated block-table rows park on the reserved null page 0; its
    garbage entries must be invisible.  Slot 0 holds a live 1-token
    sequence; slot 1 is an idle lane entirely on the null page — its
    output is a don't-care but must be finite (no NaN from an all-masked
    softmax)."""
    B, H, KV, hd, P = 2, 4, 2, 64, 3
    q, kp, vp = _pool_setup(jax.random.PRNGKey(5), B, H, KV, hd, P, 8)
    # poison the null page: if it leaks through the mask, outputs explode
    kp = kp.at[0].set(1e4)
    vp = vp.at[0].set(1e4)
    bt = jnp.array([[7, 0, 0], [0, 0, 0]], jnp.int32)
    last = jnp.array([0, 0], jnp.int32)
    out = pa_ops.paged_attention(q, kp, vp, bt, last)
    want = pa_ref.reference_paged_attention(q[:, 0], kp, vp, bt, last)
    assert np.isfinite(np.asarray(out)).all()
    # slot 0 saw only its own page-7 entry at ring index 0
    np.testing.assert_allclose(np.asarray(out[0, 0], np.float32),
                               np.asarray(want[0], np.float32),
                               rtol=2e-6, atol=1e-5)
    assert np.abs(np.asarray(out[0])).max() < 1e3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pool_dtypes(dtype):
    """Narrower KV-pool storage (kv_cache_dtype) accumulates in fp32."""
    B, H, KV, hd, P = 2, 4, 2, 64, 2
    q, kp, vp = _pool_setup(jax.random.PRNGKey(6), B, H, KV, hd, P, 8)
    bt = jnp.array([[1, 2], [3, 4]], jnp.int32)
    last = jnp.array([20, 9], jnp.int32)
    out = pa_ops.paged_attention(q, kp.astype(dtype), vp.astype(dtype),
                                 bt, last)
    want = pa_ref.reference_paged_attention(
        q[:, 0], kp.astype(dtype), vp.astype(dtype), bt, last)
    tol = 2e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out[:, 0], np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 5)


def test_matches_layers_xla_gather_path():
    """The kernel must agree with the exact XLA path models/layers.py
    runs under kernel="xla" — gather the ring, mask by validity, jnp
    softmax — on a shared-pool state two ragged slots wrote themselves."""
    from repro.models import layers as Lyr

    B, H, KV, hd, P, psz = 2, 4, 2, 64, 3, PSZ
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    k_pool = jax.random.normal(ks[1], (8, KV, psz, hd))
    v_pool = jax.random.normal(ks[2], (8, KV, psz, hd))
    bt = jnp.array([[1, 2, 3], [4, 5, 6]], jnp.int32)
    last = jnp.array([18, 3], jnp.int32)

    T = P * psz
    ring = jnp.arange(T)
    ck = k_pool[bt[:, ring // psz], :, ring % psz]  # (B, T, KV, hd)
    cv = v_pool[bt[:, ring // psz], :, ring % psz]
    k_pos = pa_ref.ring_positions(last, T)
    mask = Lyr._attn_mask(last[:, None], k_pos) & (k_pos >= 0)[:, None, :]
    want = Lyr.multi_head_attention(q, ck, cv, mask, dtype=q.dtype)

    out = pa_ops.paged_attention(q, k_pool, v_pool, bt, last)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-6, atol=1e-5)


# ------------------------------------------------------ v2: S>1 query blocks


def _block_setup(key, B, S, H, KV, hd, n_pages, psz=PSZ):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k_pool = jax.random.normal(ks[1], (n_pages, KV, psz, hd))
    v_pool = jax.random.normal(ks[2], (n_pages, KV, psz, hd))
    k_new = jax.random.normal(ks[3], (B, S, KV, hd))
    v_new = jax.random.normal(ks[4], (B, S, KV, hd))
    return q, k_pool, v_pool, k_new, v_new


@pytest.mark.parametrize("S", [2, 5, 16])
@pytest.mark.parametrize("window", [0, 20])
def test_s_block_parity(S, window):
    """S>1 query blocks (chunked prefill / resume-recompute shapes) match
    the block oracle, per-row causal masking included."""
    B, H, KV, hd, P = 3, 4, 2, 64, 3
    q, kp, vp, _, _ = _block_setup(jax.random.PRNGKey(10), B, S, H, KV,
                                   hd, 10)
    bt = jnp.asarray(np.random.default_rng(1).permutation(
        np.arange(1, 10)).reshape(B, P), jnp.int32)
    last = jnp.array([S - 1, S + 3, 60], jnp.int32)
    out = pa_ops.paged_attention(q, kp, vp, bt, last, window=window)
    want = pa_ref.reference_paged_attention_block(q, kp, vp, bt, last,
                                                  window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-6, atol=1e-5)


def test_intra_block_causality():
    """Row s of an S-token fused block must equal token s of S sequential
    single-token fused calls — the strongest intra-block causality oracle
    (later rows see earlier rows' K/V, never the reverse).  Scenarios obey
    the engine's block contract — a block's writes never evict ring
    entries its own earlier rows still attend (no-wrap, and wrap under a
    window that already excludes the evicted positions); outside that
    contract scatter-then-attend (XLA and kernel alike) legitimately
    differs from sequential decode."""
    B, S, H, KV, hd, P = 2, 4, 4, 2, 64, 3
    window = 8  # < T - S: wrapped-over positions are already out of window
    q, kp, vp, kn, vn = _block_setup(jax.random.PRNGKey(11), B, S, H, KV,
                                     hd, 8)
    bt = jnp.array([[1, 2, 3], [4, 5, 6]], jnp.int32)
    last = jnp.array([S + 6, 3 * P * PSZ + S - 1], jnp.int32)
    out, okp, ovp = pa_ops.paged_attention_update(q, kn, vn, kp, vp, bt,
                                                  last, window=window)
    skp, svp = kp, vp
    for s in range(S):
        step_out, skp, svp = pa_ops.paged_attention_update(
            q[:, s:s + 1], kn[:, s:s + 1], vn[:, s:s + 1], skp, svp, bt,
            last - (S - 1 - s), window=window)
        np.testing.assert_allclose(np.asarray(out[:, s], np.float32),
                                   np.asarray(step_out[:, 0], np.float32),
                                   rtol=2e-6, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(okp), np.asarray(skp))
    np.testing.assert_array_equal(np.asarray(ovp), np.asarray(svp))


def test_nondefault_q_positions():
    """Explicit per-row query positions (the non-default-pos path that v1
    forced onto XLA) mask against the same block table."""
    B, S, H, KV, hd, P = 2, 3, 4, 2, 64, 3
    q, kp, vp, _, _ = _block_setup(jax.random.PRNGKey(12), B, S, H, KV,
                                   hd, 8)
    bt = jnp.array([[1, 2, 3], [4, 5, 6]], jnp.int32)
    last = jnp.array([30, 9], jnp.int32)
    qpos = jnp.array([[5, 17, 30], [0, 4, 9]], jnp.int32)
    out = pa_ops.paged_attention(q, kp, vp, bt, last, window=12,
                                 q_positions=qpos)
    want = pa_ref.reference_paged_attention_block(
        q, kp, vp, bt, last, window=12, q_positions=qpos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-6, atol=1e-5)


# ------------------------------------------------- v2: fused K/V scatter


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_scatter_pool_exact(dtype):
    """paged_attention_update must return pools BYTE-EQUAL to the XLA
    scatter (`.at[w_idx].set`) models/layers.py used to pay as a separate
    dispatch, and attention over them must match the oracle — including
    the narrower-kv-dtype round trip."""
    B, S, H, KV, hd, P = 3, 6, 4, 2, 64, 3
    q, kp, vp, kn, vn = _block_setup(jax.random.PRNGKey(13), B, S, H, KV,
                                     hd, 10)
    kp, vp = kp.astype(dtype), vp.astype(dtype)
    bt = jnp.asarray(np.random.default_rng(2).permutation(
        np.arange(1, 10)).reshape(B, P), jnp.int32)
    last = jnp.array([S - 1, 40, 2 * P * PSZ + 3], jnp.int32)
    out, okp, ovp = pa_ops.paged_attention_update(q, kn, vn, kp, vp, bt,
                                                  last, window=10)
    want, wkp, wvp = pa_ref.reference_paged_update(q, kn, vn, kp, vp, bt,
                                                   last, window=10)
    np.testing.assert_array_equal(np.asarray(okp), np.asarray(wkp))
    np.testing.assert_array_equal(np.asarray(ovp), np.asarray(wvp))
    tol = 2e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 5)


def test_fused_scatter_never_touches_unwritten_pages():
    """Pages outside the write window — shared prompt-prefix pages under
    CoW — must come back bit-identical: the in-kernel scatter's write
    mask is what keeps copy-on-write sound."""
    B, S, H, KV, hd, P = 2, 2, 4, 2, 64, 3
    q, kp, vp, kn, vn = _block_setup(jax.random.PRNGKey(14), B, S, H, KV,
                                     hd, 8)
    # both slots share page 7 as their (read-only) first page
    bt = jnp.array([[7, 2, 3], [7, 4, 5]], jnp.int32)
    last = jnp.array([PSZ + 3, PSZ + 8], jnp.int32)  # writes land on page 2/4
    _, okp, ovp = pa_ops.paged_attention_update(q, kn, vn, kp, vp, bt, last)
    for page in (0, 1, 6, 7):  # null, unreferenced, shared prefix
        np.testing.assert_array_equal(np.asarray(okp[page]),
                                      np.asarray(kp[page]))
        np.testing.assert_array_equal(np.asarray(ovp[page]),
                                      np.asarray(vp[page]))


# ---------------------------------------------- v2: multi-page tile masking


@pytest.mark.parametrize("tile_k", [1, 2, 3, 4])
def test_tile_factor_sweep_ragged_tail(tile_k):
    """Every tile factor agrees with the oracle on a page count that does
    NOT divide it (P=3): the padded null-page tail rows must mask out."""
    B, H, KV, hd, P = 3, 4, 2, 64, 3
    q, kp, vp = _pool_setup(jax.random.PRNGKey(15), B, H, KV, hd, P, 10)
    kp = kp.at[0].set(1e4)  # poison the null page the padding points at
    vp = vp.at[0].set(1e4)
    bt = jnp.asarray(np.random.default_rng(3).permutation(
        np.arange(1, 10)).reshape(B, P), jnp.int32)
    last = jnp.array([7, 29, 47], jnp.int32)
    out = pa_ops.paged_attention(q, kp, vp, bt, last, tile_k=tile_k)
    want = pa_ref.reference_paged_attention(q[:, 0], kp, vp, bt, last)
    assert np.abs(np.asarray(out)).max() < 1e3
    np.testing.assert_allclose(np.asarray(out[:, 0], np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-6, atol=1e-5)


def test_ring_wrap_mid_tile():
    """The ring-wrap boundary (oldest-live vs overwritten entries) landing
    strictly inside a multi-page tile, not on a tile edge."""
    B, H, KV, hd, P = 2, 4, 2, 64, 4
    T = P * PSZ
    q, kp, vp = _pool_setup(jax.random.PRNGKey(16), B, H, KV, hd, P, 9)
    bt = jnp.array([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    # last % T = 20 and 57: the validity cut falls at ring index 21 / 58,
    # mid-tile for tile_k=2 (tiles span rings [0,32) and [32,64))
    last = jnp.array([T + 20, 2 * T + 57], jnp.int32)
    out = pa_ops.paged_attention(q, kp, vp, bt, last, tile_k=2)
    want = pa_ref.reference_paged_attention(q[:, 0], kp, vp, bt, last)
    np.testing.assert_allclose(np.asarray(out[:, 0], np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-6, atol=1e-5)


@pytest.mark.parametrize("window", [24, 40])
def test_window_straddles_tile_boundary(window):
    """A sliding window whose lower edge crosses a multi-page tile
    boundary (tile span 32 for tile_k=2): in-tile masking must cut rows
    of a tile whose other rows stay live."""
    B, H, KV, hd, P = 2, 4, 2, 64, 4
    q, kp, vp = _pool_setup(jax.random.PRNGKey(17), B, H, KV, hd, P, 9)
    bt = jnp.array([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    last = jnp.array([45, 61], jnp.int32)
    out = pa_ops.paged_attention(q, kp, vp, bt, last, window=window,
                                 tile_k=2)
    want = pa_ref.reference_paged_attention(q[:, 0], kp, vp, bt, last,
                                            window=window)
    np.testing.assert_allclose(np.asarray(out[:, 0], np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-6, atol=1e-5)


def test_fully_masked_tail_tiles():
    """A short sequence leaves whole multi-page tiles (and the padded
    tail) fully masked — the online softmax must pass through them
    without poisoning (no NaN, no null-page leakage)."""
    B, H, KV, hd, P = 2, 4, 2, 64, 4
    q, kp, vp = _pool_setup(jax.random.PRNGKey(18), B, H, KV, hd, P, 9)
    kp = kp.at[0].set(1e4)
    vp = vp.at[0].set(1e4)
    bt = jnp.array([[1, 2, 0, 0], [3, 0, 0, 0]], jnp.int32)
    last = jnp.array([3, 0], jnp.int32)  # tiles [2,3] / [1..3] all dead
    out = pa_ops.paged_attention(q, kp, vp, bt, last, tile_k=2)
    want = pa_ref.reference_paged_attention(q[:, 0], kp, vp, bt, last)
    assert np.isfinite(np.asarray(out)).all()
    assert np.abs(np.asarray(out)).max() < 1e3
    np.testing.assert_allclose(np.asarray(out[:, 0], np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-6, atol=1e-5)


# ----------------------------------------------------- loud ineligibility


def test_non_int32_inputs_fail_loud():
    """Engine-side block tables / positions are int32 at construction;
    a float or int64 leaking in must raise, not silently cast per tick."""
    B, H, KV, hd, P = 2, 4, 2, 64, 2
    q, kp, vp = _pool_setup(jax.random.PRNGKey(19), B, H, KV, hd, P, 6)
    bt = jnp.array([[1, 2], [3, 4]], jnp.int32)
    last = jnp.array([5, 9], jnp.int32)
    with pytest.raises(ValueError, match="int32"):
        pa_ops.paged_attention(q, kp, vp, bt.astype(jnp.float32), last)
    with pytest.raises(ValueError, match="int32"):
        pa_ops.paged_attention(q, kp, vp, bt, last.astype(jnp.float32))
    with pytest.raises(ValueError, match="int32"):
        pa_ops.paged_attention(q, kp, vp, bt, last,
                               q_positions=last[:, None].astype(jnp.float32)
                               * jnp.ones((1, 1)))


def test_oversized_block_fails_loud():
    """S larger than the logical ring would overwrite its own tokens —
    ineligible, and the ValueError must carry the rule."""
    B, H, KV, hd, P = 1, 4, 2, 64, 2
    S = P * PSZ + 1
    q = jax.random.normal(jax.random.PRNGKey(20), (B, S, H, hd))
    kp = jnp.zeros((4, KV, PSZ, hd))
    bt = jnp.array([[1, 2]], jnp.int32)
    last = jnp.array([S - 1], jnp.int32)
    with pytest.raises(ValueError, match="ring"):
        pa_ops.paged_attention(q, kp, kp, bt, last)


# ---------------------------------------------- live pages, row-only writes


@pytest.mark.parametrize("fuse", [False, True])
@pytest.mark.parametrize("tile_k", [1, 2, 3])
def test_dead_pages_never_read(tile_k, fuse):
    """A 1-token slot, a mid-length slot and a wrapped full-ring slot,
    with every page past each slot's live range full of NaN: the kernel
    reads only ceil((last + 1) / page_size) pages of a slot (all P once
    the ring wrapped), so its answer is the oracle's over finite pools."""
    B, H, KV, hd, P = 3, 4, 2, 64, 4
    q, kp, vp, kn, vn = _block_setup(jax.random.PRNGKey(21), B, 1, H, KV,
                                     hd, 1 + B * P)
    bt = jnp.arange(1, 1 + B * P, dtype=jnp.int32).reshape(B, P)
    last = jnp.array([0, 37, 2 * P * PSZ + 9], jnp.int32)
    dead = [int(bt[b, p]) for b in range(B) for p in range(P)
            if p >= min(P, (int(last[b]) + PSZ) // PSZ)]
    assert len(dead) == 3 + 1  # slot 0: pages 1-3; slot 1: page 3
    nan_k = kp.at[jnp.array(dead)].set(jnp.nan)
    nan_v = vp.at[jnp.array(dead)].set(jnp.nan)
    if fuse:
        out, okp, _ = pa_ops.paged_attention_update(
            q, kn, vn, nan_k, nan_v, bt, last, tile_k=tile_k)
        want, wkp, _ = pa_ref.reference_paged_update(q, kn, vn, kp, vp, bt,
                                                     last)
        live = np.setdiff1d(np.arange(1 + B * P), dead)
        np.testing.assert_array_equal(np.asarray(okp)[live],
                                      np.asarray(wkp)[live])
    else:
        out = pa_ops.paged_attention(q, nan_k, nan_v, bt, last,
                                     tile_k=tile_k)
        want = pa_ref.reference_paged_attention_block(q, kp, vp, bt, last)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-6, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tile_k", [2, 3])
def test_fused_write_touches_only_new_rows(tile_k, dtype):
    """An S=16 block whose rows straddle a page boundary, in a fresh ring
    and in a wrapped one, with tile_k > 1: the returned pools equal the
    inputs bit for bit everywhere but the new rows, which hold the new
    K/V in the pool's dtype (f32 pools take row copies, bf16 pools whole
    page merges)."""
    B, S, H, KV, hd, P = 2, 16, 4, 2, 64, 4
    T = P * PSZ
    q, kp, vp, kn, vn = _block_setup(jax.random.PRNGKey(22), B, S, H, KV,
                                     hd, 1 + B * P)
    kp, vp = kp.astype(dtype), vp.astype(dtype)
    bt = jnp.array([[3, 7, 1, 5], [2, 8, 6, 4]], jnp.int32)
    last = jnp.array([PSZ + 7, T + 40], jnp.int32)  # rows 8-23, 25-40
    out, okp, ovp = pa_ops.paged_attention_update(q, kn, vn, kp, vp, bt,
                                                  last, tile_k=tile_k)
    new = np.zeros(kp.shape[:3], bool)          # (n_pages, KV, psz)
    want_k, want_v = np.asarray(kp).copy(), np.asarray(vp).copy()
    for b in range(B):
        for s in range(S):
            ring = (int(last[b]) - S + 1 + s) % T
            page, row = int(bt[b, ring // PSZ]), ring % PSZ
            new[page, :, row] = True
            want_k[page, :, row] = np.asarray(kn[b, s].astype(dtype))
            want_v[page, :, row] = np.asarray(vn[b, s].astype(dtype))
    assert new.sum() == B * S * KV
    for got, was, want in ((okp, kp, want_k), (ovp, vp, want_v)):
        got = np.asarray(got)
        np.testing.assert_array_equal(got[~new], np.asarray(was)[~new])
        np.testing.assert_array_equal(got, want)
    ref_out, _, _ = pa_ref.reference_paged_update(q, kn, vn, kp, vp, bt,
                                                  last)
    tol = 2e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32),
                               rtol=tol, atol=tol * 5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_idle_lanes_on_null_page_beside_live_lanes(dtype):
    """Idle lanes parked on the null page 0 (their positions pinned where
    their last request left them) between live lanes: the live lanes'
    answers and pages match the oracle, the idle lanes' writes land on
    page 0 only, and every output is finite."""
    B, H, KV, hd, P = 4, 4, 2, 64, 3
    q, kp, vp, kn, vn = _block_setup(jax.random.PRNGKey(23), B, 1, H, KV,
                                     hd, 8)
    kp, vp = kp.astype(dtype), vp.astype(dtype)
    bt = jnp.array([[1, 2, 3], [0, 0, 0], [4, 5, 0], [0, 0, 0]], jnp.int32)
    last = jnp.array([40, 37, 20, 0], jnp.int32)
    out, okp, ovp = pa_ops.paged_attention_update(q, kn, vn, kp, vp, bt,
                                                  last)
    live = np.array([0, 2])
    want, wkp, wvp = pa_ref.reference_paged_update(
        q[live], kn[live], vn[live], kp, vp, bt[live], last[live])
    assert np.isfinite(np.asarray(out, np.float32)).all()
    tol = 2e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 5)
    for got, ref in ((okp, wkp), (ovp, wvp)):  # page 0 is don't-care
        np.testing.assert_array_equal(np.asarray(got)[1:],
                                      np.asarray(ref)[1:])
