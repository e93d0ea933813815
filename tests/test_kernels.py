"""Per-kernel validation: shape/dtype sweeps, interpret=True vs the pure-jnp
ref.py oracle (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.irt_lookup.irt_lookup import E as LEAF_E
from repro.kernels.irt_lookup.irt_lookup import irt_lookup
from repro.kernels.irt_lookup.ref import irt_lookup_ref
from repro.kernels.paged_attention.paged_attention import (
    paged_attention, paged_attention_fused, paged_attention_split)
from repro.kernels.paged_attention.ref import (paged_attention_fused_ref,
                                               paged_attention_ref,
                                               paged_attention_split_ref)
from repro.kernels.remap_gather.ops import remap_scatter_op
from repro.kernels.remap_gather.remap_gather import remap_gather
from repro.kernels.remap_gather.ref import remap_gather_ref

KEY = jax.random.key(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,KV,S,hd", [
    (1, 4, 2, 128, 64),
    (2, 8, 8, 256, 64),     # MHA
    (1, 8, 2, 128, 128),    # GQA group 4
    (2, 2, 1, 192, 64),     # MQA, non-pow2 seq blocks (bq=64)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_sweep(B, H, KV, S, hd, dtype, causal, window):
    q = jax.random.normal(KEY, (B, H, S, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, KV, S, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, KV, S, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_matches_model_sdpa():
    """The kernel agrees with the model's reference attention path."""
    from repro.models.attention import _sdpa, make_mask
    B, H, KV, S, hd = 2, 4, 2, 128, 64
    q = jax.random.normal(KEY, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, KV, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, KV, hd))
    model_out = _sdpa(q, k, v, make_mask(S, S, causal=True))
    kern_out = flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, block_q=64, block_k=64,
        interpret=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(model_out), np.asarray(kern_out),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,KV,G,hd,page,npages,nslots", [
    (2, 2, 4, 64, 64, 4, 16),
    (1, 4, 8, 128, 128, 8, 32),
    (4, 1, 2, 64, 32, 2, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(B, KV, G, hd, page, npages, nslots, dtype):
    q = jax.random.normal(KEY, (B, KV, G, hd), dtype)
    kp = jax.random.normal(jax.random.fold_in(KEY, 1),
                           (nslots, KV, page, hd), dtype)
    vp = jax.random.normal(jax.random.fold_in(KEY, 2),
                           (nslots, KV, page, hd), dtype)
    pt = jax.random.randint(jax.random.fold_in(KEY, 3), (B, npages),
                            0, nslots)
    sl = jnp.full((B,), npages * page - 7, jnp.int32)
    out = paged_attention(q, kp, vp, pt, sl, interpret=True)
    ref = paged_attention_ref(q, kp, vp, pt, sl)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_paged_attention_respects_page_table():
    """Shuffling pool slots + fixing the table must not change the output."""
    B, KV, G, hd, page, npages, nslots = 1, 2, 2, 64, 32, 4, 16
    q = jax.random.normal(KEY, (B, KV, G, hd))
    kp = jax.random.normal(jax.random.fold_in(KEY, 1), (nslots, KV, page, hd))
    vp = jax.random.normal(jax.random.fold_in(KEY, 2), (nslots, KV, page, hd))
    pt = jnp.array([[3, 7, 1, 12]], jnp.int32)
    sl = jnp.array([npages * page], jnp.int32)
    base = paged_attention_ref(q, kp, vp, pt, sl)
    perm = jax.random.permutation(jax.random.fold_in(KEY, 5), nslots)
    inv = jnp.argsort(perm)
    out = paged_attention(q, kp[perm], vp[perm], inv[pt], sl, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=3e-5, atol=3e-5)


def _split_table(key, B, npages, fast_slots, n_homes):
    """A Trimma-valid split page table: some lanes routed to *distinct*
    fast slots (slot_owner is injective, so at most fast_slots lanes can
    ever be fast-routed), the rest to slow homes."""
    n = B * npages
    n_fast = min(fast_slots, max(1, n // 3))
    lanes = jax.random.permutation(key, n)[:n_fast]
    slots = jax.random.permutation(jax.random.fold_in(key, 1),
                                   fast_slots)[:n_fast]
    flat = fast_slots + jax.random.randint(jax.random.fold_in(key, 2),
                                           (n,), 0, n_homes)
    return flat.at[lanes].set(slots).reshape(B, npages).astype(jnp.int32)


@pytest.mark.parametrize("B,KV,G,hd,page,npages,fast_slots,n_homes", [
    (2, 2, 4, 64, 64, 4, 8, 16),
    (1, 4, 8, 128, 128, 8, 4, 32),
    (3, 1, 2, 64, 32, 5, 6, 24),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_split_sweep(B, KV, G, hd, page, npages,
                                     fast_slots, n_homes, dtype):
    """Split-pool kernel vs both oracles, ragged per-sequence lengths."""
    q = jax.random.normal(KEY, (B, KV, G, hd), dtype)
    fk = jax.random.normal(jax.random.fold_in(KEY, 1),
                           (fast_slots, KV, page, hd), dtype)
    fv = jax.random.normal(jax.random.fold_in(KEY, 2),
                           (fast_slots, KV, page, hd), dtype)
    sk = jax.random.normal(jax.random.fold_in(KEY, 3),
                           (n_homes, KV, page, hd), dtype)
    sv = jax.random.normal(jax.random.fold_in(KEY, 4),
                           (n_homes, KV, page, hd), dtype)
    pt = _split_table(jax.random.fold_in(KEY, 5), B, npages, fast_slots,
                      n_homes)
    sl = jax.random.randint(jax.random.fold_in(KEY, 6), (B,), 1,
                            npages * page + 1).astype(jnp.int32)
    ref = paged_attention_split_ref(q, fk, fv, sk, sv, pt, sl)
    out = paged_attention_split(q, fk, fv, sk, sv, pt, sl, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_paged_attention_split_matches_concat_bitwise():
    """The split-pool read must be indistinguishable from the legacy
    concatenated-pool read: same table, same bytes, bit-identical output
    (kernel vs kernel in interpret mode, and oracle vs oracle)."""
    B, KV, G, hd, page, npages = 2, 2, 2, 64, 32, 6
    fast_slots, n_homes = 8, 16
    q = jax.random.normal(KEY, (B, KV, G, hd))
    fk = jax.random.normal(jax.random.fold_in(KEY, 1),
                           (fast_slots, KV, page, hd))
    fv = jax.random.normal(jax.random.fold_in(KEY, 2),
                           (fast_slots, KV, page, hd))
    sk = jax.random.normal(jax.random.fold_in(KEY, 3),
                           (n_homes, KV, page, hd))
    sv = jax.random.normal(jax.random.fold_in(KEY, 4),
                           (n_homes, KV, page, hd))
    pt = _split_table(jax.random.fold_in(KEY, 5), B, npages, fast_slots,
                      n_homes)
    sl = jnp.array([npages * page, 3 * page - 5], jnp.int32)
    uk = jnp.concatenate([fk, sk])
    uv = jnp.concatenate([fv, sv])
    np.testing.assert_array_equal(
        np.asarray(paged_attention_split_ref(q, fk, fv, sk, sv, pt, sl)),
        np.asarray(paged_attention_ref(q, uk, uv, pt, sl)))
    np.testing.assert_array_equal(
        np.asarray(paged_attention_split(q, fk, fv, sk, sv, pt, sl,
                                         interpret=True)),
        np.asarray(paged_attention(q, uk, uv, pt, sl, interpret=True)))


def _fused_args(B, K, KV, G, hd, page, np_total, n_pages, fast_slots, dtype):
    ks = [jax.random.fold_in(KEY, i) for i in range(8)]
    q = jax.random.normal(ks[0], (B, K, KV, G, hd), dtype)
    fk = jax.random.normal(ks[1], (fast_slots, KV, page, hd), dtype)
    fv = jax.random.normal(ks[2], (fast_slots, KV, page, hd), dtype)
    sk = jax.random.normal(ks[3], (B * np_total, KV, page, hd), dtype)
    sv = jax.random.normal(ks[4], (B * np_total, KV, page, hd), dtype)
    kn = jax.random.normal(ks[5], (B, K, KV, hd), dtype)
    vn = jax.random.normal(ks[6], (B, K, KV, hd), dtype)
    # distinct fast slots for a few pages, every other page at its home
    entries = np.full((B, n_pages), -1, np.int32)
    lanes = np.asarray(jax.random.permutation(ks[7], B * n_pages))
    entries.reshape(-1)[lanes[:fast_slots]] = np.arange(fast_slots)
    return q, fk, fv, sk, sv, jnp.asarray(entries), kn, vn


@pytest.mark.parametrize("B,K,KV,G,hd,page,np_total,n_pages,fast_slots", [
    (2, 1, 2, 3, 64, 16, 8, 8, 4),
    (3, 4, 2, 2, 64, 16, 8, 4, 6),      # live-page bucket < table width
    # granite widths; a bucket of 22 pages is no multiple of the block
    (4, 1, 8, 3, 64, 16, 24, 22, 16),
    # qwen2 widths, 4 new tokens; 18 pages, no multiple of the block
    (4, 4, 4, 7, 128, 16, 24, 18, 12),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_fused_sweep(B, K, KV, G, hd, page, np_total,
                                     n_pages, fast_slots, dtype):
    """Fused k-token append+attend kernel vs its oracle: fast-routed and
    slow-home pages mixed, new rows that overlay positions inside the
    attended prefix, and lanes of every length: lane 0 reaches the
    bucket's last page, lane 1 holds one page (less than a block), a
    middle lane ends mid-bucket, and the last lane is parked (its rows
    are 0: it fetches and computes nothing)."""
    args = _fused_args(B, K, KV, G, hd, page, np_total, n_pages,
                       fast_slots, dtype)
    span = n_pages * page
    pos = np.asarray([span - K - 3] + [5 + (b - 1) * (span // 2)
                                       for b in range(1, B)], np.int32)
    pos[-1] = -1                                    # parked lane
    args = args + (jnp.asarray(pos),)
    out = paged_attention_fused(*args, interpret=True)
    ref = paged_attention_fused_ref(*args)
    live = pos >= 0
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref, np.float32)[live],
                               **_tol(dtype))
    np.testing.assert_array_equal(np.asarray(out, np.float32)[~live], 0.0)


def test_paged_attention_fused_bucket_bitwise():
    """The kernel at the full table width and at the live-page bucket
    runs the same operations: blocks past every lane's last attended
    page fetch and compute nothing, so the outputs are bitwise equal."""
    B, K, KV, G, hd, page, np_total = 3, 1, 8, 3, 64, 16, 32
    args = _fused_args(B, K, KV, G, hd, page, np_total, np_total, 12,
                       jnp.float32)
    q, fk, fv, sk, sv, entries, kn, vn = args
    n_pages = 16
    pos = jnp.asarray([n_pages * page - 2, 70, -1], jnp.int32)
    full = paged_attention_fused(q, fk, fv, sk, sv, entries, kn, vn, pos,
                                 interpret=True)
    bucket = paged_attention_fused(q, fk, fv, sk, sv, entries[:, :n_pages],
                                   kn, vn, pos, interpret=True)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(bucket))


# ---------------------------------------------------------------------------
# iRT lookup
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_leaf,N", [(8, 256), (64, 2048), (128, 512)])
def test_irt_lookup_sweep(n_leaf, N):
    ids = jax.random.randint(KEY, (N,), 0, n_leaf * LEAF_E)
    home = ids + 10_000
    bits = jax.random.randint(jax.random.fold_in(KEY, 1),
                              ((n_leaf + 31) // 32,), -2**31, 2**31 - 1,
                              jnp.int32)
    leaf = jnp.where(
        jax.random.bernoulli(jax.random.fold_in(KEY, 2), 0.5,
                             (n_leaf * LEAF_E,)),
        jax.random.randint(jax.random.fold_in(KEY, 3),
                           (n_leaf * LEAF_E,), 0, 999), -1).astype(jnp.int32)
    out = irt_lookup(ids, home, bits, leaf, block=min(256, N),
                     interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(irt_lookup_ref(ids, home, bits, leaf)))


def test_irt_lookup_identity_default():
    """Unallocated leaves / invalid entries -> identity mapping (the paper's
    central default path)."""
    n_leaf = 4
    ids = jnp.arange(n_leaf * LEAF_E, dtype=jnp.int32)
    home = ids * 2 + 1
    bits = jnp.zeros((1,), jnp.int32)            # nothing allocated
    leaf = jnp.full((n_leaf * LEAF_E,), 123, jnp.int32)
    out = irt_lookup_ref(ids, home, bits, leaf)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(home))


# ---------------------------------------------------------------------------
# remap gather / scatter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nslots,rows,cols,n_out", [
    (16, 8, 128, 6), (64, 64, 128, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_remap_gather_sweep(nslots, rows, cols, n_out, dtype):
    if dtype == jnp.int32:
        pool = jax.random.randint(KEY, (nslots, rows, cols), 0, 100, dtype)
    else:
        pool = jax.random.normal(KEY, (nslots, rows, cols), dtype)
    idx = jax.random.randint(jax.random.fold_in(KEY, 1), (n_out,), 0, nslots)
    out = remap_gather(pool, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(remap_gather_ref(pool, idx)))


def test_remap_scatter_roundtrip():
    pool = jnp.zeros((8, 4, 16))
    blocks = jax.random.normal(KEY, (3, 4, 16))
    idx = jnp.array([5, 1, 7], jnp.int32)
    pool2 = remap_scatter_op(pool, idx, blocks)
    got = remap_gather_ref(pool2, idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(blocks))
