"""Paged decode attention as a Pallas TPU kernel (the Trimma consumer).

One new token per sequence attends to a KV cache stored as fixed-size pages
in a physical pool; the *page table* rows (already translated through
iRT/iRC — see repro.tiered.kvcache) are passed as a scalar-prefetch operand
so the K/V BlockSpec index maps can chase the Trimma pointers: page j of
sequence b physically lives at pool slot ``page_table[b, j]``.  This is the
paper's "every access must translate physical->device" fused directly into
the data access, and the TPU analogue of its parallel fixed-location lookup
(Section 3.2): the index map *is* the lookup.

Two variants share the online-softmax body:

``paged_attention``        one unified pool (slot indexes the concat of
                           fast|slow) — the legacy path, which forces the
                           caller to materialise that concat;
``paged_attention_split``  the zero-copy path: fast and slow pools are
                           separate operands and the scalar-prefetch index
                           maps route each page by ``slot < fast_slots``
                           (fast pool) vs ``slot - fast_slots`` (slow
                           home).  Nothing is concatenated; on deployment
                           hardware the two operands live in different
                           memory kinds (HBM vs host/CXL) and each page's
                           DMA is issued against its own tier.  Both tiles
                           are prefetched per step (the unused one is
                           clamped to slot 0) and the body selects by the
                           routing bit — one page of spare bandwidth per
                           step in exchange for never copying the pools.

Grid: (B, KV, n_pages), pages sequential for the online softmax.
VMEM working set per step: one (page, hd) K tile + V tile + [G, hd]
accumulator — hardware-aligned for page=128, hd=128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _softmax_step(q_ref, k, v, seq_lens, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, page: int, npages: int):
    """One online-softmax update with this page's [page, hd] K/V tiles."""
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # [G, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = pos < seq_lens[b]                      # [1, page]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == npages - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _kernel(page_table, seq_lens,          # scalar prefetch
            q_ref, kp_ref, vp_ref, o_ref,
            acc_ref, m_ref, l_ref, *,
            scale: float, page: int, npages: int):
    _softmax_step(q_ref, kp_ref[0, 0].astype(jnp.float32),
                  vp_ref[0, 0].astype(jnp.float32), seq_lens,
                  o_ref, acc_ref, m_ref, l_ref,
                  scale=scale, page=page, npages=npages)


def _split_kernel(page_table, seq_lens,    # scalar prefetch
                  q_ref, kf_ref, vf_ref, ks_ref, vs_ref, o_ref,
                  acc_ref, m_ref, l_ref, *,
                  scale: float, page: int, npages: int, fast_slots: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    # the routing bit: which tier this page's DMA actually targeted
    is_fast = page_table[b, j] < fast_slots
    k = jnp.where(is_fast, kf_ref[0, 0], ks_ref[0, 0]).astype(jnp.float32)
    v = jnp.where(is_fast, vf_ref[0, 0], vs_ref[0, 0]).astype(jnp.float32)
    _softmax_step(q_ref, k, v, seq_lens, o_ref, acc_ref, m_ref, l_ref,
                  scale=scale, page=page, npages=npages)


def _fused_kernel(entries, pos,            # scalar prefetch
                  q_ref, kf_ref, vf_ref, ks_ref, vs_ref, kn_ref, vn_ref,
                  o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, page: int, npages: int, ktok: int,
                  group: int):
    """Fused append+attend: the k new K/V rows are overlaid onto this
    page's tile in VMEM (registers, really) before the softmax update, so
    the new tokens are attended in the same pass that reads the pools —
    no separate append write+readback on the hot path.  Rows are per-token
    causal: query row r (token r // group) sees positions < pos+1+r//group.
    """
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # the routing bit: leaf entry >= 0 -> fast slot, else identity home
    e = entries[b, j]
    k = jnp.where(e >= 0, kf_ref[0, 0], ks_ref[0, 0]).astype(jnp.float32)
    v = jnp.where(e >= 0, vf_ref[0, 0], vs_ref[0, 0]).astype(jnp.float32)

    p0 = pos[b]
    row = jax.lax.broadcasted_iota(jnp.int32, (page, 1), 0)
    for r in range(ktok):                      # static unroll over k tokens
        pg = p0 + r
        sel = (p0 >= 0) & (pg // page == j) & (row == pg % page)
        k = jnp.where(sel, kn_ref[0, 0, r].astype(jnp.float32)[None, :], k)
        v = jnp.where(sel, vn_ref[0, 0, r].astype(jnp.float32)[None, :], v)

    q = q_ref[0, 0].astype(jnp.float32)        # [ktok*group, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    col = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    tok = jax.lax.broadcasted_iota(jnp.int32, (ktok * group, 1), 0) // group
    limit = jnp.where(p0 >= 0, p0 + 1 + tok, 0)
    s = jnp.where(col < limit, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == npages - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, page_table, seq_lens, *,
                    interpret: bool = False):
    """q [B,KV,G,hd]; pools [n_slots, KV, page, hd];
    page_table [B, npages] int32 (Trimma-translated device slots);
    seq_lens [B] int32.  Returns [B,KV,G,hd]."""
    B, KV, G, hd = q.shape
    n_slots, _, page, _ = k_pool.shape
    npages = page_table.shape[1]
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_kernel, scale=scale, page=page,
                               npages=npages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, npages),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd),
                         lambda b, h, j, pt, sl: (b, h, 0, 0)),
            # the Trimma pointer chase: pool slot = page_table[b, j]
            pl.BlockSpec((1, 1, page, hd),
                         lambda b, h, j, pt, sl: (pt[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, page, hd),
                         lambda b, h, j, pt, sl: (pt[b, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, j, pt, sl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, seq_lens, q, k_pool, v_pool)


def paged_attention_split(q, fast_k, fast_v, slow_k, slow_v, page_table,
                          seq_lens, *, interpret: bool = False):
    """Zero-copy variant: q [B,KV,G,hd]; fast pools [fast_slots,KV,page,hd];
    slow pools [n_homes,KV,page,hd]; page_table [B,npages] int32 in the
    *unified* index space (< fast_slots -> fast, else fast_slots + home);
    seq_lens [B] int32.  Returns [B,KV,G,hd], bit-identical to
    ``paged_attention`` over the concatenated pools."""
    B, KV, G, hd = q.shape
    fast_slots, _, page, _ = fast_k.shape
    npages = page_table.shape[1]
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_split_kernel, scale=scale, page=page,
                               npages=npages, fast_slots=fast_slots)

    def _fast_idx(b, h, j, pt, sl):
        return (jnp.where(pt[b, j] < fast_slots, pt[b, j], 0), h, 0, 0)

    def _slow_idx(b, h, j, pt, sl):
        return (jnp.where(pt[b, j] < fast_slots, 0,
                          pt[b, j] - fast_slots), h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, npages),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd),
                         lambda b, h, j, pt, sl: (b, h, 0, 0)),
            # per-tier pointer chase: the slot routes its own tier's DMA,
            # the other tier's fetch is clamped to slot 0 and discarded
            pl.BlockSpec((1, 1, page, hd), _fast_idx),
            pl.BlockSpec((1, 1, page, hd), _fast_idx),
            pl.BlockSpec((1, 1, page, hd), _slow_idx),
            pl.BlockSpec((1, 1, page, hd), _slow_idx),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, j, pt, sl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, seq_lens, q, fast_k, fast_v, slow_k, slow_v)


def paged_attention_fused(q, fast_k, fast_v, slow_k, slow_v, entries,
                          k_new, v_new, pos, *, interpret: bool = False):
    """Fused k-token append+attend: q [B,K,KV,G,hd]; fast pools
    [fast_slots,KV,page,hd]; slow pools [B*npages,KV,page,hd] (identity
    homes); entries [B,npages] int32 = per-lane leaf-table rows (>= 0 ->
    fast slot, < 0 -> the page lives at its slow home ``b*npages + j``);
    k_new/v_new [B,K,KV,hd]; pos [B] (first new token's position, < 0
    parks the lane).  Returns [B,K,KV,G,hd].

    The index maps route each page's DMA straight off the leaf entries —
    no unified page table is ever materialised — and the new rows ride
    in as operands overlaid inside the kernel, so persisting them to the
    pools happens off the critical path (batched scatter at end of step)
    rather than as a dependency of the attention read."""
    B, K, KV, G, hd = q.shape
    page = fast_k.shape[2]
    npages = entries.shape[1]          # may be the live-page bucket
    np_total = slow_k.shape[0] // B    # identity-home stride (full table)
    scale = 1.0 / (hd ** 0.5)
    q2 = q.transpose(0, 2, 1, 3, 4).reshape(B, KV, K * G, hd)
    # new rows head-major, [B, KV, K, hd]: a (1, 1, K, hd) block spans the
    # array's last two dims, as Mosaic's tiling rule requires
    kn2 = k_new.transpose(0, 2, 1, 3)
    vn2 = v_new.transpose(0, 2, 1, 3)

    kernel = functools.partial(_fused_kernel, scale=scale, page=page,
                               npages=npages, ktok=K, group=G)

    def _fast_idx(b, h, j, en, ps):
        return (jnp.where(en[b, j] >= 0, en[b, j], 0), h, 0, 0)

    def _slow_idx(b, h, j, en, ps):
        return (jnp.where(en[b, j] >= 0, 0, b * np_total + j), h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, npages),
        in_specs=[
            pl.BlockSpec((1, 1, K * G, hd),
                         lambda b, h, j, en, ps: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page, hd), _fast_idx),
            pl.BlockSpec((1, 1, page, hd), _fast_idx),
            pl.BlockSpec((1, 1, page, hd), _slow_idx),
            pl.BlockSpec((1, 1, page, hd), _slow_idx),
            pl.BlockSpec((1, 1, K, hd),
                         lambda b, h, j, en, ps: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, K, hd),
                         lambda b, h, j, en, ps: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, K * G, hd),
                               lambda b, h, j, en, ps: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K * G, hd), jnp.float32),
            pltpu.VMEM((K * G, 1), jnp.float32),
            pltpu.VMEM((K * G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, K * G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(entries, pos, q2, fast_k, fast_v, slow_k, slow_v, kn2, vn2)
    return out.reshape(B, KV, K, G, hd).transpose(0, 2, 1, 3, 4)
