"""Paged decode attention as a Pallas TPU kernel (the Trimma consumer).

One new token per sequence attends to a KV cache stored as fixed-size pages
in a physical pool; the *page table* rows (already translated through
iRT/iRC — see repro.tiered.kvcache) are passed as a scalar-prefetch operand
so the K/V BlockSpec index maps can chase the Trimma pointers: page j of
sequence b physically lives at pool slot ``page_table[b, j]``.  This is the
paper's "every access must translate physical->device" fused directly into
the data access, and the TPU analogue of its parallel fixed-location lookup
(Section 3.2): the index map *is* the lookup.

Three variants:

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
                           Grid (B, KV, n_pages), one (page, hd) tile per
                           step, shared with ``paged_attention``;
``paged_attention_fused``  the engine's decode read: k new tokens appended
                           and attended in one pass, routed straight off
                           the leaf entries.  Grid (B, n_blocks): each
                           step takes ``block_pages`` pages of one lane,
                           all KV heads, each page fetched once from the
                           one tier its entry names; pages past the
                           lane's last position, and parked lanes, are
                           neither fetched nor computed.

The first two share the online-softmax body; VMEM working set per step:
one (page, hd) K tile + V tile + [G, hd] accumulator.
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


# Bytes of one block's K and V pages together; the grid pipeline holds two.
_BLOCK_BYTES = 256 * 1024


def attended_pages(pos, k_tok: int, page: int, n_pages: int):
    """Pages of each lane that the fused kernel fetches: those holding a
    position under ``pos + k_tok`` (the k new rows included), at most
    ``n_pages``, and none for a parked lane (``pos < 0``).  Works on the
    [B] positions and on the kernel's own scalars alike, so the kernel's
    skip predicate and the fetched-pages counter are one function."""
    need = (jnp.maximum(pos, 0) + k_tok + page - 1) // page
    return jnp.where(pos >= 0, jnp.minimum(need, n_pages), 0)


def block_pages(n_pages: int, kv: int, page: int, hd: int, dtype) -> int:
    """Pages per grid step of the fused kernel: the largest power of two,
    at most ``n_pages``, whose K and V pages (all KV heads) fit
    ``_BLOCK_BYTES``."""
    per_page = 2 * kv * page * hd * jnp.dtype(dtype).itemsize
    p = 1
    while 2 * p <= n_pages and 2 * p * per_page <= _BLOCK_BYTES:
        p *= 2
    return p


def _fused_kernel(entries, pos, fast_ix, slow_ix,   # scalar prefetch
                  q_ref, kn_ref, vn_ref, *refs, scale: float, page: int,
                  n_pages: int, bp: int, ktok: int, group: int, kv: int):
    """Fused append+attend over one block of ``bp`` logical pages of lane
    b, every KV head at once.  ``refs`` holds, per page p of the block,
    that page's [KV, page, hd] K and V tiles from the fast and from the
    slow pool, then the output and the softmax scratch.  Only the tier
    the page's leaf entry names was fetched for it (``_routed_blocks``);
    the other tile still holds whatever page that operand last fetched.
    The k new rows are overlaid in VMEM before the softmax update, so
    they are attended in the same pass that reads the pools.  Rows are
    per-token causal: query row r (token r // group) sees positions
    < pos+1+r//group.  A block past the lane's last attended page, and
    every block of a parked lane, computes nothing.
    """
    pools, (o_ref, acc_ref, m_ref, l_ref) = refs[:4 * bp], refs[4 * bp:]
    kf, vf, ks, vs = (pools[t::4] for t in range(4))
    b = pl.program_id(0)
    i = pl.program_id(1)
    p0 = pos[b]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(i * bp < attended_pages(p0, ktok, page, n_pages))
    def _block():
        # the routing bit per page: leaf entry >= 0 -> the fast tile
        is_fast = [entries[b, jnp.minimum(i * bp + p, n_pages - 1)] >= 0
                   for p in range(bp)]
        span = bp * page
        row = i * span + jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0)
        col = i * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        tok = jax.lax.broadcasted_iota(jnp.int32, (ktok * group, 1),
                                       0) // group
        keep = col < p0 + 1 + tok                  # [ktok*group, span]

        def gather(fast, slow, h):
            return jnp.concatenate(
                [jnp.where(is_fast[p], fast[p][0, h], slow[p][0, h])
                 .astype(jnp.float32) for p in range(bp)], axis=0)

        for h in range(kv):                        # static unroll over heads
            k = gather(kf, ks, h)                  # [span, hd]
            v = gather(vf, vs, h)
            for r in range(ktok):                  # static unroll over k tokens
                sel = row == p0 + r
                k = jnp.where(sel, kn_ref[0, h, pl.ds(r, 1), :]
                              .astype(jnp.float32), k)
                v = jnp.where(sel, vn_ref[0, h, pl.ds(r, 1), :]
                              .astype(jnp.float32), v)
            q = q_ref[0, h].astype(jnp.float32)    # [ktok*group, hd]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _sticky(ix, use, rows: int):
    """ix [S, bp] block indices (< ``rows``) and use [S, bp], rows in
    grid order: where ``use`` is False, repeat the row before's value (a
    leading run takes the first used value).  The grid pipeline copies an
    input block only when its index changes between consecutive steps,
    so an operand fetches exactly its used pages.  Gather-free: the
    running max of ``step * rows + ix`` names the last used step's
    index."""
    S = ix.shape[0]
    if S * rows >= 2 ** 31:
        raise ValueError(f"{S} grid steps x {rows} pool rows overflow int32")
    step = jnp.arange(S, dtype=jnp.int32)[:, None]
    key = jnp.where(use, step * rows + ix, -1)
    last = jax.lax.cummax(key, axis=0)
    first = jnp.min(jnp.where(use, key, S * rows), axis=0) % rows
    return jnp.where(last >= 0, last % rows, first[None, :])


def _routed_blocks(entries, pos, k_tok: int, page: int, np_total: int,
                   n_fast: int, bp: int):
    """Per grid step (lane-major) and page of its block, the fast slot and
    the slow home to fetch, flattened [B * n_blocks * bp].  An attended
    page names its own row in the tier its entry routes to; every other
    (operand, step) repeats that operand's previous index, so it fetches
    nothing: not the other tier, not a page past ``pos + k_tok``, nothing
    for a parked lane."""
    B, n_pages = entries.shape
    width = pl.cdiv(n_pages, bp) * bp
    en = jnp.pad(entries, ((0, 0), (0, width - n_pages)),
                 constant_values=-1)
    j = jnp.arange(width, dtype=jnp.int32)[None, :]
    live = j < attended_pages(pos, k_tok, page, n_pages)[:, None]
    home = jnp.arange(B, dtype=jnp.int32)[:, None] * np_total + j
    fast_use, slow_use = live & (en >= 0), live & (en < 0)
    rows = (B * width // bp, bp)
    fast = _sticky(jnp.where(fast_use, en, 0).reshape(rows),
                   fast_use.reshape(rows), n_fast)
    slow = _sticky(home.reshape(rows), slow_use.reshape(rows), B * np_total)
    return fast.reshape(-1), slow.reshape(-1)


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
    parks the lane).  Returns [B,K,KV,G,hd]; a parked lane's rows are 0.

    The grid is (lanes, blocks of ``block_pages`` pages).  Each page of a
    block is one [KV, page, hd] tile per pool, all heads, and its index
    map routes it straight off the leaf entries — no unified page table
    is ever materialised.  The new rows ride in as operands overlaid
    inside the kernel, so persisting them to the pools happens off the
    critical path (batched scatter at end of step) rather than as a
    dependency of the attention read."""
    B, K, KV, G, hd = q.shape
    page = fast_k.shape[2]
    n_pages = entries.shape[1]         # may be the live-page bucket
    np_total = slow_k.shape[0] // B    # identity-home stride (full table)
    scale = 1.0 / (hd ** 0.5)
    bp = block_pages(n_pages, KV, page, hd, fast_k.dtype)
    nb = pl.cdiv(n_pages, bp)
    fast_ix, slow_ix = _routed_blocks(entries, pos, K, page, np_total,
                                      fast_k.shape[0], bp)
    q2 = q.transpose(0, 2, 1, 3, 4).reshape(B, KV, K * G, hd)
    # new rows head-major, [B, KV, K, hd]: a lane's block spans the
    # array's last two dims, as Mosaic's tiling rule requires
    kn2 = k_new.transpose(0, 2, 1, 3)
    vn2 = v_new.transpose(0, 2, 1, 3)

    kernel = functools.partial(_fused_kernel, scale=scale, page=page,
                               n_pages=n_pages, bp=bp, ktok=K, group=G,
                               kv=KV)

    def lane(b, i, *_):
        return (b, 0, 0, 0)

    def tile(tier, p):
        def index(b, i, en, ps, fi, si):
            return ((fi, si)[tier][(b * nb + i) * bp + p], 0, 0, 0)
        return pl.BlockSpec((1, KV, page, hd), index)

    page_specs = [tile(t, p) for p in range(bp) for t in (0, 0, 1, 1)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, nb),
        in_specs=[pl.BlockSpec((1, KV, K * G, hd), lane),
                  pl.BlockSpec((1, KV, K, hd), lane),
                  pl.BlockSpec((1, KV, K, hd), lane)] + page_specs,
        out_specs=pl.BlockSpec((1, KV, K * G, hd), lane),
        scratch_shapes=[
            pltpu.VMEM((KV, K * G, hd), jnp.float32),
            pltpu.VMEM((KV, K * G, 1), jnp.float32),
            pltpu.VMEM((KV, K * G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, K * G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(entries, pos, fast_ix, slow_ix, q2, kn2, vn2,
      *[fast_k, fast_v, slow_k, slow_v] * bp)
    return out.reshape(B, KV, K, G, hd).transpose(0, 2, 1, 3, 4)
