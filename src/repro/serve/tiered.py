"""Tiered KV serving path (DESIGN.md §2 Layer C) — the zero-copy decode
hot path.

``attend`` is one decode-attention read for a batch of sequences whose KV
pages live under Trimma metadata, and it moves **no pool bytes**:

  logical page table --`tiered.kvcache.lookup`--> translated device table
      (served from the cached ``dev_table`` rows; the iRC/iRT engine runs
       only for live rows whose mapping is not yet cached)
  device table --split-pool paged attention--> output
      (the Pallas kernel reads the fast and slow pools in place, routing
       each page by ``slot < fast_slots`` — the old per-step
       ``unified_pools`` concatenation, a full KV-cache copy, is gone)

Only pages under ``seq_lens`` are translated or counted (``live_mask``),
so per-step metadata work scales with live context.  ``maintain`` runs
the off-critical-path migration pass (Figure 3's step 3) between decode
steps; its moves write the new translations through the device table, so
decode never re-walks after churn.

The translation must be invisible to the math: ``attend`` returns exactly
the dense-cache attention no matter which pages have migrated or been
evicted — bit-identical to the legacy concat path ``attend_concat``
(tests/test_engine.py::test_tiered_attend_invariant_under_serving, under
every policy preset).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.paged_attention.ops import (paged_attention_fused_op,
                                               paged_attention_op,
                                               paged_attention_split_op)
from repro.kernels.paged_attention.paged_attention import attended_pages
from repro.obs import metrics as obs_metrics
from repro.tiered import kvcache as tk


def page_table(cfg: tk.TieredConfig, st: tk.TieredState):
    """Full logical page-id table [n_seqs, max_pages_per_seq]."""
    pages = jnp.arange(cfg.max_pages_per_seq, dtype=jnp.int32)[None, :]
    seqs = jnp.arange(cfg.n_seqs, dtype=jnp.int32)[:, None]
    return tk.logical_page(cfg, seqs, pages)


def live_mask(cfg: tk.TieredConfig, seq_lens):
    """[n_seqs, max_pages_per_seq] bool: page j holds context iff its
    first token position is under the sequence length."""
    pages = jnp.arange(cfg.max_pages_per_seq, dtype=jnp.int32)[None, :]
    return pages * cfg.page_tokens < seq_lens[:, None]


def attend(cfg: tk.TieredConfig, st: tk.TieredState, q, seq_lens,
           *, impl: str = "auto"):
    """q [B, KV, G, hd], seq_lens [B] -> (attention out, new state).

    The zero-copy decode read: cached-device-table lookup over the live
    pages, then split-pool paged attention straight out of the two tiers."""
    table, st = tk.lookup(cfg, st, page_table(cfg, st),
                          live=live_mask(cfg, seq_lens))
    out = paged_attention_split_op(q, st.fast_k, st.fast_v,
                                   st.slow_k, st.slow_v, table, seq_lens,
                                   impl=impl)
    return out, st


def record_fetches(cfg: tk.TieredConfig, st: tk.TieredState, pos,
                   k_tok: int, n_pages: int) -> tk.TieredState:
    """Count the pages one fused-kernel call fetches
    (``trimma_attn_pages_fetched_total``): each lane's pages under
    ``pos + k_tok`` within the ``n_pages`` attended, none for a parked
    lane — the kernel's own skip predicate.  Over ``B * n_pages`` it is
    the share of the bucket the kernel reads."""
    n = attended_pages(pos, k_tok, cfg.page_tokens, n_pages)
    return st._replace(attn_pages=st.attn_pages + n.sum(dtype=jnp.int32))


def attend_tokens(cfg: tk.TieredConfig, st: tk.TieredState, q, k_new,
                  v_new, pos, *, n_pages: int | None = None,
                  impl: str = "auto"):
    """Fused k-token decode read+write: q [B, K, KV, G, hd] are K new
    queries per lane, k_new/v_new [B, K, KV, hd] their KV rows, pos [B]
    the first new token's position (< 0 parks the lane).  Returns
    (out [B, K, KV, G, hd], new state).

    One fused kernel overlays the new rows onto their routed tier and
    attends all K tokens per-token-causally in the same pass — bitwise
    equal to K sequential ``append_token`` -> ``attend`` steps — then the
    rows persist via one batched routed scatter (``tk.append_tokens``)
    off the attention's critical path.  No page table is materialised
    (the leaf entries *are* the translation), so the device-table and
    tracker accounting amortises to one record per call: each live page
    gets one touch and counts one cold translation (first read) or one
    ``dev_hits`` (``tk.record_reads``, lookup()'s cold/steady split).

    ``n_pages`` (static) is the live-page attention bucket (DESIGN.md
    §11): the kernel reads only that page prefix instead of the full
    table.  The caller guarantees ``n_pages * page_tokens > max(pos) +
    K - 1``; the truncated tail is fully masked, so the output is
    bit-identical to the full-width read."""
    B, K = q.shape[0], q.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    entries = st.leaf_table[:cfg.n_logical].reshape(cfg.n_seqs,
                                                    cfg.max_pages_per_seq)
    if n_pages is not None and n_pages < cfg.max_pages_per_seq:
        entries = entries[:, :n_pages]
    out = paged_attention_fused_op(q, st.fast_k, st.fast_v,
                                   st.slow_k, st.slow_v, entries,
                                   k_new, v_new, pos, impl=impl)
    st = tk.append_tokens(cfg, st, jnp.arange(cfg.n_seqs, dtype=jnp.int32),
                          k_new, v_new, pos)
    st = record_fetches(cfg, st, pos, K, entries.shape[1])
    lv = live_mask(cfg, jnp.where(pos >= 0, pos + K, 0))
    st = tk.record_reads(cfg, st, page_table(cfg, st).reshape(-1),
                         lv.reshape(-1))
    st = tk.record_touches(cfg, st, page_table(cfg, st).reshape(-1),
                           lv.reshape(-1))
    return out, st


def attend_concat(cfg: tk.TieredConfig, st: tk.TieredState, q, seq_lens,
                  *, impl: str = "auto"):
    """LEGACY baseline: full-table translation + unified-pool concat (a
    complete KV-cache copy per step) + unified-pool kernel.  Kept only for
    the ``serve_decode`` benchmark and the golden-equality regression test
    — the decode path never calls it.  Pair it with
    ``cache_device_table=False`` to reproduce the pre-zero-copy path
    exactly."""
    table, st = tk.lookup(cfg, st, page_table(cfg, st))
    uk, uv = tk.unified_pools(st)
    return paged_attention_op(q, uk, uv, table, seq_lens, impl=impl), st


def maintain(cfg: tk.TieredConfig, st: tk.TieredState,
             max_moves: int | None = None) -> tk.TieredState:
    """Between decode steps: one policy-scheduler pass (core/policy,
    DESIGN.md §7) — bounded promotion *and* demotion queues plus epoch
    decay of the hotness tracker, so the work per call stays off the
    critical path and stale-hot pages eventually return to the slow pool.
    Every move writes its new translation through ``dev_table`` (epoch-
    style row updates, like the iRC), so the next ``attend`` re-walks
    nothing.  ``cfg.policy`` selects the scheme; ``max_moves`` (default:
    the policy's budget) caps promotions + demotions per call."""
    return tk.run_scheduler(cfg, st, max_moves=max_moves)


def release(cfg: tk.TieredConfig, st: tk.TieredState, seq) -> tk.TieredState:
    """Recycle one lane (continuous batching): drop the finished
    sequence's pages from every metadata structure in one batched pass
    (``tiered.kvcache.release_seq``)."""
    return tk.release_seq(cfg, st, seq)


def metrics(cfg: tk.TieredConfig, st: tk.TieredState) -> dict:
    """Canonical telemetry view of one store (DESIGN.md §10): the obs tap
    over the in-graph counters under their registered ``trimma_*`` names,
    bandwidth already scaled to bytes.  Works on a single store, a
    layer-stacked one (``models.kv_backend.TieredBackend``) or any vmapped
    state — counters sum over every leading axis.  The config's geometry
    additionally derives the saved-metadata gauges (identity-entry
    ratio, iRT leaf occupancy, metadata bytes — DESIGN.md §12)."""
    return obs_metrics.tiered_metrics(st, page_bytes=cfg.page_bytes,
                                      n_logical=cfg.n_logical,
                                      fast_slots=cfg.fast_slots,
                                      leaf_entries=tk.E)
