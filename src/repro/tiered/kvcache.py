"""TieredKVCache: the paper's metadata scheme as a first-class serving
feature (DESIGN.md §2 Layer B).

Two pools of KV pages per layer:
  slow pool — every logical page's *home* (host DRAM / CXL at deployment;
              device memory in this container, see the deployment note in
              DESIGN.md);
  fast pool — small HBM pool holding hot pages + the iRT metadata region.

Exactly the paper's structures, at page granularity — and exactly *one*
implementation of them: every metadata op below drives the shared
batch-first engine in ``core/remap`` (the same code the trace simulator
runs at batch size 1):

  iRT (Section 3.2)   ``remap.irt``: l1_bits (one bit per leaf,
                      "allocated?"), leaf_table [n_leaf * E] logical page
                      -> fast slot; entries exist ONLY for migrated
                      (non-identity) pages; a miss at any level defaults
                      to the slow home.  Lookups batch hundreds of page
                      ids; ``remap.irt.walk`` dispatches large batches to
                      the Pallas kernel (kernels/irt_lookup) and small /
                      off-TPU ones to the jnp reference.
  saved-space caching (Section 3.3)
                      the fast pool's last ``meta_slots`` slots host leaf
                      blocks 1:1; while leaf i is unallocated its slot backs
                      a data page; allocating the leaf force-evicts it
                      (metadata priority).
  iRC (Section 3.4)   ``remap.rcache``: NonIdCache (tag -> slot) + IdCache
                      (sector bit vectors) probed before walking the iRT;
                      entries update in place on migration.

Hotness tracking and migration scheduling are NOT implemented here: they
are ``core/policy`` (DESIGN.md §7).  ``lookup``/``append_token`` record
touches through the policy's tracker, and ``run_scheduler`` (the
``serve/tiered.maintain`` body) plans bounded promotion + demotion queues
per epoch — ``TieredConfig.policy`` selects the scheme.

The translated page table feeds the Pallas paged-attention kernels.  The
pools are *addressed* as one unified index space (slot < fast_slots ->
fast pool, else fast_slots + home -> slow pool) but since the zero-copy
decode path they are never *materialised* as one array: the split-pool
kernel (kernels/paged_attention) reads each tier in place, routing pages
by the slot range — on real hardware the two pools live in different
memory kinds (HBM vs host/CXL) and each page's DMA targets its own tier.

Translation itself is amortised, mirroring the paper's remap-cache
philosophy (translate once, reuse until invalidated): ``TieredState``
carries the *device page table* (``dev_table``/``dev_valid``), the cached
result of iRC-probe + iRT-walk per logical page.  ``lookup`` serves valid
rows without touching the metadata engine and translates only invalid
live rows; every mapping mutation (promote install, demote, victim /
forced evict, sequence release) writes the new translation through in
place — the same entry-granular coherence rule the iRC uses — so
steady-state decode does zero iRC probes and zero iRT walks.

Migration data movement goes through the migration engine
(``kernels/remap_gather``): page copies at promote/demote/evict sites are
``remap_gather_op`` gathers (Pallas DMA pipeline on TPU, ``impl="ref"``
jnp takes on CPU/CI — ``TieredConfig.gather_impl``).

All state is a pure pytree; every op is jit-able and returns a new state.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.policy import scheduler as pol_sched
from repro.core.policy import trackers as pol_track
from repro.core.policy.config import PolicyConfig
from repro.core.remap import irt as irt_ops
from repro.core.remap import rcache as rc_ops
from repro.core.remap.irt import E, INVALID
from repro.core.remap.rcache import RemapCacheGeometry
from repro.kernels.remap_gather.ops import remap_gather_op
from repro.obs.registry import MetricSpec, register

# canonical metric names for the counters this store accumulates beyond
# the iRC/iRT/migration families its building blocks declare
# (DESIGN.md §10; obs.metrics.tiered_metrics is the tap)
register(
    MetricSpec("trimma_dev_table_hits_total", "counter",
               "live lookup lanes served from the cached device page "
               "table (zero iRC probes, zero iRT walks)"),
    MetricSpec("trimma_attn_pages_fetched_total", "counter",
               "pages the fused decode kernel fetched, each from the one "
               "tier its leaf entry names (summed over layers)"),
    MetricSpec("trimma_fast_resident_pages", "gauge",
               "pages currently resident in the fast pool"),
    MetricSpec("trimma_metadata_pages", "gauge",
               "allocated iRT leaf blocks (saved-space metadata "
               "footprint, Figure 9 analogue)"),
    MetricSpec("trimma_identity_entry_ratio", "gauge",
               "fraction of logical pages holding NO remap entry "
               "(identity-mapped — the saved-metadata story, live)"),
    MetricSpec("trimma_irt_leaf_occupancy", "gauge",
               "allocated iRT leaf blocks / provisioned leaf slots "
               "(leaf-level table occupancy)"),
    MetricSpec("trimma_metadata_bytes", "gauge",
               "bytes of allocated iRT leaf metadata (E entries x 4 "
               "bytes per allocated leaf)", unit="bytes"),
)


@dataclasses.dataclass(frozen=True)
class TieredConfig:
    n_seqs: int
    max_pages_per_seq: int          # logical pages per sequence
    page_tokens: int
    n_kv_heads: int
    head_dim: int
    fast_data_slots: int            # HBM data-area pages
    # hotness / migration policy (core/policy, DESIGN.md §7); ``None``
    # resolves the DEPRECATED ``migrate_threshold`` shim into the default
    # threshold policy (see ``pol``)
    policy: Optional[PolicyConfig] = None
    migrate_threshold: int = 2      # DEPRECATED -> policy.promote_threshold
    # iRC geometry (scaled Table 1)
    nid_sets: int = 32
    nid_ways: int = 6
    id_sets: int = 8
    id_ways: int = 16
    dtype: str = "bfloat16"
    walk_impl: str = "auto"         # remap.irt.walk backend selection
    # decode hot path: keep the translated device table in state and only
    # re-translate rows whose mapping changed (False = legacy re-walk of
    # every row per lookup, kept for the serve_decode baseline benchmark)
    cache_device_table: bool = True
    gather_impl: str = "auto"       # migration-copy backend (remap_gather)

    @property
    def n_logical(self) -> int:
        return self.n_seqs * self.max_pages_per_seq

    @property
    def n_leaf(self) -> int:
        return -(-self.n_logical // E)

    @property
    def meta_slots(self) -> int:
        """Reserved metadata region, lendable while leaves are unallocated
        (one slot hosts one leaf block)."""
        return self.n_leaf

    @property
    def fast_slots(self) -> int:
        return self.fast_data_slots + self.meta_slots

    @property
    def n_words(self) -> int:
        return -(-self.n_leaf // 32)

    @property
    def rc_geometry(self) -> RemapCacheGeometry:
        return RemapCacheGeometry.from_tiered_config(self)

    @property
    def pol(self) -> PolicyConfig:
        """Effective policy: ``policy=`` if given, else the legacy
        ``migrate_threshold`` knob resolved into the default."""
        if self.policy is not None:
            return self.policy
        return PolicyConfig(promote_threshold=self.migrate_threshold)

    @property
    def page_bytes(self) -> int:
        """Bytes one K+V page moves across tiers (bandwidth accounting)."""
        return (2 * self.n_kv_heads * self.page_tokens * self.head_dim
                * jnp.dtype(self.dtype).itemsize)


class TieredState(NamedTuple):
    fast_k: jnp.ndarray          # [fast_slots, KV, page, hd]
    fast_v: jnp.ndarray
    slow_k: jnp.ndarray          # [n_logical, KV, page, hd] (homes)
    slow_v: jnp.ndarray
    l1_bits: jnp.ndarray         # [n_words] int32
    leaf_table: jnp.ndarray      # [n_leaf*E] int32 (page -> fast slot)
    leaf_cnt: jnp.ndarray        # [n_leaf] int32
    slot_owner: jnp.ndarray      # [fast_slots] int32 (inverse mapping)
    touch: jnp.ndarray           # [n_logical] int32 hotness (tracker base)
    ema: jnp.ndarray             # [n_logical] int32 (mea tracker carry)
    last_seen: jnp.ndarray       # [n_logical] int32 (recency tracker)
    wtouch: jnp.ndarray          # [n_logical] int32 write intensity
    epoch: jnp.ndarray           # scalar: maintain() calls so far
    fifo_ptr: jnp.ndarray        # scalar
    # cached device page table (the decode hot path reads THIS, not the
    # engine): dev_table[p] is p's translated device slot, valid until the
    # mapping mutates — every mutation site writes the new slot through
    dev_table: jnp.ndarray       # [n_logical] int32 (unified device slots)
    dev_valid: jnp.ndarray       # [n_logical] bool
    # iRC (state layout owned by core/remap/rcache)
    nid_tag: jnp.ndarray         # [nid_sets, nid_ways]
    nid_val: jnp.ndarray
    nid_fifo: jnp.ndarray
    id_tag: jnp.ndarray          # [id_sets, id_ways]
    id_bits: jnp.ndarray         # uint32 sector vectors
    id_fifo: jnp.ndarray
    # counters
    lookups: jnp.ndarray
    irc_hits: jnp.ndarray
    irc_id_hits: jnp.ndarray
    migrations: jnp.ndarray
    demotions: jnp.ndarray
    forced_evict: jnp.ndarray
    promo_pages: jnp.ndarray     # pages promoted (installs); bytes =
    demo_pages: jnp.ndarray      # count * cfg.page_bytes at read-out;
                                 # demo_pages counts ALL fast->slow
                                 # copy-backs (int32-safe page counts)
    dev_hits: jnp.ndarray        # live lookup lanes served from dev_table
    attn_pages: jnp.ndarray      # pages the fused decode kernel fetched


_RC_KEYS = ("nid_tag", "nid_val", "nid_fifo", "id_tag", "id_bits", "id_fifo")

# tracker-state field <-> core/policy/trackers key (DESIGN.md §7)
_TR_FIELDS = {"touch": "touch", "pol_ema": "ema", "pol_last": "last_seen"}


def _rc_view(st: TieredState) -> dict:
    return {k: getattr(st, k) for k in _RC_KEYS}


def _tr_view(cfg: TieredConfig, st: TieredState) -> dict:
    tr = {"touch": st.touch}
    if cfg.pol.tracker == "mea":
        tr["pol_ema"] = st.ema
    elif cfg.pol.tracker == "recency":
        tr["pol_last"] = st.last_seen
    return tr


def _tr_replace(st: TieredState, tr: dict) -> TieredState:
    return st._replace(**{_TR_FIELDS[k]: v for k, v in tr.items()})


def _now(cfg: TieredConfig, st: TieredState):
    """Current epoch index (``epoch_len`` maintain calls per epoch)."""
    return st.epoch // cfg.pol.epoch_len


def _irt_view(st: TieredState) -> dict:
    return {"entries": st.leaf_table, "l1_bits": st.l1_bits,
            "leaf_cnt": st.leaf_cnt}


def _irt_replace(st: TieredState, tab: dict) -> TieredState:
    return st._replace(leaf_table=tab["entries"], l1_bits=tab["l1_bits"],
                       leaf_cnt=tab["leaf_cnt"])


def init_state(cfg: TieredConfig) -> TieredState:
    dt = jnp.dtype(cfg.dtype)
    KV, P, hd = cfg.n_kv_heads, cfg.page_tokens, cfg.head_dim
    z = jnp.zeros
    tab = irt_ops.init_tables(cfg.n_logical)
    rc = rc_ops.init_state(cfg.rc_geometry)
    return TieredState(
        fast_k=z((cfg.fast_slots, KV, P, hd), dt),
        fast_v=z((cfg.fast_slots, KV, P, hd), dt),
        slow_k=z((cfg.n_logical, KV, P, hd), dt),
        slow_v=z((cfg.n_logical, KV, P, hd), dt),
        l1_bits=tab["l1_bits"],
        leaf_table=tab["entries"],
        leaf_cnt=tab["leaf_cnt"],
        slot_owner=jnp.full((cfg.fast_slots,), INVALID, jnp.int32),
        touch=z((cfg.n_logical,), jnp.int32),
        ema=z((cfg.n_logical,), jnp.int32),
        last_seen=jnp.full((cfg.n_logical,), -(1 << 20), jnp.int32),
        wtouch=z((cfg.n_logical,), jnp.int32),
        epoch=z((), jnp.int32),
        fifo_ptr=z((), jnp.int32),
        dev_table=cfg.fast_slots + jnp.arange(cfg.n_logical,
                                              dtype=jnp.int32),
        dev_valid=z((cfg.n_logical,), jnp.bool_),
        lookups=z((), jnp.int32), irc_hits=z((), jnp.int32),
        irc_id_hits=z((), jnp.int32), migrations=z((), jnp.int32),
        demotions=z((), jnp.int32), forced_evict=z((), jnp.int32),
        promo_pages=z((), jnp.int32), demo_pages=z((), jnp.int32),
        dev_hits=z((), jnp.int32), attn_pages=z((), jnp.int32),
        **rc,
    )


def logical_page(cfg: TieredConfig, seq: jnp.ndarray, j: jnp.ndarray):
    return seq * cfg.max_pages_per_seq + j


# ---------------------------------------------------------------------------
# lookup: logical page table -> device page table (the serving hot path)
# ---------------------------------------------------------------------------

def _translate(cfg: TieredConfig, st: TieredState, ids, enable):
    """The metadata path for one batch of page ids [N]: iRC probe, then
    the parallel two-level iRT walk (``remap.irt.walk`` routes large
    batches to the Pallas kernel).  iRC fills and every counter are masked
    by ``enable`` — disabled lanes cost nothing in the books.  Returns
    (device slots [N] — only enabled lanes meaningful, state)."""
    rcg = cfg.rc_geometry
    hit, val, id_hit = rc_ops.probe(rcg, _rc_view(st), ids)
    home = cfg.fast_slots + ids
    walked = irt_ops.walk(ids, jnp.full_like(ids, INVALID),
                          st.l1_bits, st.leaf_table, impl=cfg.walk_impl)
    dev_walk = jnp.where(walked == INVALID, home, walked)
    dev_irc = jnp.where(id_hit, home, val)
    dev = jnp.where(hit, dev_irc, dev_walk)
    st = st._replace(**rc_ops.fill(rcg, _rc_view(st), ids, walked,
                                   st.leaf_table, enable & ~hit))
    st = st._replace(
        lookups=st.lookups + enable.sum(dtype=jnp.int32),
        irc_hits=st.irc_hits + (enable & hit).sum(dtype=jnp.int32),
        irc_id_hits=st.irc_id_hits + (enable & id_hit).sum(dtype=jnp.int32))
    return dev, st


def lookup(cfg: TieredConfig, st: TieredState, page_ids, live=None):
    """page_ids [B, npages] logical -> (device_table [B, npages], state).

    Device slots index the *unified* address space: < fast_slots -> fast
    pool, otherwise fast_slots + home (slow pool) — the split-pool kernel
    routes on exactly this encoding, no concatenated pool exists.

    ``live`` [B, npages] bool masks the lanes that actually hold context
    (pages under ``seq_lens``): dead lanes are never translated or
    counted — translation work scales with live context, not max context
    — and resolve to their identity home (attention weights there are
    exactly zero, so any in-bounds slot is equivalent).

    With ``cfg.cache_device_table`` (the default), valid ``dev_table``
    rows are served directly and the metadata engine runs only when some
    live row is invalid (``lax.cond`` skips it entirely otherwise), so
    steady-state decode performs zero iRC probes and zero iRT walks.
    Hotness is recorded for every live lane either way — caching the
    translation must not starve the policy's tracker."""
    B, NP = page_ids.shape
    ids = page_ids.reshape(-1)
    lv = (jnp.ones(ids.shape, jnp.bool_) if live is None
          else jnp.asarray(live).reshape(-1))
    home = cfg.fast_slots + ids
    if not cfg.cache_device_table:
        dev, st = _translate(cfg, st, ids, lv)
        dev = jnp.where(lv, dev, home)
    else:
        need = lv & ~st.dev_valid[ids]
        # the cond carries ONLY the metadata arrays the engine can write —
        # routing the whole state (the KV pools!) through a lax.cond would
        # copy the pools at the conditional boundary, the very cost this
        # path exists to delete
        carry_keys = _RC_KEYS + ("dev_table", "dev_valid", "lookups",
                                 "irc_hits", "irc_id_hits")

        def _miss(sub):
            s = st._replace(**sub)
            dev, s = _translate(cfg, s, ids, need)
            idx = jnp.where(need, ids, cfg.n_logical)
            s = s._replace(
                dev_table=s.dev_table.at[idx].set(dev, mode="drop"),
                dev_valid=s.dev_valid.at[idx].set(True, mode="drop"))
            return {k: getattr(s, k) for k in carry_keys}

        sub = jax.lax.cond(need.any(), _miss, lambda sub: dict(sub),
                           {k: getattr(st, k) for k in carry_keys})
        st = st._replace(**sub)
        st = st._replace(
            dev_hits=st.dev_hits + (lv & ~need).sum(dtype=jnp.int32))
        dev = jnp.where(lv, st.dev_table[ids], home)
    st = _tr_replace(st, pol_track.record(cfg.pol, _tr_view(cfg, st), ids,
                                          now=_now(cfg, st), enable=lv))
    return dev.reshape(B, NP), st


def record_touches(cfg: TieredConfig, st: TieredState, ids,
                   enable) -> TieredState:
    """Record one hotness-tracker touch per enabled page id (the
    ``lookup`` tail without the translation) — the fused decode path's
    accounting hook: translation is the index map there, but the policy
    tracker must still see every live page or maintenance would starve."""
    return _tr_replace(st, pol_track.record(cfg.pol, _tr_view(cfg, st),
                                            ids, now=_now(cfg, st),
                                            enable=enable))


def record_reads(cfg: TieredConfig, st: TieredState, ids,
                 lv) -> TieredState:
    """Read-side accounting for the fused decode path, with ``lookup``'s
    cold/steady split: a live page whose ``dev_table`` row is not yet
    cached counts one translation (the leaf entry IS the translation —
    no walk runs) and caches its row; an already-cached page counts one
    ``dev_table`` hit.  Keeps ``trimma_translated_pages_total`` /
    ``trimma_dev_table_hits_total`` meaningful on the fused path, where
    no page table is ever materialised.  ``ids``/``lv`` are flat."""
    if not cfg.cache_device_table:
        return st._replace(lookups=st.lookups + lv.sum(dtype=jnp.int32))
    valid = st.dev_valid[ids]
    cold = lv & ~valid
    entry = st.leaf_table[ids]
    dev = jnp.where(entry != INVALID, entry, cfg.fast_slots + ids)
    idx = jnp.where(cold, ids, cfg.n_logical)
    return st._replace(
        dev_table=st.dev_table.at[idx].set(dev, mode="drop"),
        dev_valid=st.dev_valid.at[idx].set(True, mode="drop"),
        lookups=st.lookups + cold.sum(dtype=jnp.int32),
        dev_hits=st.dev_hits + (lv & valid).sum(dtype=jnp.int32))


def unified_pools(st: TieredState):
    """LEGACY: concatenated (fast | slow) pools — a full KV-cache copy.
    The decode path no longer calls this (the split-pool kernel reads both
    tiers in place); it survives as the reference layout for ground-truth
    checks and the ``serve_decode`` baseline benchmark.  It could never map
    onto deployment hardware, where the tiers are different memory kinds."""
    return (jnp.concatenate([st.fast_k, st.slow_k], axis=0),
            jnp.concatenate([st.fast_v, st.slow_v], axis=0))


def _page_gather(cfg: TieredConfig, pool, pid):
    """Fetch one page [KV, page, hd] through the migration engine
    (kernels/remap_gather: scalar-prefetched Pallas DMA on TPU,
    ``impl="ref"`` jnp take on CPU/CI — ``cfg.gather_impl`` selects)."""
    n, KV, P, hd = pool.shape
    out = remap_gather_op(pool.reshape(n, KV * P, hd),
                          jnp.asarray(pid, jnp.int32)[None],
                          impl=cfg.gather_impl)
    return out.reshape(KV, P, hd)


def _dev_update(cfg: TieredConfig, st: TieredState, pid, slot,
                enable) -> TieredState:
    """Write a page's new translation through the cached device table
    (entry-granular coherence, like the iRC's in-place bit update): the
    row stays valid, so mapping churn costs zero re-walks on the decode
    path.  All scalars; masked by ``enable``."""
    idx = jnp.where(enable, pid, cfg.n_logical)
    return st._replace(
        dev_table=st.dev_table.at[idx].set(slot, mode="drop"),
        dev_valid=st.dev_valid.at[idx].set(True, mode="drop"))


# ---------------------------------------------------------------------------
# append / migrate
# ---------------------------------------------------------------------------

def append_token(cfg: TieredConfig, st: TieredState, seq_ids, k, v, pos):
    """Write one new token's KV for each sequence at position ``pos``.
    k,v [B, KV, hd]; ``pos`` a scalar or a per-sequence [B] vector
    (ragged lanes).  New tokens land in the page's home slot; if the page
    is currently migrated (non-identity), the fast copy is updated
    instead.  Lanes whose position is negative (idle) or past the
    sequence's page capacity write nothing — an overflowing lane must
    never spill into a neighbour's logical range."""
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), seq_ids.shape)
    page = pos // cfg.page_tokens
    off = pos % cfg.page_tokens
    ok = (page >= 0) & (page < cfg.max_pages_per_seq)
    ids = logical_page(cfg, seq_ids, jnp.clip(page, 0,
                                              cfg.max_pages_per_seq - 1))
    entry = st.leaf_table[ids]
    in_fast = entry != INVALID
    # masked scatter via out-of-bounds drop: disabled lanes must not write
    # anything (a clamped index + old-value write can clobber an enabled
    # write to the same row — scatter order is undefined)
    fast_idx = jnp.where(ok & in_fast, entry, cfg.fast_slots)
    slow_idx = jnp.where(ok & ~in_fast, ids, cfg.n_logical)
    dt = st.fast_k.dtype
    st = st._replace(
        fast_k=st.fast_k.at[fast_idx, :, off].set(k.astype(dt), mode="drop"),
        fast_v=st.fast_v.at[fast_idx, :, off].set(v.astype(dt), mode="drop"),
        slow_k=st.slow_k.at[slow_idx, :, off].set(k.astype(dt), mode="drop"),
        slow_v=st.slow_v.at[slow_idx, :, off].set(v.astype(dt), mode="drop"),
        wtouch=st.wtouch.at[jnp.where(ok, ids, cfg.n_logical)].add(
            1, mode="drop"))
    if cfg.pol.write_weight > 1:        # write-aware: appends heat pages up
        # base weight only: the extra (write_weight-1) per write comes from
        # wtouch at scoring time (run_scheduler), matching the simulator's
        # R + write_weight*W accumulation without double counting
        st = _tr_replace(st, pol_track.record(
            cfg.pol, _tr_view(cfg, st), ids, now=_now(cfg, st), enable=ok))
    return st


def append_routing(cfg: TieredConfig, st: TieredState, seq_ids, pos, k_tok):
    """Routing for ``k_tok`` consecutive new tokens per lane starting at
    ``pos`` [B]: (ok, ids, fast_idx, slow_idx, off), all [B, k_tok].
    Masked-out entries carry the out-of-bounds sentinel their pool's
    ``mode="drop"`` scatter drops.  Idle lanes (``pos < 0``) are fully
    masked — a parked lane's later tokens (``pos + i >= 0``) must not
    alias page 0."""
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), seq_ids.shape)
    pgrid = pos[:, None] + jnp.arange(k_tok, dtype=jnp.int32)
    page = pgrid // cfg.page_tokens
    off = pgrid % cfg.page_tokens
    ok = (pos[:, None] >= 0) & (page >= 0) & (page < cfg.max_pages_per_seq)
    ids = logical_page(cfg, seq_ids[:, None],
                       jnp.clip(page, 0, cfg.max_pages_per_seq - 1))
    entry = st.leaf_table[ids]
    in_fast = entry != INVALID
    fast_idx = jnp.where(ok & in_fast, entry, cfg.fast_slots)
    slow_idx = jnp.where(ok & ~in_fast, ids, cfg.n_logical)
    return ok, ids, fast_idx, slow_idx, off


def append_tokens(cfg: TieredConfig, st: TieredState, seq_ids, k, v, pos):
    """k-token ``append_token``: k, v [B, K, KV, hd] are K consecutive new
    tokens per lane, lane b's token i landing at position ``pos[b] + i``.
    One batched routed scatter per pool — bitwise equal to K sequential
    ``append_token`` calls (routing cannot change mid-call: appends never
    move pages, and all K offsets derive from the same leaf entries)."""
    K = k.shape[1]
    ok, ids, fast_idx, slow_idx, off = append_routing(cfg, st, seq_ids,
                                                      pos, K)
    dt = st.fast_k.dtype
    st = st._replace(
        fast_k=st.fast_k.at[fast_idx, :, off].set(k.astype(dt), mode="drop"),
        fast_v=st.fast_v.at[fast_idx, :, off].set(v.astype(dt), mode="drop"),
        slow_k=st.slow_k.at[slow_idx, :, off].set(k.astype(dt), mode="drop"),
        slow_v=st.slow_v.at[slow_idx, :, off].set(v.astype(dt), mode="drop"),
        wtouch=st.wtouch.at[jnp.where(ok, ids, cfg.n_logical)].add(
            1, mode="drop"))
    if cfg.pol.write_weight > 1:
        st = _tr_replace(st, pol_track.record(
            cfg.pol, _tr_view(cfg, st), ids.reshape(-1),
            now=_now(cfg, st), enable=ok.reshape(-1)))
    return st


def prefill_tokens(cfg: TieredConfig, st: TieredState, seq, k, v,
                   length=None):
    """Batched prompt ingest: write tokens ``[0, length)`` of sequence
    ``seq`` into its slow-pool homes in one pass (no per-token replay).

    k, v: [S, KV, hd] post-RoPE prompt K/V; ``S`` may carry padding —
    tokens at positions >= ``length`` (traced scalar; default S) are
    either skipped page-wise or masked downstream by ``seq_lens`` until
    decode appends overwrite them.  Only whole pages below ``length``
    plus the partial tail page are written, each as one row store.

    Precondition: the sequence's pages map to identity (freshly
    initialised or just released) — writes go to the homes, so a still-
    resident page's fast copy would go stale.  The engine releases every
    lane before prefilling it."""
    S, KV, hd = k.shape
    P = cfg.page_tokens
    npages = -(-S // P)
    if npages > cfg.max_pages_per_seq:
        raise ValueError(
            f"prompt of {S} tokens needs {npages} pages; sequence capacity "
            f"is {cfg.max_pages_per_seq}")
    length = jnp.asarray(S if length is None else length, jnp.int32)
    dt = st.slow_k.dtype
    pad = npages * P - S
    pages_k = jnp.pad(k.astype(dt), ((0, pad), (0, 0), (0, 0))) \
        .reshape(npages, P, KV, hd).transpose(0, 2, 1, 3)
    pages_v = jnp.pad(v.astype(dt), ((0, pad), (0, 0), (0, 0))) \
        .reshape(npages, P, KV, hd).transpose(0, 2, 1, 3)
    seq = jnp.asarray(seq, jnp.int32)
    j = jnp.arange(npages, dtype=jnp.int32)
    rows = jnp.where(j * P < length,
                     seq * cfg.max_pages_per_seq + j, cfg.n_logical)
    return st._replace(
        slow_k=st.slow_k.at[rows].set(pages_k, mode="drop"),
        slow_v=st.slow_v.at[rows].set(pages_v, mode="drop"))


def prefill_chunk(cfg: TieredConfig, st: TieredState, seq, k, v, start,
                  length):
    """Chunked prompt ingest (DESIGN.md §9): write tokens
    ``[start, start + C)`` of sequence ``seq``, one chunk of a prompt
    whose earlier chunks already landed.  Unlike ``prefill_tokens`` this
    write ROUTES: each page goes to its *current* tier — the fast copy if
    the page is resident (direct-to-fast admission at ingest,
    ``admit_pages``), else the slow home — the same write-through rule
    ``append_token`` follows, so ingest after admission (or after a
    mid-ingest promotion by ``run_scheduler``) never leaves a stale fast
    copy.

    k, v: [C, KV, hd] post-RoPE chunk K/V.  ``start`` (traced int32) must
    be page-aligned and every chunk except the last must cover whole
    pages — each page row is ONE store, so a ragged chunk boundary inside
    a page would zero the page's earlier tokens.  Tokens at positions
    >= ``length`` are pad garbage masked downstream by ``seq_lens`` until
    decode appends overwrite them (exactly ``prefill_tokens``'s
    convention).  Applying the chunks of a prompt through this op is
    bit-identical to one ``prefill_tokens`` pass over the whole prompt
    when nothing is resident (tests/test_sched.py pins it)."""
    C, KV, hd = k.shape
    P = cfg.page_tokens
    npages = -(-C // P)
    start = jnp.asarray(start, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    dt = st.slow_k.dtype
    pad = npages * P - C
    pages_k = jnp.pad(k.astype(dt), ((0, pad), (0, 0), (0, 0))) \
        .reshape(npages, P, KV, hd).transpose(0, 2, 1, 3)
    pages_v = jnp.pad(v.astype(dt), ((0, pad), (0, 0), (0, 0))) \
        .reshape(npages, P, KV, hd).transpose(0, 2, 1, 3)
    seq = jnp.asarray(seq, jnp.int32)
    j = start // P + jnp.arange(npages, dtype=jnp.int32)
    ok = (j * P < length) & (j < cfg.max_pages_per_seq)
    ids = logical_page(cfg, seq, jnp.clip(j, 0, cfg.max_pages_per_seq - 1))
    entry = st.leaf_table[ids]
    in_fast = entry != INVALID
    fast_idx = jnp.where(ok & in_fast, entry, cfg.fast_slots)
    slow_idx = jnp.where(ok & ~in_fast, ids, cfg.n_logical)
    return st._replace(
        fast_k=st.fast_k.at[fast_idx].set(pages_k, mode="drop"),
        fast_v=st.fast_v.at[fast_idx].set(pages_v, mode="drop"),
        slow_k=st.slow_k.at[slow_idx].set(pages_k, mode="drop"),
        slow_v=st.slow_v.at[slow_idx].set(pages_v, mode="drop"))


def admit_pages(cfg: TieredConfig, st: TieredState, seq, length,
                n_pages: int) -> TieredState:
    """Direct-to-fast admission at ingest (DESIGN.md §9): promote the
    first ``n_pages`` pages of sequence ``seq`` (those holding tokens
    below ``length``) into the fast pool NOW, instead of waiting for
    decode touches to heat them — the cache-style on-demand install the
    scheduler consults the policy decider for.  Each admitted page
    records one tracker touch (install touch), so a maintain pass that
    lands mid-ingest cannot demote it straight back as score-0 cold.

    Chunk writes that follow route to the admitted fast copies
    (``prefill_chunk``); the slow home then holds pre-ingest garbage
    until demotion/eviction copies the fast bytes back — the standard
    resident-page coherence rule (§3's write-through table applies at
    ingest)."""
    seq = jnp.asarray(seq, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    j = jnp.arange(int(n_pages), dtype=jnp.int32)
    en = (j * cfg.page_tokens < length) & (j < cfg.max_pages_per_seq)
    ids = logical_page(cfg, seq, jnp.clip(j, 0, cfg.max_pages_per_seq - 1))

    def body(s, args):
        pid, e = args
        return migrate_one(cfg, s, pid, e), None

    st, _ = jax.lax.scan(body, st, (ids, en))
    return _tr_replace(st, pol_track.record(cfg.pol, _tr_view(cfg, st), ids,
                                            now=_now(cfg, st), enable=en))


def _leaf_hosting_slot(cfg: TieredConfig, leaf):
    """Leaf i is hosted at fast slot fast_data_slots + i (fixed location,
    Section 3.2)."""
    return cfg.fast_data_slots + leaf


def _drop_entry(cfg: TieredConfig, st: TieredState, pid, enable,
                copy_back_from=None, apply_pools: bool = True
                ) -> TieredState:
    """Shared eviction tail: clear pid's iRT entry (engine op), update the
    iRC (entry becomes identity), write the identity translation through
    the device table, optionally copy the fast bytes home (a migration-
    engine gather + masked scatter).  ``apply_pools=False`` skips the byte
    copy but keeps every metadata effect and counter — the descriptor
    record/replay path (stacked maintenance) moves the bytes itself."""
    pv = jnp.where(enable, pid, 0)
    if copy_back_from is not None:
        if apply_pools:
            src = jnp.where(enable, copy_back_from, 0)
            st = st._replace(
                slow_k=st.slow_k.at[pv].set(
                    jnp.where(enable, _page_gather(cfg, st.fast_k, src),
                              st.slow_k[pv])),
                slow_v=st.slow_v.at[pv].set(
                    jnp.where(enable, _page_gather(cfg, st.fast_v, src),
                              st.slow_v[pv])))
        # every fast->slow copy-back is migration bandwidth, whether a
        # scheduler demotion, a FIFO victim or a forced metadata evict
        st = st._replace(demo_pages=st.demo_pages + jnp.where(enable, 1, 0))
    st = _irt_replace(st, irt_ops.invalidate(_irt_view(st), pv[None],
                                             enable[None]))
    st = st._replace(**rc_ops.invalidate(
        cfg.rc_geometry, _rc_view(st), pv[None], enable[None],
        becomes_identity=True))
    return _dev_update(cfg, st, pv, cfg.fast_slots + pv, enable)


def migrate_one(cfg: TieredConfig, st: TieredState, page_id, enable):
    """Migrate one hot logical page into the fast pool (FIFO victim,
    skipping allocated-metadata slots; metadata priority on leaf
    allocation).  All updates masked by ``enable``."""
    st, _ = _migrate_one_desc(cfg, st, page_id, enable)
    return st


def _migrate_one_desc(cfg: TieredConfig, st: TieredState, page_id, enable,
                      apply_pools: bool = True):
    """``migrate_one`` body, returning ``(state, desc)`` where ``desc``
    records the (up to three) page copies the move implies — victim
    copy-back, install, forced-evict copy-back — as (src, dst, enable)
    scalar triples.  With ``apply_pools=False`` the copies are *only*
    recorded: the stacked maintenance path replays them once over the
    whole ``[L, ...]`` pool stack instead of per layer."""
    pid = jnp.where(enable, page_id, 0)
    already = st.leaf_table[pid] != INVALID
    en = enable & ~already

    # --- FIFO victim skipping slots whose hosted leaf is allocated -------
    K = cfg.fast_slots
    order = (st.fifo_ptr + jnp.arange(K)) % K
    hosted_leaf = order - cfg.fast_data_slots          # leaf id or <0
    is_meta = order >= cfg.fast_data_slots
    leaf_ok = jnp.where(
        is_meta, st.leaf_cnt[jnp.clip(hosted_leaf, 0, cfg.n_leaf - 1)] == 0,
        True)
    # cannot evict the slot that will host this page's own leaf
    my_leaf = pid // E
    leaf_ok &= order != _leaf_hosting_slot(cfg, my_leaf)
    # prefer an admissible *empty* slot (e.g. one a demotion just freed in
    # this maintain pass) — only fall back to evicting a resident, and
    # only then advance the FIFO hand, so demote-first actually frees
    # slots for the promotions that follow
    empty_ok = leaf_ok & (st.slot_owner[order] == INVALID)
    has_empty = empty_ok.any()
    pos = jnp.where(has_empty, jnp.argmax(empty_ok), jnp.argmax(leaf_ok))
    v = order[pos]
    st = st._replace(fifo_ptr=jnp.where(en & ~has_empty,
                                        (st.fifo_ptr + pos + 1) % K,
                                        st.fifo_ptr))

    # --- evict current occupant (slow-swap: copy back is a no-op, homes
    # always hold the canonical bytes except the in-fast tail writes,
    # which append_token keeps mirrored) --------------------------------
    o = st.slot_owner[v]
    has_o = en & (o != INVALID)
    st = _drop_entry(cfg, st, o, has_o, copy_back_from=jnp.where(en, v, 0),
                     apply_pools=apply_pools)

    # --- install the page (migration-engine gather from the slow home) ----
    vv = jnp.where(en, v, 0)
    if apply_pools:
        st = st._replace(
            fast_k=st.fast_k.at[vv].set(
                jnp.where(en, _page_gather(cfg, st.slow_k, pid),
                          st.fast_k[vv])),
            fast_v=st.fast_v.at[vv].set(
                jnp.where(en, _page_gather(cfg, st.slow_v, pid),
                          st.fast_v[vv])))
    st = st._replace(
        slot_owner=st.slot_owner.at[vv].set(
            jnp.where(en, pid, st.slot_owner[vv])),
        migrations=st.migrations + jnp.where(en, 1, 0),
        promo_pages=st.promo_pages + jnp.where(en, 1, 0))
    st = _irt_replace(st, irt_ops.fill(_irt_view(st), pid[None], v[None],
                                       en[None]))
    st = st._replace(**rc_ops.invalidate(
        cfg.rc_geometry, _rc_view(st), pid[None], en[None],
        becomes_identity=False))
    st = _dev_update(cfg, st, pid, vv, en)

    # --- metadata priority: evict data from the newly-allocated leaf's
    # hosting slot (Section 3.3) -----------------------------------------
    h = _leaf_hosting_slot(cfg, my_leaf)
    was_free = st.leaf_cnt[my_leaf] == 1        # we allocated it just now
    hv0 = jnp.clip(h, 0, cfg.fast_slots - 1)
    x = st.slot_owner[hv0]
    need = en & was_free & (x != INVALID) & (h < cfg.fast_slots)
    hv = jnp.where(need, h, 0)
    st = _drop_entry(cfg, st, x, need, copy_back_from=hv,
                     apply_pools=apply_pools)
    st = st._replace(
        slot_owner=st.slot_owner.at[hv].set(
            jnp.where(need, INVALID, st.slot_owner[hv])),
        forced_evict=st.forced_evict + jnp.where(need, 1, 0))
    desc = {"cb1_src": jnp.where(en, v, 0), "cb1_dst": jnp.where(has_o, o, 0),
            "cb1_en": has_o,
            "in_src": pid, "in_dst": vv, "in_en": en,
            "cb2_src": hv, "cb2_dst": jnp.where(need, x, 0), "cb2_en": need}
    return st, desc


def demote_one(cfg: TieredConfig, st: TieredState, page_id, enable):
    """Demote one resident page back to its slow home: copy the fast bytes
    home, clear the iRT entry (engine op) + slot, reset its hotness.  All
    updates masked by ``enable``; non-resident pages are a no-op."""
    st, _ = _demote_one_desc(cfg, st, page_id, enable)
    return st


def _demote_one_desc(cfg: TieredConfig, st: TieredState, page_id, enable,
                     apply_pools: bool = True):
    """``demote_one`` body returning ``(state, desc)`` — one copy-back
    triple; see ``_migrate_one_desc``."""
    pid = jnp.where(enable, page_id, 0)
    entry = st.leaf_table[pid]
    en = enable & (entry != INVALID)
    slot = jnp.where(en, entry, 0)
    st = _drop_entry(cfg, st, pid, en, copy_back_from=slot,
                     apply_pools=apply_pools)
    st = st._replace(
        slot_owner=st.slot_owner.at[slot].set(
            jnp.where(en, INVALID, st.slot_owner[slot])),
        demotions=st.demotions + jnp.where(en, 1, 0))
    return st, {"cb1_src": slot, "cb1_dst": pid, "cb1_en": en}


def release_seq(cfg: TieredConfig, st: TieredState, seq) -> TieredState:
    """Free one sequence's pages when its lane is recycled (continuous
    batching: a finished request's KV is dead the moment the lane refills).

    No bytes move — the pages are garbage — but every metadata structure
    resets to identity in one batched pass: iRT entries cleared (engine
    op over the row), fast slots released, hotness forgotten, the iRC
    row-range invalidated (``remap.rcache.invalidate_range`` — one dense
    pass instead of ``max_pages_per_seq`` per-id probes), and the device
    table's rows rewritten to the identity homes, still valid."""
    seq = jnp.asarray(seq, jnp.int32)
    lo = seq * cfg.max_pages_per_seq
    ids = lo + jnp.arange(cfg.max_pages_per_seq, dtype=jnp.int32)
    entry = st.leaf_table[ids]
    res = entry != INVALID
    st = st._replace(
        slot_owner=st.slot_owner.at[jnp.where(res, entry, cfg.fast_slots)]
        .set(INVALID, mode="drop"))
    st = _irt_replace(st, irt_ops.invalidate(_irt_view(st), ids, res))
    st = st._replace(**rc_ops.invalidate_range(
        cfg.rc_geometry, _rc_view(st), lo, lo + cfg.max_pages_per_seq))
    st = _tr_replace(st, pol_track.forget(
        cfg.pol, _tr_view(cfg, st), ids, jnp.ones_like(res)))
    return st._replace(
        wtouch=st.wtouch.at[ids].set(0),
        dev_table=st.dev_table.at[ids].set(cfg.fast_slots + ids),
        dev_valid=st.dev_valid.at[ids].set(True))


def run_scheduler(cfg: TieredConfig, st: TieredState,
                  max_moves: int | None = None) -> TieredState:
    """One off-critical-path maintenance pass (Figure 3's step 3), driven
    by ``core/policy`` (DESIGN.md §7):

      1. score every logical page with the policy's tracker;
      2. ``scheduler.plan``: bounded promotion + demotion queues
         (residents never re-enter the promotion queue; write-aware
         policies demote first and keep write-hot residents);
      3. apply demotions, then promotions (bandwidth is accounted at the
         copy sites: ``promo_pages`` per promotion install, ``demo_pages``
         per fast->slow copy-back — scheduler demotions AND victim/forced
         evictions; multiply by ``cfg.page_bytes`` at read-out so the
         int32 state counter can't overflow at realistic page sizes);
      4. advance the epoch; at each ``epoch_len`` boundary the tracker
         decays, so an untouched page eventually becomes demotable (the
         stale-hotness fix — tests/test_policy.py pins it).
    """
    pol = cfg.pol
    mm = pol.max_moves if max_moves is None else int(max_moves)
    sc, resident, now = _plan_inputs(cfg, st)
    p = pol_sched.plan(pol, sc, resident, mm)
    st, _, _ = _apply_plan(cfg, st, p, now)
    return st


def run_scheduler_tenants(cfg: TieredConfig, st: TieredState, page_tenant,
                          pols, quotas) -> TieredState:
    """The multi-tenant maintenance pass (DESIGN.md §9): same scoring and
    apply path as ``run_scheduler``, but the move queues come from
    ``core/policy.plan_tenants`` — one bounded plan per tenant over its
    own pages (``page_tenant`` [n_logical] int32; < 0 == unowned, moves
    for nobody), each with its own decider thresholds + ``max_moves``
    budget (``pols``, static tuple) and a fast-slot quota (``quotas``,
    static tuple) its residency can never exceed.  The hotness trackers
    are shared state — tenants may vary deciders and budgets, not the
    tracker kind (the tracker arrays are laid out once per
    ``TieredConfig``)."""
    sc, resident, now = _plan_inputs(cfg, st)
    p = pol_sched.plan_tenants(pols, sc, resident, page_tenant, quotas)
    st, _, _ = _apply_plan(cfg, st, p, now)
    return st


def _plan_inputs(cfg: TieredConfig, st: TieredState):
    """Shared scoring front half of the maintenance pass: (scores [n],
    residency [n], epoch now)."""
    pol = cfg.pol
    n = cfg.n_logical
    now = _now(cfg, st)
    sc = pol_track.score(pol, _tr_view(cfg, st), now=now)[:n]
    if pol.decider == "write_aware":
        # one write-weighted score for gate AND demote ranking: touch holds
        # R + W (base weight), wtouch holds W, so this is R + write_weight*W
        # — the same accumulation the simulator gate makes per access
        sc = sc + (pol.write_weight - 1) * st.wtouch[:n]
    resident = st.leaf_table[:n] != INVALID
    return sc, resident, now


def _apply_plan(cfg: TieredConfig, st: TieredState, p, now,
                apply_pools: bool = True):
    """Shared apply tail: demotions, then promotions, then tracker
    forget/decay and the epoch advance.  Returns ``(state, demote_descs,
    promote_descs)`` — the copy descriptors each move recorded
    (move-major arrays), which the stacked path replays over the whole
    layer stack when ``apply_pools=False`` left the bytes in place."""
    pol = cfg.pol
    n = cfg.n_logical

    def dbody(s, args):
        pid, en = args
        return _demote_one_desc(cfg, s, pid, en, apply_pools=apply_pools)

    st, ddesc = jax.lax.scan(dbody, st, (p.demote_ids, p.demote_en))

    def pbody(s, args):
        pid, en = args
        return _migrate_one_desc(cfg, s, pid, en, apply_pools=apply_pools)

    st, pdesc = jax.lax.scan(pbody, st, (p.promote_ids, p.promote_en))

    # demoted pages restart cold (write intensity included); promoted
    # pages keep their score so the demotion band can't reclaim them
    # before at least one decay epoch
    tr = _tr_view(cfg, st)
    tr = pol_track.forget(pol, tr, p.demote_ids, p.demote_en)
    tick = ((st.epoch + 1) % pol.epoch_len) == 0
    tr = pol_track.epoch_tick(pol, tr, now=now, enable=tick)
    st = _tr_replace(st, tr)
    didx = jnp.where(p.demote_en, p.demote_ids, n)
    wtouch = st.wtouch.at[didx].set(0, mode="drop")
    st = st._replace(
        epoch=st.epoch + 1,
        wtouch=jnp.where(tick, wtouch >> 1, wtouch))
    return st, ddesc, pdesc


def migrate_hot(cfg: TieredConfig, st: TieredState, max_moves: int = 4):
    """DEPRECATED shim: the inlined top-k promotion pass is now the policy
    scheduler (``run_scheduler``), which adds demotion + epoch decay."""
    return run_scheduler(cfg, st, max_moves=max_moves)


def metadata_pages(cfg: TieredConfig, st: TieredState) -> jnp.ndarray:
    """Current metadata footprint in pages (allocated leaves), vs the
    linear-table equivalent n_leaf (Figure 9 analogue for serving)."""
    return (st.leaf_cnt > 0).sum()


# ---------------------------------------------------------------------------
# layer-stacked variants (DESIGN.md §11)
#
# A transformer's L layers share one residency history: every metadata
# mutation is driven by lane-level events (appends, lookups, releases,
# maintenance) that are identical across layers, so from the broadcast
# init onward the leaf table, slot owners, trackers and counters are the
# same in every layer — only the pool *bytes* differ.  The ops below
# exploit that invariant: metadata work (scoring, planning, iRT/iRC
# updates, counters) runs ONCE on layer 0, the per-move page copies are
# recorded as descriptors and replayed over the whole [L, ...] pool
# stack, and the resulting metadata is broadcast back — bit-identical to
# ``jax.vmap`` over L independent passes at 1/L the metadata cost.
# ---------------------------------------------------------------------------

_POOL_FIELDS = ("fast_k", "fast_v", "slow_k", "slow_v")


def _layer0(sts: TieredState) -> TieredState:
    return jax.tree.map(lambda x: x[0], sts)


def _restack(st0: TieredState, pools, L: int) -> TieredState:
    """Broadcast layer-0 metadata back over L layers around the (already
    stacked) pools."""
    rep = {f: jnp.broadcast_to(getattr(st0, f),
                               (L,) + getattr(st0, f).shape)
           for f in TieredState._fields if f not in _POOL_FIELDS}
    rep.update(dict(zip(_POOL_FIELDS, pools)))
    return TieredState(**rep)


def _copy_page_stacked(cfg: TieredConfig, dst_pool, src_pool, src, dst, en):
    """Replay one recorded page copy on every layer of a [L, n, KV, P, hd]
    pool pair: gather row ``src`` of each layer through the migration
    engine, scatter to row ``dst`` (dropped when ``en`` is false)."""
    L, n_src = src_pool.shape[:2]
    n_dst = dst_pool.shape[1]
    KV, P, hd = src_pool.shape[2:]
    rows = (jnp.where(en, src, 0)
            + jnp.arange(L, dtype=jnp.int32) * n_src)
    pages = remap_gather_op(src_pool.reshape(L * n_src, KV * P, hd), rows,
                            impl=cfg.gather_impl).reshape(L, KV, P, hd)
    di = jnp.where(en, dst, n_dst)
    return dst_pool.at[:, di].set(pages, mode="drop")


def _replay_descs(cfg: TieredConfig, pools, ddesc, pdesc):
    """Apply recorded maintenance copies to the stacked pools, in exactly
    the order the metadata pass recorded them: all demote copy-backs,
    then per promotion victim copy-back -> install -> forced-evict
    copy-back.  Move order matters (a promotion may install into a slot
    an earlier move freed), so moves replay sequentially; layers replay
    together inside each move."""
    def dstep(pl, d):
        fk, fv, sk, sv = pl
        sk = _copy_page_stacked(cfg, sk, fk, d["cb1_src"], d["cb1_dst"],
                                d["cb1_en"])
        sv = _copy_page_stacked(cfg, sv, fv, d["cb1_src"], d["cb1_dst"],
                                d["cb1_en"])
        return (fk, fv, sk, sv), None

    if ddesc is not None:
        pools, _ = jax.lax.scan(dstep, pools, ddesc)

    def pstep(pl, d):
        fk, fv, sk, sv = pl
        sk = _copy_page_stacked(cfg, sk, fk, d["cb1_src"], d["cb1_dst"],
                                d["cb1_en"])
        sv = _copy_page_stacked(cfg, sv, fv, d["cb1_src"], d["cb1_dst"],
                                d["cb1_en"])
        fk = _copy_page_stacked(cfg, fk, sk, d["in_src"], d["in_dst"],
                                d["in_en"])
        fv = _copy_page_stacked(cfg, fv, sv, d["in_src"], d["in_dst"],
                                d["in_en"])
        sk = _copy_page_stacked(cfg, sk, fk, d["cb2_src"], d["cb2_dst"],
                                d["cb2_en"])
        sv = _copy_page_stacked(cfg, sv, fv, d["cb2_src"], d["cb2_dst"],
                                d["cb2_en"])
        return (fk, fv, sk, sv), None

    if pdesc is not None:
        pools, _ = jax.lax.scan(pstep, pools, pdesc)
    return pools


def _stacked_pools(sts: TieredState):
    return (sts.fast_k, sts.fast_v, sts.slow_k, sts.slow_v)


def plan_maintenance(cfg: TieredConfig, sts: TieredState,
                     max_moves: int | None = None):
    """Score + plan from layer 0 of a stacked state (one plan serves every
    layer).  Returns the ``core/policy`` Plan pytree;
    ``apply_maintenance_stacked`` applies it — possibly one decode step
    later (the engine double-buffers the pair; write-through makes the
    bytes order-independent, DESIGN.md §11)."""
    st0 = _layer0(sts)
    pol = cfg.pol
    mm = pol.max_moves if max_moves is None else int(max_moves)
    sc, resident, now = _plan_inputs(cfg, st0)
    return pol_sched.plan(pol, sc, resident, mm)


def apply_maintenance_stacked_desc(cfg: TieredConfig, sts: TieredState,
                                   p):
    """``apply_maintenance_stacked`` that also returns the move
    descriptors ``(state, ddesc, pdesc)`` — the per-move copy records
    (``_demote_one_desc`` / ``_migrate_one_desc``) the replay consumed.
    The descriptors are the ground truth of what actually moved (a
    planned promotion whose page was already resident records a
    disabled move), so the flight recorder (obs/flight, DESIGN.md §12)
    stamps its promote/demote/evict events from them."""
    L = sts.fast_k.shape[0]
    st0 = _layer0(sts)
    st0, ddesc, pdesc = _apply_plan(cfg, st0, p, _now(cfg, st0),
                                    apply_pools=False)
    pools = _replay_descs(cfg, _stacked_pools(sts), ddesc, pdesc)
    return _restack(st0, pools, L), ddesc, pdesc


def apply_maintenance_stacked(cfg: TieredConfig, sts: TieredState,
                              p) -> TieredState:
    """Apply a Plan to a stacked state: metadata once on layer 0 with
    pool writes recorded as descriptors, copies replayed over the [L, ...]
    stack, metadata broadcast back."""
    sts, _, _ = apply_maintenance_stacked_desc(cfg, sts, p)
    return sts


def run_scheduler_stacked(cfg: TieredConfig, sts: TieredState,
                          max_moves: int | None = None) -> TieredState:
    """One synchronous maintenance pass over a stacked [L, ...] state —
    the batched replacement for ``jax.vmap(run_scheduler)`` over L."""
    return apply_maintenance_stacked(cfg, sts,
                                     plan_maintenance(cfg, sts, max_moves))


def run_scheduler_tenants_stacked(cfg: TieredConfig, sts: TieredState,
                                  page_tenant, pols, quotas) -> TieredState:
    """Stacked ``run_scheduler_tenants`` (always synchronous — the tenant
    map can go stale across a deferred apply, so the engine never
    double-buffers this path)."""
    L = sts.fast_k.shape[0]
    st0 = _layer0(sts)
    sc, resident, now = _plan_inputs(cfg, st0)
    p = pol_sched.plan_tenants(pols, sc, resident, page_tenant, quotas)
    st0, ddesc, pdesc = _apply_plan(cfg, st0, p, now, apply_pools=False)
    pools = _replay_descs(cfg, _stacked_pools(sts), ddesc, pdesc)
    return _restack(st0, pools, L)


def release_seq_stacked(cfg: TieredConfig, sts: TieredState,
                        seq) -> TieredState:
    """Stacked ``release_seq``: pure metadata (no bytes move), so layer 0
    releases and the result broadcasts."""
    L = sts.fast_k.shape[0]
    st0 = release_seq(cfg, _layer0(sts), seq)
    return _restack(st0, _stacked_pools(sts), L)


def prefill_tokens_stacked(cfg: TieredConfig, sts: TieredState, seq, k, v,
                           length=None) -> TieredState:
    """Stacked ``prefill_tokens``: k, v [L, S, KV, hd] (all layers of one
    prompt's post-RoPE K/V) land in the slow homes as one scatter per
    pool.  Same preconditions as the per-layer op."""
    L, S, KV, hd = k.shape
    P = cfg.page_tokens
    npages = -(-S // P)
    if npages > cfg.max_pages_per_seq:
        raise ValueError(
            f"prompt of {S} tokens needs {npages} pages; sequence capacity "
            f"is {cfg.max_pages_per_seq}")
    length = jnp.asarray(S if length is None else length, jnp.int32)
    dt = sts.slow_k.dtype
    pad = npages * P - S

    def paged(x):
        return jnp.pad(x.astype(dt), ((0, 0), (0, pad), (0, 0), (0, 0))) \
            .reshape(L, npages, P, KV, hd).transpose(0, 1, 3, 2, 4)

    seq = jnp.asarray(seq, jnp.int32)
    j = jnp.arange(npages, dtype=jnp.int32)
    rows = jnp.where(j * P < length,
                     seq * cfg.max_pages_per_seq + j, cfg.n_logical)
    return sts._replace(
        slow_k=sts.slow_k.at[:, rows].set(paged(k), mode="drop"),
        slow_v=sts.slow_v.at[:, rows].set(paged(v), mode="drop"))


def prefill_chunk_stacked(cfg: TieredConfig, sts: TieredState, seq, k, v,
                          start, length) -> TieredState:
    """Stacked ``prefill_chunk``: k, v [L, C, KV, hd]; each page routes to
    its current tier via the (layer-uniform) layer-0 leaf table."""
    L, C, KV, hd = k.shape
    P = cfg.page_tokens
    npages = -(-C // P)
    start = jnp.asarray(start, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    dt = sts.slow_k.dtype
    pad = npages * P - C

    def paged(x):
        return jnp.pad(x.astype(dt), ((0, 0), (0, pad), (0, 0), (0, 0))) \
            .reshape(L, npages, P, KV, hd).transpose(0, 1, 3, 2, 4)

    seq = jnp.asarray(seq, jnp.int32)
    j = start // P + jnp.arange(npages, dtype=jnp.int32)
    ok = (j * P < length) & (j < cfg.max_pages_per_seq)
    ids = logical_page(cfg, seq, jnp.clip(j, 0, cfg.max_pages_per_seq - 1))
    entry = sts.leaf_table[0][ids]
    in_fast = entry != INVALID
    fast_idx = jnp.where(ok & in_fast, entry, cfg.fast_slots)
    slow_idx = jnp.where(ok & ~in_fast, ids, cfg.n_logical)
    return sts._replace(
        fast_k=sts.fast_k.at[:, fast_idx].set(paged(k), mode="drop"),
        fast_v=sts.fast_v.at[:, fast_idx].set(paged(v), mode="drop"),
        slow_k=sts.slow_k.at[:, slow_idx].set(paged(k), mode="drop"),
        slow_v=sts.slow_v.at[:, slow_idx].set(paged(v), mode="drop"))


def admit_pages_stacked_desc(cfg: TieredConfig, sts: TieredState, seq,
                             length, n_pages: int):
    """``admit_pages_stacked`` that also returns the install descriptors
    ``(state, pdesc)`` — the flight recorder stamps its install (and any
    admission-triggered eviction) events from them."""
    L = sts.fast_k.shape[0]
    st0 = _layer0(sts)
    seq = jnp.asarray(seq, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    j = jnp.arange(int(n_pages), dtype=jnp.int32)
    en = (j * cfg.page_tokens < length) & (j < cfg.max_pages_per_seq)
    ids = logical_page(cfg, seq, jnp.clip(j, 0, cfg.max_pages_per_seq - 1))

    def body(s, args):
        pid, e = args
        return _migrate_one_desc(cfg, s, pid, e, apply_pools=False)

    st0, pdesc = jax.lax.scan(body, st0, (ids, en))
    st0 = _tr_replace(st0, pol_track.record(cfg.pol, _tr_view(cfg, st0), ids,
                                            now=_now(cfg, st0), enable=en))
    pools = _replay_descs(cfg, _stacked_pools(sts), None, pdesc)
    return _restack(st0, pools, L), pdesc


def admit_pages_stacked(cfg: TieredConfig, sts: TieredState, seq, length,
                        n_pages: int) -> TieredState:
    """Stacked ``admit_pages``: the promotion scan runs once on layer-0
    metadata, the install copies replay over the stack."""
    sts, _ = admit_pages_stacked_desc(cfg, sts, seq, length, n_pages)
    return sts
