"""Pluggable KV-cache backends for the full-model decode loop.

The decode stack (``transformer.decode_step`` -> ``_block_decode`` ->
``attention.block_decode_attention``) speaks to its KV storage only
through the ``KVBackend`` protocol: per layer, ``append`` one token's
K/V at each lane's position, then ``attend`` a query against everything
stored so far.  Two implementations:

  DenseBackend   today's contiguous ``DecodeState`` caches
                 ([L, B, max_len, KV, hd] per layer under the layer
                 scan), bit-for-bit the pre-refactor numerics;
  TieredBackend  one Trimma-managed two-tier store per attention layer
                 (``tiered.kvcache.TieredState`` stacked on a leading
                 layer axis, sliced by the same layer scan) — each decode
                 step routes its append once (``begin_step``), runs one
                 fused append+attend kernel per layer (``append_attend``)
                 and persists all layers' new rows in four stacked
                 scatters (``end_step``); ``maintain`` / ``release`` run
                 the migration scheduler and lane recycling natively on
                 the [L, ...] stack (plan once, replay copies).

The translation must be invisible to the math: for the same token
stream at the same (per-lane, ragged) positions the two backends
produce bit-identical logits — tests/test_engine.py pins it under every
policy preset.

``pos`` is per-lane everywhere ([B] int32; scalars broadcast): lanes
decode at independent positions, so continuous batching never waits for
the batch to align.  A negative position marks an idle lane — both
backends drop its append and mask its read to nothing.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Protocol

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig

from . import attention as attn


class KVBackend(Protocol):
    """Per-layer KV-cache interface consumed by the decode layer scan.

    ``cache`` is one layer's slice of ``DecodeState.caches`` (the scan
    hands each layer its own slice); its concrete pytree type belongs to
    the backend.  Both methods must be pure and jit-able.
    """

    def init_state(self, batch: int, max_len: int):
        """Fresh ``DecodeState`` (``pos`` [B] int32 zeros, layer-stacked
        caches)."""
        ...

    def append(self, cache, k, v, pos, *, ring: bool = False):
        """Write one token's K/V per lane.  k, v [B, KV, hd] (post-RoPE);
        pos [B].  Lanes with ``pos < 0`` (idle) or past capacity write
        nothing.  Returns the updated cache slice."""
        ...

    def attend(self, cache, q, pos, *, window=0, ring: bool = False):
        """q [B, KV, G, hd], pos [B] -> (out [B, KV, G, hd], cache).
        Attends keys at positions <= pos per lane (SWA-masked when
        ``window`` > 0); may update the cache slice (the tiered backend
        records hotness and fills its device table)."""
        ...


def _host_num(v):
    """Device scalar -> concrete Python number: ints stay exact ints
    (the legacy counter contract), non-integral gauges (the derived
    ratio metrics) keep their float value."""
    f = float(v)
    return int(f) if f.is_integer() else f


# ---------------------------------------------------------------------------
# dense: the contiguous per-layer cache (pre-refactor numerics)
# ---------------------------------------------------------------------------

class DenseBackend:
    """Contiguous [B, max_len, KV, hd] caches per layer — the default.

    ``append``/``attend`` reproduce the fused pre-refactor
    ``decode_self_attention`` bit for bit (the scatter writes the same
    values the dynamic-update-slice wrote; the per-lane mask rows are
    the old shared-position mask when all lanes agree).  The cache slice
    is a dict holding at least ``{"k", "v"}``; extra keys (hybrid SSM
    state) pass through untouched.
    """

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    def init_state(self, batch: int, max_len: int):
        from . import transformer
        return transformer.init_decode_state(self.cfg, batch, max_len)

    def is_ring(self, cache) -> bool:
        sw = self.cfg.sliding_window
        return sw > 0 and cache["k"].shape[1] <= sw

    def append(self, cache, k, v, pos, *, ring: bool = False):
        ck, cv = cache["k"], cache["v"]
        B, S = ck.shape[:2]
        write = pos % S if ring else pos
        # idle (pos < 0) and past-capacity lanes route to an OOB sentinel
        # (traced negative indices wrap in JAX — they must be remapped)
        write = jnp.where((pos >= 0) & (write >= 0) & (write < S), write, S)
        lane = jnp.arange(B)
        ck = ck.at[lane, write].set(k.astype(ck.dtype), mode="drop")
        cv = cv.at[lane, write].set(v.astype(cv.dtype), mode="drop")
        return {**cache, "k": ck, "v": cv}

    def attend(self, cache, q, pos, *, window=0, ring: bool = False):
        B, KV, G, hd = q.shape
        ck, cv = cache["k"], cache["v"]
        S = ck.shape[1]
        ki = jnp.arange(S)[None, :]
        pb = pos[:, None]
        window = jnp.asarray(window, jnp.int32)
        if ring:
            # idle lanes (pb < 0) fall out via abs_pos < 0
            abs_pos = pb - ((pb - ki) % S)
            ok = (abs_pos >= 0) & ((window == 0) | (abs_pos > pb - window))
        else:
            ok = (ki <= pb) & ((window == 0) | (ki > pb - window))
        mask = jnp.where(ok, 0.0, attn.NEG_INF).astype(jnp.float32)
        out = attn._sdpa(q.reshape(B, 1, KV * G, hd), ck.astype(q.dtype),
                         cv.astype(q.dtype), mask[:, None, None, None, :])
        return out.reshape(B, KV, G, hd), cache

    # engine hooks: nothing to migrate or recycle in a dense cache — the
    # per-lane position mask makes a refilled lane's stale rows invisible
    def maintain(self, state):
        return state

    def release(self, state, lane):
        return state

    def write_prefill(self, state, lane, k_layers, v_layers, length):
        """Install a prompt's K/V into one lane: k/v [L, P, KV, hd]
        (post-RoPE rows 0..P-1; only rows < ``length`` are real — later
        rows are pad garbage the position mask hides until the decode
        appends overwrite them).  Sets ``pos[lane] = length``."""
        c = state.caches
        P = k_layers.shape[1]
        ck = c["k"].at[:, lane, :P].set(k_layers.astype(c["k"].dtype))
        cv = c["v"].at[:, lane, :P].set(v_layers.astype(c["v"].dtype))
        return state._replace(pos=state.pos.at[lane].set(length),
                              caches={**c, "k": ck, "v": cv})

    def write_prefill_chunk(self, state, lane, k_layers, v_layers, start,
                            length):
        """Chunked prompt ingest: install rows [start, start + C) of one
        lane's prompt K/V (k/v [L, C, KV, hd]; ``start``/``lane`` traced).
        ``pos`` is untouched — the scheduler sets it when the last chunk
        lands (the lane stays parked at pos = -1 until then)."""
        c = state.caches
        lane = jnp.asarray(lane, jnp.int32)
        start = jnp.asarray(start, jnp.int32)
        idx = (jnp.int32(0), lane, start, jnp.int32(0), jnp.int32(0))
        ck = jax.lax.dynamic_update_slice(
            c["k"], k_layers[:, None].astype(c["k"].dtype), idx)
        cv = jax.lax.dynamic_update_slice(
            c["v"], v_layers[:, None].astype(c["v"].dtype), idx)
        return state._replace(caches={**c, "k": ck, "v": cv})


# ---------------------------------------------------------------------------
# tiered: one Trimma two-tier store per attention layer
# ---------------------------------------------------------------------------

class PoolOperands(NamedTuple):
    """The four pool arrays of a (stacked) tiered store — the layer
    scan's read-only operand view (``TieredBackend.scan_operands``)."""
    fast_k: Any
    fast_v: Any
    slow_k: Any
    slow_v: Any


class TieredBackend:
    """Per-layer ``TieredState`` stacked on a leading layer axis.

    The decode hot path is the fused begin/attend/end triple (DESIGN.md
    §11): ``begin_step`` routes this step's append and advances all
    metadata ONCE on layer 0 (every layer shares it — metadata is
    layer-uniform by construction), the layer scan calls
    ``append_attend`` (one fused Pallas kernel that overlays the new K/V
    row onto its routed tier and attends in the same pass), and
    ``end_step`` persists every layer's new rows with four stacked
    scatters.  ``transformer.decode_step`` dispatches on the presence of
    ``begin_step``.  The legacy per-layer ``append``/``attend`` pair is
    kept for direct store-level use and tests.

    ``maintain``/``release``/``write_prefill`` run the layer-stacked
    kvcache ops: one plan / one metadata pass on layer 0, pool copies
    replayed over the [L, ...] stack — no ``jax.vmap`` over L.
    ``plan_maintain``/``apply_maintain`` split the maintenance pass so
    the engine can double-buffer the apply against the next decode step.

    Only plain-KV decoder families qualify (no sliding window, no
    recurrent side state): the paged kernel has no window semantics and
    the tiers hold nothing but KV pages.
    """

    def __init__(self, cfg: ArchConfig, batch: int, max_len: int, *,
                 page_tokens: int = 16, fast_data_slots: int = 16,
                 policy=None, impl: str = "auto", walk_impl: str = "auto",
                 gather_impl: str = "auto"):
        from repro.tiered import kvcache as tk
        if cfg.family not in ("dense", "moe"):
            raise NotImplementedError(
                f"TieredBackend supports plain-KV decoder families; "
                f"got family={cfg.family!r}")
        if cfg.sliding_window:
            raise NotImplementedError(
                "TieredBackend has no sliding-window semantics "
                "(the paged kernel reads every live page)")
        self.cfg = cfg
        self.impl = impl
        self.n_layers = cfg.n_layers
        self.tcfg = tk.TieredConfig(
            n_seqs=batch,
            max_pages_per_seq=-(-max_len // page_tokens),
            page_tokens=page_tokens,
            n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.hd,
            fast_data_slots=fast_data_slots,
            policy=policy,
            dtype=cfg.dtype,
            walk_impl=walk_impl,
            gather_impl=gather_impl,
        )
        self._seq_ids = jnp.arange(batch, dtype=jnp.int32)

    def init_state(self, batch: int, max_len: int):
        from . import transformer
        from repro.tiered import kvcache as tk
        assert batch == self.tcfg.n_seqs
        one = tk.init_state(self.tcfg)
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (self.n_layers,) + x.shape), one)
        return transformer.DecodeState(
            jnp.zeros((batch,), jnp.int32), stacked)

    def is_ring(self, cache) -> bool:
        return False

    def append(self, cache, k, v, pos, *, ring: bool = False):
        if ring:
            raise NotImplementedError(
                "TieredBackend cannot ring-wrap appends: a paged store "
                "has no modular position axis")
        from repro.tiered import kvcache as tk
        return tk.append_token(self.tcfg, cache, self._seq_ids, k, v, pos)

    def attend(self, cache, q, pos, *, window=0, ring: bool = False):
        if ring:
            raise NotImplementedError(
                "TieredBackend cannot ring-read: a paged store has no "
                "modular position axis")
        try:
            window = int(window)
        except Exception as e:                      # traced window value
            raise NotImplementedError(
                "TieredBackend has no sliding-window semantics "
                "(the paged kernel reads every live page)") from e
        if window != 0:
            raise NotImplementedError(
                "TieredBackend has no sliding-window semantics "
                "(the paged kernel reads every live page)")
        from repro.serve import tiered as srv
        # idle lanes (pos < 0) read nothing: seq_lens 0 masks every page
        seq_lens = jnp.maximum(pos + 1, 0)
        return srv.attend(self.tcfg, cache, q, seq_lens, impl=self.impl)

    # -- fused decode step: one metadata pass, one kernel per layer -----

    def begin_step(self, caches, pos, n_pages: int | None = None):
        """Pre-scan half of the fused decode step: route this step's
        one-token append and advance ALL per-step metadata once on
        layer 0 (write touches, policy-tracker records, device-table
        hits), then broadcast — every layer sees identical metadata, so
        one pass serves all L.  Returns (caches, aux); ``aux`` carries
        the routing (fast/slow row + in-page offset per lane) and the
        translation view (leaf entries + slot owners) that
        ``append_attend``/``end_step`` consume.  Pool bytes do not move
        here.

        ``n_pages`` is the static live-page bucket (DESIGN.md §11): the
        attended leaf entries are sliced to that page prefix, so every
        layer's fused read scans ``n_pages * page_tokens`` positions
        instead of ``max_len``.  The caller guarantees the bucket covers
        every live position plus the appended token (the engine tracks a
        host-side position mirror and re-buckets on power-of-two
        growth); the truncated tail is fully masked, so logits stay
        bit-identical to the full-width read."""
        from repro.serve import tiered as srv
        from repro.tiered import kvcache as tk
        cfg = self.tcfg
        L = self.n_layers
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (cfg.n_seqs,))
        st_base = tk._layer0(caches)
        st0 = st_base
        entries = st0.leaf_table[:cfg.n_logical].reshape(
            cfg.n_seqs, cfg.max_pages_per_seq)
        if n_pages is not None and n_pages < cfg.max_pages_per_seq:
            entries = entries[:, :n_pages]
        aux = {"entries": entries}
        ok, ids, fast_idx, slow_idx, off = tk.append_routing(
            cfg, st0, self._seq_ids, pos, 1)
        aux.update(fast_idx=fast_idx[:, 0], slow_idx=slow_idx[:, 0],
                   off=off[:, 0])
        st0 = st0._replace(wtouch=st0.wtouch.at[
            jnp.where(ok, ids, cfg.n_logical)].add(1, mode="drop"))
        if cfg.pol.write_weight > 1:    # write-aware: appends heat pages
            st0 = tk.record_touches(cfg, st0, ids.reshape(-1),
                                    ok.reshape(-1))
        # read-side accounting, amortised to one record for the step:
        # every live page is touched once, and counts either one cold
        # translation (first read, dev row cached here) or one
        # dev_table hit (tk.record_reads — lookup()'s cold/steady split)
        lv = srv.live_mask(cfg, jnp.where(pos >= 0, pos + 1, 0))
        st0 = tk.record_reads(cfg, st0,
                              srv.page_table(cfg, st0).reshape(-1),
                              lv.reshape(-1))
        st0 = tk.record_touches(cfg, st0,
                                srv.page_table(cfg, st0).reshape(-1),
                                lv.reshape(-1))
        # the pages each layer's fused read fetches; L layers sum them
        st0 = srv.record_fetches(cfg, st0, pos, 1, entries.shape[1])
        # re-broadcast ONLY the metadata this pass actually changed
        # (identity against the layer-0 slice finds them); untouched
        # fields keep the input's stacked arrays, so the hot path never
        # pays a slice+broadcast round-trip for pass-through metadata —
        # bit-identical to a full _restack by the layer-uniform invariant
        upd = {f: jnp.broadcast_to(v, (L,) + v.shape)
               for f, v in zip(type(st0)._fields, st0)
               if v is not getattr(st_base, f)}
        return caches._replace(**upd), aux

    def scan_operands(self, caches):
        """The layer scan's read-only view of the stacked store: just the
        four pool arrays.  The fused body only ever touches pool bytes —
        routing and translation ride in ``aux``, metadata lives outside
        the scan — so slicing the full ``TieredState`` (27 leaves) per
        layer would spend a dynamic-slice thunk on 23 arrays the body
        never reads.  ``end_step`` still persists into the full
        ``caches``; this view exists purely to keep the scan lean."""
        return PoolOperands(caches.fast_k, caches.fast_v,
                            caches.slow_k, caches.slow_v)

    def append_attend(self, cache, q, k1, v1, pos, aux):
        """One layer's fused append+attend: q [B, KV, G, hd], k1/v1
        [B, KV, hd] -> out [B, KV, G, hd].  ``cache`` is one layer's
        ``scan_operands`` slice (pools only).  The kernel overlays the
        new row onto its routed tier and attends in the same pass; the
        cache slice is read-only (``end_step`` persists the rows)."""
        from repro.kernels.paged_attention.ops import \
            paged_attention_fused_op
        out = paged_attention_fused_op(
            q[:, None], cache.fast_k, cache.fast_v, cache.slow_k,
            cache.slow_v, aux["entries"],
            k1[:, None], v1[:, None], pos, impl=self.impl)
        return out[:, 0]

    def end_step(self, caches, knv, pos, aux):
        """Post-scan half: persist every layer's new K/V row with four
        stacked scatters (knv = (k [L, B, KV, hd], v [L, B, KV, hd]),
        the layer scan's stacked outputs).  Routing was fixed by
        ``begin_step`` — appends never move pages, so the pre-kernel
        leaf entries still name each row's tier."""
        k_all, v_all = knv
        L = self.n_layers
        li = jnp.arange(L, dtype=jnp.int32)[:, None]
        fi, si, off = (aux["fast_idx"][None], aux["slow_idx"][None],
                       aux["off"][None])
        dt = caches.fast_k.dtype
        return caches._replace(
            fast_k=caches.fast_k.at[li, fi, :, off].set(
                k_all.astype(dt), mode="drop"),
            fast_v=caches.fast_v.at[li, fi, :, off].set(
                v_all.astype(dt), mode="drop"),
            slow_k=caches.slow_k.at[li, si, :, off].set(
                k_all.astype(dt), mode="drop"),
            slow_v=caches.slow_v.at[li, si, :, off].set(
                v_all.astype(dt), mode="drop"))

    # -- maintenance & lane lifecycle: layer-stacked, plan/apply split --

    def maintain(self, state, max_moves: int | None = None):
        """One synchronous migration-scheduler pass: plan once on
        layer-0 metadata, replay the pool copies over the [L, ...]
        stack (``run_scheduler_stacked``) — bounded promotion + demotion
        + epoch decay, off the critical path."""
        from repro.tiered import kvcache as tk
        return state._replace(caches=tk.run_scheduler_stacked(
            self.tcfg, state.caches, max_moves=max_moves))

    def plan_maintain(self, state, max_moves: int | None = None):
        """Score + plan only (no state change) — the engine overlaps the
        matching ``apply_maintain`` with the next decode step."""
        from repro.tiered import kvcache as tk
        return tk.plan_maintenance(self.tcfg, state.caches,
                                   max_moves=max_moves)

    def apply_maintain(self, state, plan):
        """Apply a previously computed maintenance plan (metadata once on
        layer 0, copies replayed over the stack).  Safe one step late:
        write-through keeps both tiers' bytes fresh, so a move planned
        against last step's scores still copies current data."""
        from repro.tiered import kvcache as tk
        return state._replace(caches=tk.apply_maintenance_stacked(
            self.tcfg, state.caches, plan))

    def apply_maintain_desc(self, state, plan):
        """``apply_maintain`` that also returns the (ddesc, pdesc) move
        descriptors — what each plan entry ACTUALLY did — so the flight
        recorder (obs/flight, DESIGN.md §12) can stamp promote / demote
        / evict events from the ground truth.  Bit-identical state to
        ``apply_maintain`` (same pass, descriptors tee'd out)."""
        from repro.tiered import kvcache as tk
        caches, ddesc, pdesc = tk.apply_maintenance_stacked_desc(
            self.tcfg, state.caches, plan)
        return state._replace(caches=caches), ddesc, pdesc

    def release(self, state, lane):
        """Drop one lane's pages from every layer's metadata (lane
        recycle; ``pos`` untouched — the caller re-prefills).  Pure
        metadata: layer 0 releases, the result broadcasts."""
        from repro.tiered import kvcache as tk
        return state._replace(caches=tk.release_seq_stacked(
            self.tcfg, state.caches, lane))

    def write_prefill(self, state, lane, k_layers, v_layers, length):
        """Batched prompt ingest: all layers' prompt K/V pages land in
        the slow pool as one scatter per pool
        (``tiered.kvcache.prefill_tokens_stacked``).  Precondition: the
        lane was released (identity mapping) — the engine releases every
        lane before prefilling it."""
        from repro.tiered import kvcache as tk
        caches = tk.prefill_tokens_stacked(self.tcfg, state.caches, lane,
                                           k_layers, v_layers, length)
        return state._replace(pos=state.pos.at[lane].set(length),
                              caches=caches)

    def write_prefill_chunk(self, state, lane, k_layers, v_layers, start,
                            length):
        """Chunked prompt ingest, one page-aligned chunk: rows
        [start, start + C) of each layer's prompt K/V land in the page's
        *current* tier (``prefill_chunk_stacked`` routes resident pages
        to their fast copy — coherent with direct-to-fast admission).
        ``pos`` untouched; the scheduler sets it when the final chunk
        lands."""
        from repro.tiered import kvcache as tk
        return state._replace(caches=tk.prefill_chunk_stacked(
            self.tcfg, state.caches, lane, k_layers, v_layers, start,
            length))

    def admit_prefix(self, state, lane, length, n_pages: int):
        """Direct-to-fast admission at ingest: promote the first
        ``n_pages`` prompt pages of ``lane`` into every layer's fast
        pool now (``admit_pages_stacked`` — metadata once, install
        copies replayed over the stack), instead of waiting for decode
        touches to heat them."""
        from repro.tiered import kvcache as tk
        return state._replace(caches=tk.admit_pages_stacked(
            self.tcfg, state.caches, lane, length, n_pages))

    def admit_prefix_desc(self, state, lane, length, n_pages: int):
        """``admit_prefix`` that also returns the install descriptors
        (flight-recorder install / admission-eviction events)."""
        from repro.tiered import kvcache as tk
        caches, pdesc = tk.admit_pages_stacked_desc(
            self.tcfg, state.caches, lane, length, n_pages)
        return state._replace(caches=caches), pdesc

    def maintain_tenants(self, state, lane_tenant, pols, quotas):
        """Multi-tenant maintenance: one stacked
        ``run_scheduler_tenants`` pass (always synchronous — a tenant
        map can go stale across a deferred apply).  ``lane_tenant`` [B]
        int32 maps each lane to its tenant (< 0 == idle — those lanes'
        pages move for nobody); ``pols``/``quotas`` are the static
        per-tenant policy + fast-slot partition (serve/sched/qos builds
        them)."""
        from repro.tiered import kvcache as tk
        page_tenant = jnp.repeat(jnp.asarray(lane_tenant, jnp.int32),
                                 self.tcfg.max_pages_per_seq)
        return state._replace(caches=tk.run_scheduler_tenants_stacked(
            self.tcfg, state.caches, page_tenant, pols, quotas))

    def metrics(self, state) -> dict:
        """Canonical telemetry view (DESIGN.md §10): the obs tap summed
        over the layer axis, concrete Python numbers (counters stay
        ints; the derived ratio gauges keep their fractional value)."""
        from repro.serve import tiered as srv
        return {k: _host_num(v)
                for k, v in srv.metrics(self.tcfg, state.caches).items()}

    def counters(self, state) -> dict:
        """Aggregate per-layer counters (summed over the layer axis) under
        the legacy short keys — re-derived from the canonical view."""
        from repro.obs.metrics import legacy_counters
        return legacy_counters(self.metrics(state))


def make_backend(cfg: ArchConfig, kind: str, batch: int, max_len: int,
                 **tiered_kw: Any) -> KVBackend:
    """Backend factory for the serving engine: ``kind`` is "dense" or
    "tiered"; ``tiered_kw`` forwards geometry/policy knobs to
    ``TieredBackend``."""
    if kind == "dense":
        return DenseBackend(cfg)
    if kind == "tiered":
        return TieredBackend(cfg, batch, max_len, **tiered_kw)
    raise ValueError(f"unknown KV backend {kind!r} (want dense|tiered)")
