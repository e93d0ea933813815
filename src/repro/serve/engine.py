"""Batched serving engine: request scheduling + full-model decode loop.

Production concerns covered here:
  * continuous batching: a fixed-width decode batch; finished/empty lanes
    are refilled from the request queue each step (no head-of-line block);
  * pluggable request scheduling (serve/sched, DESIGN.md §9): the engine
    owns the jitted primitives and delegates refill / prefill pacing /
    admission to a ``Scheduler`` — ``GreedyScheduler`` (default) keeps
    the PR 4 wave-refill behaviour bit for bit; ``ChunkedScheduler`` adds
    chunked prefill, multi-tenant QoS admission and direct-to-fast
    ingest;
  * real prefill: a refilled lane's prompt runs through ``forward``
    (collect_cache) once and its K/V land in the lane's cache — dense
    rows or tiered slow-pool pages (``tiered.kvcache.prefill_tokens``)
    — so every prompt token conditions generation, at prefill cost
    O(prompt) instead of O(prompt) decode steps;
  * ragged lanes: ``DecodeState.pos`` is per-lane, so each lane decodes
    at its own position; idle lanes sit at pos = -1 and neither write
    nor read (nor heat the tiered hotness tracker);
  * straggler mitigation: requests are bucketed by remaining length so one
    long sequence cannot pin the whole batch — the bucket anchors to the
    first request of a batch wave and resets when the engine drains, so
    it tracks the wave instead of whatever refilled last;
  * tiered KV serving: ``EngineConfig(backend="tiered")`` decodes the
    full transformer through one Trimma-managed two-tier store per
    attention layer (``models.kv_backend.TieredBackend``), driving
    step -> maintain -> release: the jitted zero-copy decode step per
    token, the bounded migration scheduler between steps, and a batched
    metadata release the moment a lane's request finishes — bit-identical
    logits to the dense backend (tests/test_engine.py pins it under every
    policy preset).

``TieredServer`` below is the single-store driver for the same loop
(used by the microbenchmarks and the kernel-level tests); ``Engine``
composes the full model on top of it through the backend protocol.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import decode_step, forward
from repro.models.kv_backend import TieredBackend, make_backend
from repro.obs import NULL_TRACER, MetricsHub, ObsConfig, StepTracer
from repro.obs import flight as obs_flight
from repro.obs import metrics as obs_metrics
from repro.obs.registry import MetricSpec, register
from repro.obs.slo import SLOMonitor
from repro.obs.trace import profiler_trace
from repro.serve.decode import make_tiered_decode_step

# canonical serving-engine metrics (DESIGN.md §10).  The trimma_* families
# are declared by the modules that own them (core/remap, core/policy,
# tiered/kvcache); these are the engine loop's own books.
register(
    MetricSpec("engine_steps_total", "counter",
               "decode steps executed"),
    MetricSpec("engine_tokens_total", "counter",
               "tokens harvested from decoding lanes"),
    MetricSpec("engine_finished_requests_total", "counter",
               "requests fully decoded"),
    MetricSpec("engine_releases_total", "counter",
               "lane metadata recycles (tiered release passes)"),
    MetricSpec("engine_maintain_overlap", "counter",
               "maintenance applies overlapped with the next decode step "
               "(double-buffered plan/apply split, DESIGN.md §11)"),
    MetricSpec("engine_queue_depth", "gauge",
               "requests waiting in the scheduler queue"),
    MetricSpec("engine_active_lanes", "gauge",
               "lanes holding a live request"),
    MetricSpec("engine_translated_pages_per_step", "gauge",
               "metadata-engine translations per decode step (live pages "
               "that missed the cached device table)"),
    MetricSpec("engine_request_latency_ms", "gauge",
               "request latency percentiles "
               '(labels: tenant, stat in latency|ttft|queue_wait, '
               "quantile)", unit="ms"),
    MetricSpec("engine_token_latency_ms", "histogram",
               "inter-token latency (log2 buckets from 0.25 ms)",
               unit="ms"),
)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # [S] int32
    max_new: int
    tenant_id: str = "default"    # QoS tenant (serve/sched/qos)
    arrived: float = 0.0          # enqueue time (stamped by submit)
    admitted_at: float = 0.0      # lane assignment time
    first_token_at: float = 0.0   # first decoded token
    done_at: float = 0.0          # wall time the last token was decoded
    tokens: list = dataclasses.field(default_factory=list)
    token_times: list = dataclasses.field(default_factory=list)
    done: bool = False

    @property
    def latency(self) -> float:
        """End-to-end latency from the request's OWN enqueue time — never
        from a batch-wave anchor (requests admitted mid-wave measure
        their own span; tests/test_sched.py pins it)."""
        return self.done_at - self.arrived

    @property
    def ttft(self) -> float:
        """Time to first token, from enqueue."""
        return self.first_token_at - self.arrived

    @property
    def queue_wait(self) -> float:
        return self.admitted_at - self.arrived


@dataclasses.dataclass
class EngineConfig:
    batch: int = 4
    max_len: int = 256
    bucket: int = 64              # straggler bucketing granularity
    backend: str = "dense"        # KV backend: "dense" | "tiered"
    # tiered-backend geometry / policy (ignored for dense)
    page_tokens: int = 16
    fast_data_slots: int = 16
    policy: str | None = None     # core/policy preset name
    maintain_every: int = 4       # migration-scheduler cadence (steps)
    overlap_maintain: bool = True  # double-buffer the maintenance pass:
                                  # plan at the hook, apply the pool moves
                                  # against the NEXT decode step (multi-
                                  # tenant maintenance stays synchronous)
    page_bucket: bool = True      # tiered fused path: attend only the
                                  # power-of-two live-page prefix covering
                                  # every lane's position (DESIGN.md §11)
                                  # instead of the full provisioned
                                  # max_len — bit-identical logits, cost
                                  # scales with live context
    # request scheduling (serve/sched, DESIGN.md §9)
    scheduler: str = "greedy"     # "greedy" (PR 4 bit-for-bit) | "chunked"
                                  # ("wave" = deprecated greedy alias)
    prefill_chunk: int = 0        # chunked: prompt tokens ingested per
                                  # engine step (0 = one-shot prefill)
    admit_pages: int = 2          # direct-to-fast pages per ingest when a
                                  # tenant's policy decider is on-demand
    tenants: tuple = ()           # TenantConfig per tenant (empty: one
                                  # default tenant)
    starvation_bound: int = 8     # QoS: max admission skips in a row
    # observability (DESIGN.md §10): None = no metrics and no JSON trace
    # (the loop's spans still reach an active jax.profiler session); an
    # ObsConfig turns on periodic MetricsHub samples and, when paths are
    # set, the Prometheus exposition / JSONL series / Perfetto trace
    # written at drain
    obs: ObsConfig | None = None
    # page-lifecycle flight recorder (obs/flight, DESIGN.md §12): a
    # FlightConfig turns on the in-graph event ring (tiered backend
    # only).  Independent of ``obs`` — the ring threads beside the
    # donated decode state, so recorder-on keeps donation (and logits)
    # untouched; when a hub exists too, the drained analytics export as
    # trimma_flight_* metrics
    flight: obs_flight.FlightConfig | None = None
    # per-tenant SLO targets (obs/slo): SLOConfig tuple; the engine
    # books every finished request and exports engine_slo_* burn rates
    slos: tuple = ()


class TieredServer:
    """Continuous tiered-KV decode driver: the serving glue between lane
    scheduling and ONE Trimma-managed two-tier KV store (a single
    attention layer's worth; ``Engine`` stacks one per layer through
    ``TieredBackend``).

    One jitted zero-copy step per token (``serve.decode
    .make_tiered_decode_step``: append -> cached-table lookup ->
    split-pool attention; ``pos`` may be a per-lane vector), ``maintain``
    between steps (bounded promotion/demotion, off the critical path),
    ``release`` when a lane's request finishes and the lane is recycled —
    the freed pages drop out of the iRT/iRC/device table in one batched
    pass, so a dead request never occupies fast slots or metadata.
    """

    def __init__(self, tcfg, *, path: str = "zero_copy",
                 impl: str = "auto"):
        from repro.serve import tiered as srv
        from repro.tiered import kvcache as tk
        self.cfg = tcfg
        self.state = tk.init_state(tcfg)
        self._step = make_tiered_decode_step(tcfg, path=path, impl=impl)
        self._maintain = jax.jit(lambda s: srv.maintain(tcfg, s))
        self._release = jax.jit(lambda s, i: srv.release(tcfg, s, i))
        self.steps = 0

    def step(self, q, k_new, v_new, pos):
        """One decode token for every lane (``pos`` scalar or [B]);
        returns [B, KV, G, hd]."""
        out, self.state = self._step(self.state, q, k_new, v_new, pos)
        self.steps += 1
        return out

    def maintain(self):
        self.state = self._maintain(self.state)

    def release(self, seq: int):
        self.state = self._release(self.state, jnp.int32(seq))

    @property
    def metrics(self) -> dict:
        """Canonical telemetry view of the store (obs tap, DESIGN.md §10).
        Counters stay exact ints; the derived ratio gauges (identity
        entry ratio, leaf occupancy) keep their fractional value."""
        from repro.models.kv_backend import _host_num
        from repro.serve import tiered as srv
        return {k: _host_num(v)
                for k, v in srv.metrics(self.cfg, self.state).items()}

    @property
    def counters(self) -> dict:
        """Legacy short-key counters, re-derived from the canonical view."""
        return obs_metrics.legacy_counters(self.metrics)


_PREFILL_FAMILIES = ("dense", "moe")


def padded_len(ctx: int, max_len: int) -> int:
    """Prefill padding rule shared by one-shot AND chunked prefill: the
    context pads to a power of two (few jit keys), clamped to the cache
    capacity.  The chunked scheduler MUST size its key buffers with this
    exact P — the chunked==one-shot bit-identicality contract hinges on
    both paths reducing over the same padded key length."""
    return min(1 << (max(int(ctx), 1) - 1).bit_length(), max_len)


class Engine:
    """Greedy-decode serving engine over a fixed-width batch.

    ``ec.backend`` selects the KV storage for the full model: "dense"
    (default, contiguous caches) or "tiered" (per-layer Trimma stores;
    same logits bit for bit).  A pre-built backend instance may be
    injected via ``backend=`` for custom geometry/policy.

    ``ec.scheduler`` selects the request scheduler (serve/sched,
    DESIGN.md §9): the engine owns the jitted primitives (decode step,
    prefill, chunked-prefill forward, maintain, release) and delegates
    every refill / prefill-pacing / admission decision to it.  A custom
    ``Scheduler`` instance may be injected via ``scheduler=``.
    """

    def __init__(self, cfg: ArchConfig, params, ec: EngineConfig,
                 backend=None, scheduler=None):
        if cfg.family not in _PREFILL_FAMILIES:
            raise NotImplementedError(
                f"Engine prefill supports KV-cache families "
                f"{_PREFILL_FAMILIES}; got {cfg.family!r}")
        from repro.models.transformer import _ring_cache_len
        if _ring_cache_len(cfg, ec.max_len) != ec.max_len:
            raise NotImplementedError(
                "Engine prefill writes prompt rows linearly and does not "
                "support the ring-buffer window cache "
                "(REPRO_WINDOW_CACHE=1)")
        self.cfg, self.params, self.ec = cfg, params, ec
        if backend is not None:
            self.backend = backend
        else:
            kw = {}
            if ec.backend == "tiered":
                kw = dict(page_tokens=ec.page_tokens,
                          fast_data_slots=ec.fast_data_slots)
                if ec.policy is not None:
                    from repro.core.policy import get_policy
                    kw["policy"] = get_policy(ec.policy)
            self.backend = make_backend(cfg, ec.backend, ec.batch,
                                        ec.max_len, **kw)
        self._tiered = isinstance(self.backend, TieredBackend)
        # decode-step jits, keyed by the live-page attention bucket
        # (None = full provisioned width; dense always uses None)
        self._step_fns: dict[int | None, Callable] = {}
        # steady-state serving donates the KV state into the step: the
        # loop threads it linearly, so the pre-step buffers are dead the
        # moment the step returns and XLA updates pools in place instead
        # of copying the whole store every token.  Observability opts
        # out — its samples stash references into the state across steps
        # (the batched drain tap would read donated buffers)
        self._donate = ec.obs is None
        # every program the loop dispatches is a named function: the
        # profiler calls its module ``jit_<name>``, so a device trace sums
        # time per program (DESIGN.md §10)
        if self._tiered:
            backend = self.backend

            def engine_maintain(s):
                return backend.maintain(s)

            def engine_release(s, lane):
                return backend.release(s, lane)

            def engine_maintain_plan(s):
                return backend.plan_maintain(s)

            def engine_maintain_apply(s, p):
                return backend.apply_maintain(s, p)

            self._maintain = jax.jit(engine_maintain)
            self._release = jax.jit(engine_release)
            self._plan_fn = jax.jit(engine_maintain_plan)
            self._apply_fn = jax.jit(engine_maintain_apply)
        self._pending_plan = None      # double-buffered maintain (§11)
        self.maintain_overlaps = 0
        self._prefill_fns: dict[int, Callable] = {}
        self._chunk_fns: dict[tuple, Callable] = {}
        self._write_chunk_fns: dict[int, Callable] = {}
        self._admit_fns: dict[int, Callable] = {}

        def engine_set_pos(s, i, v):
            return s._replace(pos=s.pos.at[i].set(v))

        def engine_park_idle(s, m):
            return s._replace(pos=jnp.where(m, -1, s.pos))

        self._set_pos = jax.jit(engine_set_pos)
        self._mask_idle = jax.jit(engine_park_idle)
        self.releases = 0
        self.steps = 0
        self._bw_log: list = []        # per-maintain counter snapshots
        from repro.serve.sched import make_scheduler
        self.scheduler = scheduler if scheduler is not None \
            else make_scheduler(ec)
        self.scheduler.bind(self)
        # observability (DESIGN.md §10): hub + JSON tracer only when
        # configured; NULL_TRACER sends the same span sites to the profiler
        # alone, so the hot loop stays branch-free.  A
        # sample inside the loop only stashes array references (tap_stash)
        # — the batched jitted tap turns ALL samples' counter reductions
        # into one compiled call + one transfer at drain
        self.hub: MetricsHub | None = \
            MetricsHub(ec.obs) if ec.obs is not None else None
        self.tracer = StepTracer() \
            if ec.obs is not None and ec.obs.trace_path else NULL_TRACER
        if self._tiered and ec.obs is not None:
            from repro.core.remap.irt import E
            from repro.serve import tiered as srv
            tcfg = self.backend.tcfg
            self._tap = jax.jit(lambda c: srv.metrics(tcfg, c))
            self._batch_tap = jax.jit(lambda taps: jax.vmap(
                lambda s: obs_metrics.stashed_metrics(
                    s, page_bytes=tcfg.page_bytes,
                    n_logical=tcfg.n_logical, fast_slots=tcfg.fast_slots,
                    leaf_entries=E))(
                jax.tree.map(lambda *xs: jnp.stack(xs), *taps)))
        self._pending_obs: list[dict] = []
        self._tokens_out = 0           # tokens harvested (engine_tokens_total)
        # optional per-step logits capture (set to [] before run()):
        # benchmarks/run.py's obs section uses it to assert metrics-on
        # decode stays bit-identical to metrics-off
        self.logits_log: list | None = None
        # flight recorder (obs/flight, DESIGN.md §12): the event ring is
        # its own pytree threaded through jitted record+mutate fns — the
        # donated decode step never sees it, so recorder-on changes no
        # jit key and no logits.  Tenant stamps come from a host-side
        # lane -> tenant-index mirror refreshed each loop iteration
        self._fl_cfg = ec.flight \
            if (ec.flight is not None and self._tiered) else None
        self._fl = None
        self._flight_cache: dict | None = None
        self._tenant_idx: dict[str, int] = {}
        for t in ec.tenants:
            self._tenant_idx.setdefault(getattr(t, "name", str(t)),
                                        len(self._tenant_idx))
        if self._fl_cfg is not None:
            self._fl = obs_flight.init(self._fl_cfg.capacity)
            self._lane_tenant_np = np.zeros((ec.batch,), np.int32)
            self._rec_apply_fn = jax.jit(self._make_rec_apply())
            self._rec_release_fn = jax.jit(self._make_rec_release())
        # per-tenant SLO burn-rate monitor (obs/slo)
        self.slo = SLOMonitor(ec.slos) if ec.slos else None
        # live endpoints (obs/http): needs the hub for /metrics
        self.obs_server = None
        if self.hub is not None and ec.obs.http_port is not None:
            from repro.obs.http import ObsServer
            self.obs_server = ObsServer(
                metrics_fn=self.hub.to_prometheus,
                health_fn=lambda: {"steps": self.steps,
                                   "tokens": self._tokens_out},
                state_fn=self.debug_state,
                host=ec.obs.http_host, port=ec.obs.http_port)

    # -- request intake / scheduling ------------------------------------

    def submit(self, req: Request):
        req.arrived = time.time()
        self.scheduler.submit(req)

    @property
    def queue(self):
        """The scheduler's queue view (greedy: the FIFO deque; chunked:
        a snapshot across tenant queues)."""
        return self.scheduler.queue

    @property
    def active_bucket(self):
        """The greedy scheduler's wave anchor (None for schedulers
        without straggler bucketing)."""
        return getattr(self.scheduler, "active_bucket", None)

    # -- scheduler-facing jitted primitives -------------------------------

    def _step_fn(self, n_pages: int | None) -> Callable:
        """The jitted full-model decode step, keyed by the live-page
        attention bucket (one retrace per power-of-two bucket — at most
        log2(max_pages_per_seq) keys over a run)."""
        if n_pages not in self._step_fns:
            cfg, backend = self.cfg, self.backend

            def engine_decode(p, s, t):
                return decode_step(cfg, p, s, t, backend=backend,
                                   n_pages=n_pages)

            self._step_fns[n_pages] = jax.jit(
                engine_decode, donate_argnums=(1,) if self._donate else ())
        return self._step_fns[n_pages]

    def _live_bucket(self, state) -> int | None:
        """Pick the live-page attention bucket for the next decode step
        (DESIGN.md §11): the smallest power-of-two page prefix covering
        every lane's append position.  A lane at pos p appends at index p
        and attends positions [0, p], so ``p // page_tokens + 1`` pages
        suffice; the power-of-two rounding keeps the jit key count at
        log2.  None (full provisioned width) when bucketing is off, the
        backend is dense, every lane is parked, or the bucket already
        spans the whole table.  ``state.pos`` here may be the output of
        the refill's programs (``set_pos``, ``park_idle``) or of the
        deferred maintenance apply, so this host read waits for them: it
        runs in the ``bucket`` span."""
        if not (self._tiered and self.ec.page_bucket):
            return None
        mx = int(np.asarray(state.pos).max())
        if mx < 0:
            return None
        tcfg = self.backend.tcfg
        need = mx // tcfg.page_tokens + 1
        bucket = 1 << (need - 1).bit_length()
        return None if bucket >= tcfg.max_pages_per_seq else bucket

    # -- flight recorder (obs/flight, DESIGN.md §12) ----------------------

    def _make_rec_apply(self):
        """Build the fused apply+record maintenance fn: applies a plan
        via the descriptor-returning stacked pass and appends one event
        per ACTUAL move — demotes, FIFO-victim evicts, promotes, forced
        metadata evicts, in that (deterministic) order.  Events stamp
        the step the plan was MADE at, so the overlapped apply records
        the same stream as the synchronous pass (the event-order parity
        test pins it); ``score`` stamps the page's tracker hotness at
        apply time (best-effort — overlap applies one step later, so it
        may differ from the sync stamp by that step's touches)."""
        backend = self.backend
        mpp = backend.tcfg.max_pages_per_seq

        def engine_maintain_apply_rec(state, plan, fl, step, lane_tenant):
            touch0 = state.caches.touch[0]
            state, ddesc, pdesc = backend.apply_maintain_desc(state, plan)

            def rec(fl, kind, cause, pages, en):
                lane = pages // mpp
                return obs_flight.record(
                    fl, kind, pages, en, step=step, lane=lane,
                    tenant=lane_tenant[lane], cause=cause,
                    score=touch0[pages])

            fl = rec(fl, obs_flight.K_DEMOTE, obs_flight.C_PLAN_DEMOTE,
                     ddesc["cb1_dst"], ddesc["cb1_en"])
            fl = rec(fl, obs_flight.K_EVICT, obs_flight.C_VICTIM,
                     pdesc["cb1_dst"], pdesc["cb1_en"])
            fl = rec(fl, obs_flight.K_PROMOTE, obs_flight.C_PLAN_PROMOTE,
                     pdesc["in_src"], pdesc["in_en"])
            fl = rec(fl, obs_flight.K_EVICT, obs_flight.C_FORCED,
                     pdesc["cb2_dst"], pdesc["cb2_en"])
            return state, fl

        return engine_maintain_apply_rec

    def _make_rec_release(self):
        """Build the fused record+release fn: one RELEASE event per
        page the lane still holds under Trimma metadata (resident leaf
        entries on layer 0 — metadata is layer-uniform), then the
        batched release itself."""
        backend = self.backend
        tcfg = backend.tcfg
        from repro.tiered.kvcache import INVALID
        mpp = tcfg.max_pages_per_seq

        def engine_release_rec(state, lane, fl, step, tenant):
            lt0 = state.caches.leaf_table[0]
            ids = lane * mpp + jnp.arange(mpp, dtype=jnp.int32)
            held = lt0[ids] != INVALID
            fl = obs_flight.record(
                fl, obs_flight.K_RELEASE, ids, held, step=step,
                lane=lane, tenant=tenant, cause=obs_flight.C_RECYCLE,
                score=state.caches.touch[0][ids])
            return backend.release(state, lane), fl

        return engine_release_rec

    def _refresh_lane_tenants(self, lanes) -> None:
        """Update the host-side lane -> tenant-index mirror from the live
        lane assignments.  A freed lane keeps its LAST tenant — exactly
        what the release event (recorded after the request finished)
        must stamp."""
        if self._fl is None:
            return
        for i, r in enumerate(lanes):
            if r is not None:
                idx = self._tenant_idx.setdefault(
                    r.tenant_id, len(self._tenant_idx))
                self._lane_tenant_np[i] = idx

    def _lane_tenant(self):
        return jnp.asarray(self._lane_tenant_np)

    @property
    def _tenant_names(self) -> list[str]:
        return [t for t, _ in sorted(self._tenant_idx.items(),
                                     key=lambda kv: kv[1])]

    def flight_stats(self) -> dict | None:
        """Drain the flight ring and derive the analytics (residency /
        reuse-distance histograms, ping-pong churn, per-tenant counts —
        ``obs.flight.analyze``).  None when the recorder is off; cached
        until the ring next mutates."""
        if self._fl is None:
            return None
        head = int(np.asarray(self._fl["head"]))
        cached = self._flight_cache
        if cached is not None and cached[0] == head:
            return cached[1]
        stats = obs_flight.analyze(
            obs_flight.drain(self._fl),
            pingpong_steps=self._fl_cfg.pingpong_steps,
            tenant_names=self._tenant_names or ["default"])
        self._flight_cache = (head, stats)
        return stats

    def _flush_maintain(self, state, *, overlapped: bool = False):
        """Apply a deferred maintenance plan, if one is pending.  The
        double-buffered pass plans at the hook and applies here — at the
        top of the next loop iteration (the overlapped case: the apply
        dispatches back-to-back with the next decode step) or, crucially,
        in ``release_lane`` BEFORE any release: every plan lands before
        the next metadata mutation, so the event sequence — and therefore
        every counter — is identical to the synchronous pass.  Callers run
        it inside the ``maintain_apply`` span."""
        if self._pending_plan is None:
            return state
        plan, plan_step = self._pending_plan
        self._pending_plan = None
        if self._fl is not None:
            state, self._fl = self._rec_apply_fn(
                state, plan, self._fl, jnp.int32(plan_step),
                self._lane_tenant())
        else:
            state = self._apply_fn(state, plan)
        del plan
        if overlapped:
            self.maintain_overlaps += 1
        # materialise the snapshot NOW: the donated next step reuses the
        # state's buffers, so a live reference would read freed memory.
        # This host read waits for the apply
        self._bw_log.append((np.asarray(state.caches.promo_pages),
                             np.asarray(state.caches.demo_pages)))
        return state

    def release_lane(self, state, lane: int):
        """Recycle one lane's metadata (tiered: batched release across
        layers; dense: no-op — the position mask hides stale rows).  A
        pending maintenance plan flushes first: its moves were planned
        against pre-release residency, so applying after the release
        would resurrect the dead lane's pages."""
        if self._tiered:
            if self._pending_plan is not None:
                with self.tracer.span("maintain_apply", step=self.steps):
                    state = self._flush_maintain(state)
            with self.tracer.span("release", lane=lane):
                if self._fl is not None:
                    self._refresh_lane_tenants(
                        getattr(self, "_lanes_ref", ()))
                    state, self._fl = self._rec_release_fn(
                        state, jnp.int32(lane), self._fl,
                        jnp.int32(self.steps),
                        jnp.int32(int(self._lane_tenant_np[lane])))
                else:
                    state = self._release(state, jnp.int32(lane))
            self.releases += 1
        return state

    def park_idle(self, state, idle):
        """Park the masked lanes at pos = -1 (no writes, no reads, no
        hotness)."""
        return self._mask_idle(state, jnp.asarray(idle))

    def set_pos(self, state, lane: int, pos: int):
        return self._set_pos(state, jnp.int32(lane), jnp.int32(pos))

    def chunk_buffers(self, P: int):
        """Fresh chunked-prefill K/V buffers for a padded length P."""
        from repro.models import init_chunk_buffers
        return init_chunk_buffers(self.cfg, P)

    def chunk_fwd(self, P: int, C: int, *, logits: bool = False) -> Callable:
        """Jitted chunked-prefill forward (``serve.decode
        .make_chunk_prefill_fn``; one compiled fn, re-traced per (padded
        length, chunk size)): (params, chunk_tokens [1, C], buf_k, buf_v,
        start) -> updated buffers with rows [start, start+C) written —
        bit-identical to the matching rows of the one-shot forward.

        ``logits=True`` (a separate jit key — the plain variant's key must
        stay byte-for-byte what it always compiled) additionally returns
        the chunk's LM-head logits [1, C, vocab]: the chunked scheduler
        reads the prompt's last row off the final chunk so an admitted
        request's first token costs no extra decode step."""
        key = ("fn", logits)
        if key not in self._chunk_fns:
            from repro.serve.decode import make_chunk_prefill_fn
            self._chunk_fns[key] = make_chunk_prefill_fn(self.cfg,
                                                         logits=logits)
        return self._chunk_fns[key]

    def write_chunk(self, C: int) -> Callable:
        """Jitted chunk ingest, keyed per chunk size: slices rows
        [start, start+C) out of the accumulated buffers and hands them to
        ``backend.write_prefill_chunk`` (tiered: routed page stores)."""
        if C not in self._write_chunk_fns:
            backend = self.backend

            def engine_write_chunk(state, lane, bk, bv, start, length):
                L, _, _, KV, hd = bk.shape
                k = jax.lax.dynamic_slice(
                    bk, (0, 0, start, 0, 0), (L, 1, C, KV, hd))[:, 0]
                v = jax.lax.dynamic_slice(
                    bv, (0, 0, start, 0, 0), (L, 1, C, KV, hd))[:, 0]
                return backend.write_prefill_chunk(state, lane, k, v,
                                                   start, length)

            self._write_chunk_fns[C] = jax.jit(engine_write_chunk)

        def call(state, lane, bk, bv, start, length):
            with self.tracer.span("prefill_chunk", lane=lane,
                                  start=int(start), tokens=C):
                return self._write_chunk_fns[C](
                    state, jnp.int32(lane), bk, bv, jnp.int32(start),
                    jnp.int32(length))
        return call

    def admit_fast(self, state, lane: int, length: int, n_pages: int):
        """Direct-to-fast admission: promote the first ``n_pages`` prompt
        pages of ``lane`` into every layer's fast pool (tiered only).
        With the flight recorder on, each actual install (and any
        eviction the admission forced) records an event from the install
        descriptors."""
        if n_pages not in self._admit_fns:
            backend = self.backend
            if self._fl is None:
                def engine_admit_fast(s, ln, le):
                    return backend.admit_prefix(s, ln, le, n_pages)

                self._admit_fns[n_pages] = jax.jit(engine_admit_fast)
            else:
                mpp = backend.tcfg.max_pages_per_seq

                def engine_admit_fast_rec(s, ln, le, fl, step, lane_tenant,
                                          np_=n_pages):
                    touch0 = s.caches.touch[0]
                    s, pdesc = backend.admit_prefix_desc(s, ln, le, np_)

                    def rec(fl, kind, cause, pages, en):
                        lane = pages // mpp
                        return obs_flight.record(
                            fl, kind, pages, en, step=step,
                            lane=lane, tenant=lane_tenant[lane],
                            cause=cause, score=touch0[pages])

                    fl = rec(fl, obs_flight.K_EVICT, obs_flight.C_VICTIM,
                             pdesc["cb1_dst"], pdesc["cb1_en"])
                    fl = rec(fl, obs_flight.K_INSTALL, obs_flight.C_ADMIT,
                             pdesc["in_src"], pdesc["in_en"])
                    fl = rec(fl, obs_flight.K_EVICT, obs_flight.C_FORCED,
                             pdesc["cb2_dst"], pdesc["cb2_en"])
                    return s, fl

                self._admit_fns[n_pages] = jax.jit(engine_admit_fast_rec)
        with self.tracer.span("admit_fast", lane=lane, pages=n_pages):
            if self._fl is None:
                return self._admit_fns[n_pages](state, jnp.int32(lane),
                                                jnp.int32(length))
            self._refresh_lane_tenants(getattr(self, "_lanes_ref", ()))
            state, self._fl = self._admit_fns[n_pages](
                state, jnp.int32(lane), jnp.int32(length), self._fl,
                jnp.int32(self.steps), self._lane_tenant())
            return state

    def build_maintain_tenants(self, pols: tuple, quotas: tuple):
        """Compile the multi-tenant maintenance pass against a static
        tenant partition (called once by the QoS scheduler at bind)."""
        backend = self.backend

        def engine_maintain_tenants(s, lt):
            return backend.maintain_tenants(s, lt, pols, quotas)

        self._maintain_tenants = jax.jit(engine_maintain_tenants)

    def note_prefill_token(self, req: Request, tok: int, pos: int):
        """Credit a token decoded from prefill logits (the chunked
        scheduler's free first token: the final chunk's last prompt row
        argmaxes to exactly what the first decode step would emit, so it
        lands without one).  Books it like a harvested token; ``pos`` is
        the lane position after the token (the prompt length) — the same
        completion rules as the harvest loop apply, so a ``max_new`` of 1
        or a capacity-filling prompt finishes the request outright."""
        now = time.time()
        if not req.tokens:
            req.first_token_at = now
        req.tokens.append(int(tok))
        req.token_times.append(now)
        self._tokens_out += 1
        if len(req.tokens) >= req.max_new \
                or int(pos) >= self.ec.max_len - 1:
            req.done = True
            req.done_at = now
            if self.slo is not None:
                self.slo.observe(req.tenant_id,
                                 latency_ms=1e3 * req.latency,
                                 ttft_ms=1e3 * req.ttft)

    # -- prefill ---------------------------------------------------------

    def _prefill_fn(self, P: int) -> Callable:
        """Jitted per padded prompt length: one causal forward over the
        padded context, then the backend installs the K/V rows/pages of
        lane ``lane`` and sets ``pos[lane] = length`` (positions >=
        ``length`` are pad garbage the per-lane mask hides until decode
        appends overwrite them)."""
        if P not in self._prefill_fns:
            cfg, backend = self.cfg, self.backend

            def engine_prefill(params, state, lane, tokens, length):
                _, _, (k, v) = forward(cfg, params, {"tokens": tokens},
                                       collect_cache=True)
                return backend.write_prefill(state, lane, k[:, 0], v[:, 0],
                                             length)

            self._prefill_fns[P] = jax.jit(engine_prefill)
        return self._prefill_fns[P]

    def prefill_lane(self, state, lane: int, req: Request):
        """One-shot prefill: install ``req``'s whole prompt into ``lane``;
        returns (state, the token the first decode step consumes)."""
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        assert prompt.size >= 1, "empty prompt"
        ctx = prompt[:-1]
        if ctx.size > self.ec.max_len - 1:
            raise ValueError(
                f"prompt ({prompt.size}) exceeds max_len ({self.ec.max_len})")
        if ctx.size == 0:
            state = self._set_pos(state, jnp.int32(lane), jnp.int32(0))
            return state, int(prompt[-1])
        P = padded_len(int(ctx.size), self.ec.max_len)
        padded = np.zeros((1, P), np.int32)
        padded[0, :ctx.size] = ctx
        with self.tracer.span("prefill", lane=lane, rid=req.rid,
                              tokens=int(ctx.size), padded=P):
            state = self._prefill_fn(P)(
                self.params, state, jnp.int32(lane), jnp.asarray(padded),
                jnp.int32(ctx.size))
        return state, int(prompt[-1])

    # -- decode loop ------------------------------------------------------

    def run(self, log: Callable[[str], None] = lambda s: None) -> list[Request]:
        """Serve the queue to the end.  Every statement of an iteration
        runs inside exactly one phase span of its ``step`` (DESIGN.md
        §10): ``maintain_apply`` (the deferred apply), ``bucket``,
        ``decode_step`` (dispatch only), ``maintain`` (the plan, or the
        synchronous pass), ``sync`` (the host reads), ``harvest`` and
        ``refill``."""
        ec = self.ec
        sched = self.scheduler
        obs, tracer = ec.obs, self.tracer
        lanes: list[Request | None] = [None] * ec.batch
        self._lanes_ref = lanes    # live view for /debug/state + recorder
        state = self.backend.init_state(ec.batch, ec.max_len)
        tokens = jnp.zeros((ec.batch,), jnp.int32)
        finished: list[Request] = []
        self._bw_log = []          # per-run series: init_state reset the
                                   # backend counters this snapshots
        self._pending_plan = None  # never carry a plan across runs
        tracer.clear()             # one saved trace == one run
        self._pending_obs = []
        if self._fl_cfg is not None:   # fresh ring: one ring == one run
            self._fl = obs_flight.init(self._fl_cfg.capacity)
            self._flight_cache = None
            self._lane_tenant_np[:] = 0
        # multi-tenant maintenance is always synchronous: the tenant map
        # can go stale across a deferral, and its moves are not
        # flight-recorded (the plan has no single-descriptor pass)
        tenants = hasattr(self, "_maintain_tenants")

        with profiler_trace(obs.profiler_dir if obs else None):
            with tracer.span("refill", step=self.steps):
                state, tokens = sched.refill(state, tokens, lanes, finished)
                self._refresh_lane_tenants(lanes)
            while any(l is not None for l in lanes):
                with tracer.step(self.steps):
                    if self._pending_plan is not None:
                        # a plan deferred at the last hook applies now,
                        # its dispatch overlapping this step's host work
                        with tracer.span("maintain_apply", step=self.steps):
                            state = self._flush_maintain(state,
                                                         overlapped=True)
                    with tracer.span("bucket", step=self.steps):
                        step_fn = self._step_fn(self._live_bucket(state))
                    with tracer.span("decode_step", step=self.steps):
                        logits, state = step_fn(self.params, state, tokens)
                        tokens = jnp.argmax(logits, axis=-1).astype(
                            jnp.int32)
                        self.steps += 1
                    if self._tiered and self.steps % ec.maintain_every == 0:
                        if ec.overlap_maintain and not tenants:
                            # double-buffered: plan now (scores + top-k
                            # only), defer the pool moves to the next
                            # decode step.  The span keeps the canonical
                            # "maintain" name — the §10 trace contract —
                            # with the apply half showing up as
                            # "maintain_apply" at the top of the next step.
                            # The plan carries its hook step so the
                            # deferred apply's flight events stamp the
                            # decision time (identical to the sync stream)
                            with tracer.span("maintain", step=self.steps,
                                             phase="plan"):
                                self._pending_plan = (self._plan_fn(state),
                                                      self.steps)
                        else:
                            with tracer.span("maintain", step=self.steps):
                                if self._fl is not None and not tenants:
                                    # synchronous with the recorder on: the
                                    # same plan+apply pair
                                    # (run_scheduler_stacked IS apply(plan)
                                    # — bit-identical), tee'd through the
                                    # descriptor recorder
                                    state, self._fl = self._rec_apply_fn(
                                        state, self._plan_fn(state),
                                        self._fl, jnp.int32(self.steps),
                                        self._lane_tenant())
                                else:
                                    state = sched.maintain(state)
                                self._bw_log.append(
                                    (np.asarray(state.caches.promo_pages),
                                     np.asarray(state.caches.demo_pages)))
                    with tracer.span("sync", step=self.steps):
                        if self.logits_log is not None:
                            self.logits_log.append(np.asarray(logits))
                        del logits
                        nxt = np.asarray(tokens)
                        pos = np.asarray(state.pos)
                    with tracer.span("harvest", step=self.steps):
                        now = time.time()
                        for i, r in enumerate(lanes):
                            # lanes mid-chunk-ingest are parked: no token
                            # this step; a request finished by its prefill
                            # token (max_new == 1) must not harvest a stray
                            # extra one
                            if r is None or r.done \
                                    or not sched.is_decoding(i):
                                continue
                            if not r.tokens:
                                r.first_token_at = now
                            r.tokens.append(int(nxt[i]))
                            r.token_times.append(now)
                            self._tokens_out += 1
                            if len(r.tokens) >= r.max_new \
                                    or int(pos[i]) >= ec.max_len - 1:
                                r.done = True
                                # each request's completion stamps ITS OWN
                                # clock — latency is measured from its own
                                # enqueue time, not the batch wave's anchor
                                r.done_at = now
                                if self.slo is not None:
                                    self.slo.observe(
                                        r.tenant_id,
                                        latency_ms=1e3 * r.latency,
                                        ttft_ms=1e3 * r.ttft)
                        if self.hub is not None \
                                and self.steps % obs.sample_every == 0:
                            self._sample(state, lanes, len(finished))
                        if self.steps % 16 == 0:
                            log(f"[engine] step {self.steps}, "
                                f"queue={len(self.queue)}, "
                                f"done={len(finished)}")
                    with tracer.span("refill", step=self.steps):
                        state, tokens = sched.refill(state, tokens, lanes,
                                                     finished)
                        self._refresh_lane_tenants(lanes)
            if self._pending_plan is not None:   # a last hook may be open
                with tracer.span("maintain_apply", step=self.steps):
                    state = self._flush_maintain(state)
        self.final_state = state            # introspection (tests, examples)
        if self.hub is not None:
            self._finalize_obs(state, lanes, finished)
        return finished

    # -- observability -----------------------------------------------------

    def _sample(self, state, lanes, n_finished: int) -> None:
        """One periodic sample point (every ``obs.sample_every`` steps).
        Deliberately does NO device reads, compute or I/O: it stashes the
        engine-loop books (host ints) plus references to the tiered
        counter arrays (immutable, so the references ARE the snapshot).
        ``_drain_samples`` replays the whole series into the hub at drain
        with one batched tap call — in-loop cost is a few µs."""
        self._pending_obs.append(dict(
            step=self.steps, ts=time.time(), ts_us=self.tracer.now_us(),
            queue=len(self.queue),
            active=sum(1 for l in lanes if l is not None),
            tokens=self._tokens_out, finished=n_finished,
            releases=self.releases, overlaps=self.maintain_overlaps,
            tap=obs_metrics.tap_stash(state.caches)
            if self._tiered else None))
        if self.obs_server is not None:
            # live endpoints are up: publish the host-int books NOW so a
            # mid-run /metrics scrape sees current values (record is an
            # absolute overwrite — the drain replay lands on the same
            # numbers, so nothing double counts).  The tiered tap series
            # still waits for the batched drain
            self.hub.record({
                "engine_steps_total": self.steps,
                "engine_tokens_total": self._tokens_out,
                "engine_finished_requests_total": n_finished,
                "engine_releases_total": self.releases,
                "engine_maintain_overlap": self.maintain_overlaps})
            self.hub.set("engine_queue_depth", len(self.queue))
            self.hub.set("engine_active_lanes",
                         sum(1 for l in lanes if l is not None))

    def _drain_samples(self) -> None:
        """Replay the stashed sample points into the hub, in order: one
        jitted vmapped tap over the stacked stashes + one transfer yields
        every sample's tiered metrics at once, then each point becomes a
        hub row (and a Perfetto counter-track event stamped at its
        observed time)."""
        hub, pend = self.hub, self._pending_obs
        self._pending_obs = []
        if pend:
            # keep the newest point for post-run /debug/state scrapes
            self._last_obs = pend[-1]
        series: dict = {}
        if pend and pend[0]["tap"] is not None:
            series = jax.device_get(
                self._batch_tap(tuple(p["tap"] for p in pend)))
        for i, p in enumerate(pend):
            hub.record({
                "engine_steps_total": p["step"],
                "engine_tokens_total": p["tokens"],
                "engine_finished_requests_total": p["finished"],
                "engine_releases_total": p["releases"],
                "engine_maintain_overlap": p["overlaps"],
            })
            hub.set("engine_queue_depth", p["queue"])
            hub.set("engine_active_lanes", p["active"])
            if series:
                m = {k: float(v[i]) for k, v in series.items()}
                hub.record(m)
                hub.set("engine_translated_pages_per_step",
                        m["trimma_translated_pages_total"]
                        / max(p["step"], 1))
                self.tracer.counter("trimma_pages", {
                    "fast_resident": m["trimma_fast_resident_pages"],
                    "metadata": m["trimma_metadata_pages"]},
                    ts=p["ts_us"])
            hub.sample(step=p["step"], ts=p["ts"])

    def _finalize_obs(self, state, lanes, finished) -> None:
        """Drain-time export: replay the sample series, request-latency
        percentiles as labelled gauges, the token-latency histogram,
        tenant fairness counters, then the Prometheus exposition +
        Perfetto trace files."""
        hub = self.hub
        self._sample(state, lanes, len(finished))   # final sample point
        self._drain_samples()
        stats = self.request_stats(finished)
        blocks = {"all": stats["aggregate"], **stats.get("tenants", {})}
        for tenant, block in blocks.items():
            for stat in ("latency_ms", "ttft_ms", "queue_wait_ms"):
                for q, v in block.get(stat, {}).items():
                    if q == "n":
                        continue
                    hub.set("engine_request_latency_ms", v,
                            labels={"tenant": tenant, "stat": stat[:-3],
                                    "quantile": q})
        h = stats["aggregate"]["token_latency_hist"]
        gaps = []
        for r in finished:
            ts = [r.admitted_at] + list(r.token_times)
            gaps += [1e3 * (b - a) for a, b in zip(ts, ts[1:])]
        hub.observe_hist("engine_token_latency_ms", h["edges_ms"],
                         h["counts"], sum(gaps))
        book = getattr(self.scheduler, "book", None)
        if book is not None and hasattr(book, "metrics"):
            for name, value, labels in book.metrics():
                hub.set(name, value, labels=labels)
        if self.slo is not None:
            self.slo.export(hub)
        fs = self.flight_stats()
        if fs is not None:
            obs_flight.export(hub, fs)
        hub.finalize(step=self.steps)
        if self.ec.obs.trace_path and self.tracer is not NULL_TRACER:
            self.tracer.save(self.ec.obs.trace_path)

    def debug_state(self) -> dict:
        """Live JSON-able snapshot for ``/debug/state`` (obs/http): the
        engine books, per-lane assignments, tenant quotas/fairness,
        fast-pool occupancy (from the newest stashed sample — obs-on
        disables donation, so stashed references stay readable), the
        flight-recorder analytics and the SLO summary.  Called from the
        HTTP thread: read-only, device_gets only immutable arrays."""
        lanes = getattr(self, "_lanes_ref", None) or []
        out: dict = {
            "steps": self.steps,
            "tokens_out": self._tokens_out,
            "releases": self.releases,
            "maintain_overlaps": self.maintain_overlaps,
            "queue_depth": len(self.queue),
            "lanes": [None if r is None else
                      {"rid": r.rid, "tenant": r.tenant_id,
                       "tokens": len(r.tokens), "max_new": r.max_new,
                       "done": r.done}
                      for r in lanes],
        }
        book = getattr(self.scheduler, "book", None)
        if book is not None and hasattr(book, "fairness"):
            out["tenants"] = book.fairness()
        pend = self._pending_obs
        last = pend[-1] if pend else getattr(self, "_last_obs", None)
        if last is not None and last.get("tap") is not None:
            tap = last["tap"]
            out["fast_pool"] = {
                "sampled_step": last["step"],
                "resident_pages":
                    int(np.asarray(tap["slot_owner"] != -1).sum()),
                "slots": int(np.asarray(tap["slot_owner"]).size),
                "metadata_pages":
                    int(np.asarray(tap["leaf_cnt"] > 0).sum()),
            }
        fs = self.flight_stats()
        if fs is not None:
            out["flight"] = fs
        if self.slo is not None:
            out["slo"] = self.slo.summary()
        return out

    @property
    def counters(self) -> dict:
        """Tiered-backend metadata/migration counters summed over layers
        (empty for the dense backend), plus per-epoch migration-bandwidth
        series: ``epoch_promo_bytes`` / ``epoch_demo_bytes`` hold the
        bytes moved between consecutive maintain passes (the counters are
        snapshotted per pass and differenced at read-out, so the decode
        loop never blocks on a transfer)."""
        if not self._tiered or not hasattr(self, "final_state"):
            return {}
        out = self.backend.counters(self.final_state)
        if self._bw_log:
            pb = self.backend.tcfg.page_bytes
            promo = [int(np.asarray(p).sum()) for p, _ in self._bw_log]
            demo = [int(np.asarray(d).sum()) for _, d in self._bw_log]
            out["epoch_promo_bytes"] = [
                (b - a) * pb for a, b in zip([0] + promo[:-1], promo)]
            out["epoch_demo_bytes"] = [
                (b - a) * pb for a, b in zip([0] + demo[:-1], demo)]
        return out

    def request_stats(self, requests: list[Request]) -> dict:
        """Per-request latency statistics for a finished batch: aggregate
        and per-tenant percentiles (ms) of end-to-end latency and time to
        first token, a log-bucketed token-latency histogram (inter-token
        gaps), and the scheduler's fairness counters.  Exported into the
        benchmark JSON (``benchmarks/run.py --sched``) and consumed by
        ``examples/engine_tiered.py``."""
        def _ms(xs):
            xs = np.asarray(sorted(xs), np.float64) * 1e3
            if not xs.size:
                return {}
            return dict(n=int(xs.size), mean=float(xs.mean()),
                        p50=float(np.percentile(xs, 50)),
                        p99=float(np.percentile(xs, 99)),
                        max=float(xs.max()))

        def _hist(gaps_ms):
            # log2 buckets from 0.25 ms: [.25, .5), [.5, 1), ... [>= 2^k]
            # — the one histogram geometry the whole repo shares
            # (obs.metrics.HIST_EDGES_MS; the hub exposes it as the
            # engine_token_latency_ms Prometheus histogram)
            counts = [0] * obs_metrics.HIST_BUCKETS
            for g in gaps_ms:
                counts[obs_metrics.bucket_index(g)] += 1
            return dict(edges_ms=list(obs_metrics.HIST_EDGES_MS),
                        counts=counts)

        def _block(rs):
            gaps = []                       # one latency per decoded token
            for r in rs:
                ts = [r.admitted_at] + list(r.token_times)
                gaps += [1e3 * (b - a) for a, b in zip(ts, ts[1:])]
            return dict(
                latency_ms=_ms([r.latency for r in rs]),
                ttft_ms=_ms([r.ttft for r in rs]),
                queue_wait_ms=_ms([r.queue_wait for r in rs]),
                tokens=sum(len(r.tokens) for r in rs),
                token_latency_hist=_hist(gaps))

        out = {"aggregate": _block(requests)}
        tenants = sorted({r.tenant_id for r in requests})
        if len(tenants) > 1:
            out["tenants"] = {
                t: _block([r for r in requests if r.tenant_id == t])
                for t in tenants}
        book = getattr(self.scheduler, "book", None)
        if book is not None:
            out["fairness"] = book.fairness()
        return out
