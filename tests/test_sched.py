"""serve/sched: chunked prefill bit-identical to one-shot, multi-tenant
QoS invariants (slot partition, budget conservation, starvation bound),
direct-to-fast admission coherence, and per-request latency accounting."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.models import (decode_step, forward, forward_chunk,
                          init_chunk_buffers, init_params, prefill)
from repro.serve.engine import Engine, EngineConfig, Request
from repro.serve.sched import (ChunkedScheduler, GreedyScheduler,
                               TenantBook, TenantConfig, make_scheduler,
                               split_slots)
from repro.tiered import kvcache as tk

# two float32 code paths over the same math agree to rounding, not bit for
# bit: the misses seen on XLA:CPU were at most 1.3e-5 in magnitude
F32_PATH_TOL = dict(rtol=1e-5, atol=5e-5)


@functools.lru_cache(maxsize=1)
def _smoke_model():
    cfg = reduce_for_smoke(get_config("llama3-8b"))
    return cfg, init_params(cfg, jax.random.key(0))


def _presets():
    from repro.core.policy import PRESETS
    return sorted(PRESETS)


def _tiered_cfg(**kw):
    base = dict(n_seqs=2, max_pages_per_seq=16, page_tokens=8,
                n_kv_heads=2, head_dim=16, fast_data_slots=4,
                dtype="float32")
    base.update(kw)
    return tk.TieredConfig(**base)


# ---------------------------------------------------------------------------
# chunked prefill == one-shot, at every level
# ---------------------------------------------------------------------------

def test_forward_chunk_bitwise_equals_forward():
    """The chunk forward against a full-length key buffer reproduces the
    one-shot forward's float32 K/V rows within F32_PATH_TOL (the padded
    key axis keeps every reduction's length and values; the compiler
    may still order the sums of the two programs differently)."""
    cfg, params = _smoke_model()
    P, ctx, C = 32, 27, 8
    rng = np.random.default_rng(0)
    tokens = np.zeros((1, P), np.int32)
    tokens[0, :ctx] = rng.integers(0, cfg.vocab, ctx)
    _, _, (k_ref, v_ref) = forward(cfg, params,
                                   {"tokens": jnp.asarray(tokens)},
                                   collect_cache=True)
    bk, bv = init_chunk_buffers(cfg, P)
    fc = jax.jit(lambda p, t, a, b, s: forward_chunk(cfg, p, t, a, b, s))
    for start in range(0, P, C):
        bk, bv = fc(params, jnp.asarray(tokens[:, start:start + C]),
                    bk, bv, start)
    np.testing.assert_allclose(np.asarray(bk)[:, :, :ctx],
                               np.asarray(k_ref)[:, :, :ctx], **F32_PATH_TOL)
    np.testing.assert_allclose(np.asarray(bv)[:, :, :ctx],
                               np.asarray(v_ref)[:, :, :ctx], **F32_PATH_TOL)


@pytest.mark.parametrize("chunk_pages", [1, 2, 3])
def test_prefill_chunk_bitwise_equals_prefill_tokens(chunk_pages):
    """Applying a prompt's chunks through ``prefill_chunk`` leaves the
    store bit-identical to one ``prefill_tokens`` pass (identity homes,
    partial tail page included)."""
    cfg = _tiered_cfg()
    key = jax.random.key(1)
    S, length = 88, 83                      # 11 pages, ragged tail
    k = jax.random.normal(key, (S, cfg.n_kv_heads, cfg.head_dim))
    v = jax.random.normal(jax.random.fold_in(key, 1), k.shape)
    ref = tk.prefill_tokens(cfg, tk.init_state(cfg), 1, k, v, length)
    st = tk.init_state(cfg)
    C = chunk_pages * cfg.page_tokens
    for start in range(0, S, C):
        st = tk.prefill_chunk(cfg, st, 1, k[start:start + C],
                              v[start:start + C], start, length)
    for f in tk.TieredState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, f)), np.asarray(getattr(st, f)),
            err_msg=f"field {f} diverged")


def test_chunk_ingest_after_admission_routes_to_fast():
    """Direct-to-fast admission then chunked ingest: the chunk writes
    must land in the admitted pages' FAST copies (write-through at
    ingest, DESIGN.md §9) — reads are bit-identical to the un-admitted
    reference and the fast slots hold the prompt bytes."""
    from repro.serve import tiered as srv
    cfg = _tiered_cfg()
    key = jax.random.key(2)
    S = 4 * cfg.page_tokens
    k = jax.random.normal(key, (S, cfg.n_kv_heads, cfg.head_dim))
    v = jax.random.normal(jax.random.fold_in(key, 1), k.shape)
    # reference: plain one-shot ingest, nothing resident
    ref = tk.prefill_tokens(cfg, tk.init_state(cfg), 0, k, v, S)
    # admitted: first 2 pages promoted at ingest, then routed chunks
    st = tk.admit_pages(cfg, tk.init_state(cfg), 0, S, 2)
    assert int(st.migrations) == 2
    assert (np.asarray(st.leaf_table[:2]) != tk.INVALID).all()
    assert (np.asarray(st.touch[:2]) > 0).all(), "no install touch"
    for start in range(0, S, cfg.page_tokens):
        st = tk.prefill_chunk(cfg, st, 0, k[start:start + cfg.page_tokens],
                              v[start:start + cfg.page_tokens], start, S)
    slot0 = int(st.leaf_table[0])
    np.testing.assert_array_equal(np.asarray(st.fast_k[slot0]),
                                  np.asarray(ref.slow_k[0]))
    q = jax.random.normal(jax.random.fold_in(key, 2),
                          (cfg.n_seqs, cfg.n_kv_heads, 2, cfg.head_dim))
    sl = jnp.asarray([S, 0], jnp.int32)
    out_ref, _ = srv.attend(cfg, ref, q, sl)
    out_adm, _ = srv.attend(cfg, st, q, sl)
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(out_adm))


@pytest.mark.parametrize("preset", _presets())
def test_chunked_prefill_logits_bit_identical(preset):
    """Acceptance: the chunked-prefill decode stream equals the one-shot
    reference (``models.prefill`` + ``decode_step``) token for token,
    through the TIERED backend under every policy preset — chunked ingest
    is invisible to the math."""
    from repro.core.policy import get_policy
    cfg, params = _smoke_model()
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, cfg.vocab, 21).astype(np.int32)

    # one-shot reference greedy chain
    logits, state = prefill(cfg, params,
                            {"tokens": jnp.asarray(prompt[:-1])[None]},
                            max_len=48)
    ref = []
    tok = int(prompt[-1])
    st = state._replace(pos=jnp.full_like(state.pos, prompt.size - 1))
    for _ in range(5):
        lg, st = decode_step(cfg, params, st, jnp.asarray([tok], jnp.int32))
        tok = int(jnp.argmax(lg[0]))
        ref.append(tok)

    from repro.models.kv_backend import TieredBackend
    backend = TieredBackend(cfg, 1, 48, page_tokens=8, fast_data_slots=4,
                            policy=get_policy(preset, epoch_len=2))
    eng = Engine(cfg, params, EngineConfig(
        batch=1, max_len=48, backend="tiered", page_tokens=8,
        fast_data_slots=4, maintain_every=2, scheduler="chunked",
        prefill_chunk=8), backend=backend)
    eng.submit(Request(rid=0, prompt=prompt, max_new=5))
    got = eng.run()[0].tokens
    assert got == ref, (got, ref)


def test_chunked_tokens_equal_when_chunk_misaligned_to_buffer():
    """Chunk sizes that do NOT divide the padded buffer length: the
    final chunk back-aligns (overlap rows re-write identical bytes), so
    the stream still equals the one-shot engine's exactly."""
    cfg, params = _smoke_model()
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, cfg.vocab, 30).astype(np.int32)   # P = 32

    def run(ec):
        eng = Engine(cfg, params, ec)
        eng.submit(Request(rid=0, prompt=prompt.copy(), max_new=4))
        return eng.run()[0].tokens

    ref = run(EngineConfig(batch=1, max_len=64))
    got_dense = run(EngineConfig(batch=1, max_len=64, scheduler="chunked",
                                 prefill_chunk=12))         # 12 does not
    got_tiered = run(EngineConfig(batch=1, max_len=64,      # divide 32
                                  backend="tiered", page_tokens=8,
                                  fast_data_slots=4, scheduler="chunked",
                                  prefill_chunk=24))        # nor does 24
    assert got_dense == ref
    assert got_tiered == ref


def test_chunked_engine_tokens_equal_greedy_multilane():
    """A mixed request set decoded under the chunked scheduler yields the
    same per-request token streams as the greedy one-shot engine, dense
    and tiered (the interleaving changes, the math must not)."""
    cfg, params = _smoke_model()

    def reqs():
        rng = np.random.default_rng(5)
        return [Request(rid=r, prompt=rng.integers(0, cfg.vocab, 3 + 5 * r),
                        max_new=4 + (r % 2) * 4) for r in range(4)]

    outs = {}
    for name, ec in {
        "greedy": EngineConfig(batch=2, max_len=64),
        "chunked_dense": EngineConfig(batch=2, max_len=64,
                                      scheduler="chunked", prefill_chunk=4),
        "chunked_tiered": EngineConfig(batch=2, max_len=64,
                                       backend="tiered", page_tokens=8,
                                       fast_data_slots=8, maintain_every=3,
                                       scheduler="chunked", prefill_chunk=8),
    }.items():
        eng = Engine(cfg, params, ec)
        for r in reqs():
            eng.submit(r)
        outs[name] = {r.rid: r.tokens for r in eng.run()}
    assert outs["chunked_dense"] == outs["greedy"]
    assert outs["chunked_tiered"] == outs["greedy"]


# ---------------------------------------------------------------------------
# multi-tenant QoS invariants
# ---------------------------------------------------------------------------

def test_plan_tenants_budget_quota_membership():
    """plan_tenants: per-tenant budgets respected, every enabled lane in
    its tenant's partition, promotions capped by the fast-slot quota,
    under randomized scores/residency/grouping."""
    from repro.core.policy import get_policy, plan_tenants
    rng = np.random.default_rng(7)
    n = 64
    pols = (get_policy("threshold", max_moves=3),
            get_policy("write_aware", max_moves=2),
            get_policy("on_demand", max_moves=4))
    quotas = (4, 3, 2)
    for _ in range(20):
        score = jnp.asarray(rng.integers(0, 8, n), jnp.int32)
        resident = jnp.asarray(rng.random(n) < 0.3)
        group = jnp.asarray(rng.integers(-1, 3, n), jnp.int32)
        p = plan_tenants(pols, score, resident, group, quotas)
        pid, pen = np.asarray(p.promote_ids), np.asarray(p.promote_en)
        did, den = np.asarray(p.demote_ids), np.asarray(p.demote_en)
        g = np.asarray(group)
        res = np.asarray(resident)
        off = 0
        for t, (pol, quota) in enumerate(zip(pols, quotas)):
            k = pol.max_moves
            sl = slice(off, off + k)
            moves = pen[sl].sum() + den[sl].sum()
            assert moves <= pol.max_moves, (t, moves)
            assert (g[pid[sl][pen[sl]]] == t).all(), "foreign promotion"
            assert (g[did[sl][den[sl]]] == t).all(), "foreign demotion"
            assert (~res[pid[sl][pen[sl]]]).all(), "promoted a resident"
            assert res[did[sl][den[sl]]].all(), "demoted a non-resident"
            # residency never GROWS past the quota (a randomly seeded
            # over-quota start only shrinks — promotions are cut to zero)
            res_t = (res & (g == t)).sum()
            assert res_t + pen[sl].sum() <= max(quota, res_t), \
                "quota exceeded"
            off += k


def test_tenant_slot_partition_conservation_under_churn():
    """run_scheduler_tenants under random touch churn: no tenant's
    residency ever exceeds its quota, ownership stays conserved
    (slot_owner inverse of leaf_table), and unowned (idle-lane) pages
    never move."""
    from repro.core.policy import get_policy
    cfg = _tiered_cfg(n_seqs=4, max_pages_per_seq=8, fast_data_slots=6,
                      policy=get_policy("threshold", promote_threshold=1,
                                        epoch_len=2, max_moves=3))
    pols = (cfg.pol, get_policy("threshold", promote_threshold=1,
                                epoch_len=2, max_moves=2))
    quotas = split_slots(cfg.fast_data_slots, (TenantConfig("a", weight=2),
                                               TenantConfig("b", weight=1)))
    assert sum(quotas) == cfg.fast_data_slots
    lane_tenant = np.array([0, 1, 0, -1], np.int32)   # lane 3 idle
    page_tenant = jnp.repeat(jnp.asarray(lane_tenant), cfg.max_pages_per_seq)
    st = tk.init_state(cfg)
    rng = np.random.default_rng(3)
    g = np.asarray(page_tenant)
    for step in range(12):
        ids = jnp.asarray(rng.integers(0, cfg.n_logical, (1, 16)), jnp.int32)
        _, st = tk.lookup(cfg, st, ids)
        st = tk.run_scheduler_tenants(cfg, st, page_tenant, pols, quotas)
        lt = np.asarray(st.leaf_table)
        so = np.asarray(st.slot_owner)
        resident = np.nonzero(lt != tk.INVALID)[0]
        assert (so[lt[resident]] == resident).all(), "ownership broken"
        for t, quota in enumerate(quotas):
            assert (g[resident] == t).sum() <= quota, (step, t)
        assert (g[resident] >= 0).all(), "an idle lane's page moved"


def test_split_slots_partition():
    ts = (TenantConfig("a", weight=3), TenantConfig("b", weight=1),
          TenantConfig("c", weight=1))
    q = split_slots(10, ts)
    assert sum(q) == 10 and q[0] > q[1] >= 1 and q[2] >= 1
    assert split_slots(2, ts)[0] >= 1


def test_qos_admission_starvation_bound():
    """The weighted picker never skips a non-empty tenant more than
    ``starvation_bound`` consecutive admissions, no matter the weight
    ratio."""
    ts = (TenantConfig("heavy", weight=100), TenantConfig("light", weight=1))
    book = TenantBook(ts, starvation_bound=4)
    for i in range(64):
        book.submit(Request(rid=i, prompt=np.zeros(1, np.int32), max_new=1,
                            tenant_id="heavy", arrived=float(i)))
    for i in range(4):
        book.submit(Request(rid=100 + i, prompt=np.zeros(1, np.int32),
                            max_new=1, tenant_id="light",
                            arrived=float(100 + i)))
    picks = [book.pick().tenant_id for _ in range(40)]
    gap = 0
    worst = 0
    for t in picks:
        if t == "light":
            worst = max(worst, gap)
            gap = 0
        else:
            gap += 1
    assert "light" in picks
    assert worst <= 4, f"light starved for {worst} admissions"
    assert book.stats[1]["max_skips"] <= 4


def test_qos_weighted_share():
    """With both queues saturated, admission shares track the weights."""
    ts = (TenantConfig("a", weight=3), TenantConfig("b", weight=1))
    book = TenantBook(ts, starvation_bound=100)
    for i in range(80):
        book.submit(Request(rid=i, prompt=np.zeros(1, np.int32), max_new=1,
                            tenant_id="ab"[i % 2], arrived=float(i)))
    picks = [book.pick().tenant_id for _ in range(40)]
    assert 25 <= picks.count("a") <= 35           # ~30 of 40


# ---------------------------------------------------------------------------
# engine-level scheduling behaviour
# ---------------------------------------------------------------------------

def test_make_scheduler_kinds_and_wave_shim():
    ec = EngineConfig()
    assert isinstance(make_scheduler(ec), GreedyScheduler)
    assert isinstance(
        make_scheduler(EngineConfig(scheduler="chunked")), ChunkedScheduler)
    with pytest.warns(FutureWarning, match="wave-refill"):
        s = make_scheduler(EngineConfig(scheduler="wave"))
    assert isinstance(s, GreedyScheduler)
    with pytest.raises(ValueError):
        make_scheduler(EngineConfig(scheduler="nope"))


def test_mid_wave_latency_uses_own_enqueue(monkeypatch):
    """Straggler-accounting regression: a request admitted mid-wave
    measures latency/ttft from ITS OWN enqueue time, not the wave
    anchor.  Wall clocks are faked so the assertion is exact: request 1
    is submitted 10 virtual seconds after request 0, so anchoring to the
    wave would inflate its latency by 10s."""
    import repro.serve.engine as eng_mod
    clock = {"t": 0.0}
    monkeypatch.setattr(eng_mod.time, "time", lambda: clock["t"])

    cfg, params = _smoke_model()
    eng = Engine(cfg, params, EngineConfig(batch=1, max_len=32))
    rng = np.random.default_rng(11)
    r0 = Request(rid=0, prompt=rng.integers(0, cfg.vocab, 2), max_new=3)
    r1 = Request(rid=1, prompt=rng.integers(0, cfg.vocab, 2), max_new=3)
    eng.submit(r0)
    clock["t"] = 10.0                      # r1 enqueues 10s into the wave
    eng.submit(r1)

    real = [clock["t"]]

    def tick():
        real[0] += 0.5
        return real[0]
    monkeypatch.setattr(eng_mod.time, "time", tick)
    done = {r.rid: r for r in eng.run()}
    assert done[1].arrived == 10.0
    # r1 decodes AFTER r0 on the single lane; its latency still spans
    # only its own enqueue -> done window, which is < r0's full span +10
    assert done[1].latency < (done[1].done_at - done[0].arrived) - 5.0
    for r in done.values():
        assert r.first_token_at >= r.admitted_at >= r.arrived
        assert r.done_at >= r.first_token_at
        assert len(r.token_times) == len(r.tokens)


def test_engine_chunked_qos_end_to_end_invariants():
    """Two-tenant chunked+QoS serve on the tiered backend: every request
    served, released metadata returns to identity, fairness counters
    conserved, request stats well-formed."""
    cfg, params = _smoke_model()
    tenants = (TenantConfig("interactive", weight=2, policy="on_demand"),
               TenantConfig("batch", weight=1))
    eng = Engine(cfg, params, EngineConfig(
        batch=2, max_len=64, backend="tiered", page_tokens=8,
        fast_data_slots=8, maintain_every=2, scheduler="chunked",
        prefill_chunk=8, tenants=tenants, admit_pages=2))
    rng = np.random.default_rng(17)
    n = 6
    for rid in range(n):
        t = "interactive" if rid % 2 == 0 else "batch"
        eng.submit(Request(
            rid=rid, prompt=rng.integers(0, cfg.vocab,
                                         4 if t == "interactive" else 24),
            max_new=5, tenant_id=t))
    done = eng.run()
    assert sorted(r.rid for r in done) == list(range(n))
    assert eng.releases == n
    st = eng.final_state.caches
    assert (np.asarray(st.leaf_table) == tk.INVALID).all()
    assert (np.asarray(st.slot_owner) == tk.INVALID).all()
    stats = eng.request_stats(done)
    fair = stats["fairness"]
    assert fair["interactive"]["finished"] == 3
    assert fair["batch"]["finished"] == 3
    assert fair["interactive"]["admitted_fast_pages"] > 0
    assert fair["batch"]["chunks"] > fair["interactive"]["chunks"]
    agg = stats["aggregate"]
    assert agg["tokens"] == sum(len(r.tokens) for r in done)
    assert sum(agg["token_latency_hist"]["counts"]) == agg["tokens"]
    assert set(stats["tenants"]) == {"interactive", "batch"}
    c = eng.counters
    assert c["migrations"] > 0
    assert len(c["epoch_promo_bytes"]) == len(c["epoch_demo_bytes"])
    assert sum(c["epoch_promo_bytes"]) == c["promo_bytes"]


def test_engine_reuse_bandwidth_series_per_run():
    """Counter-snapshot regression: a reused Engine must emit a per-run
    epoch-bandwidth series (init_state resets the backend counters, so a
    stale snapshot log would produce negative deltas)."""
    cfg, params = _smoke_model()
    eng = Engine(cfg, params, EngineConfig(
        batch=2, max_len=48, backend="tiered", page_tokens=8,
        fast_data_slots=4, maintain_every=2))
    rng = np.random.default_rng(29)
    for run in range(2):
        for rid in range(4):
            eng.submit(Request(rid=rid, prompt=rng.integers(0, cfg.vocab, 4),
                               max_new=8))
        done = eng.run()
        assert len(done) == 4
        c = eng.counters
        assert all(b >= 0 for b in c["epoch_promo_bytes"]), (run, c)
        assert all(b >= 0 for b in c["epoch_demo_bytes"]), (run, c)
        assert sum(c["epoch_promo_bytes"]) == c["promo_bytes"]
        assert sum(c["epoch_demo_bytes"]) == c["demo_bytes"]


def test_admission_capped_by_remaining_quota():
    """Direct-to-fast admission cannot grow a tenant past its fast-slot
    partition across concurrent lanes: the per-ingest cap subtracts the
    pages already admitted on the tenant's live lanes."""
    cfg, params = _smoke_model()
    tenants = (TenantConfig("only", weight=1, policy="on_demand"),)
    eng = Engine(cfg, params, EngineConfig(
        batch=2, max_len=64, backend="tiered", page_tokens=8,
        fast_data_slots=3, scheduler="chunked", prefill_chunk=8,
        tenants=tenants, admit_pages=2))
    s = eng.scheduler
    assert s.quotas == (3,)
    assert s._admit_fast_pages(0, 0, 64) == 2          # fresh: engine cap
    s.lane_tenant[0] = 0
    s._note_admit(0, 0, 2)                             # lane 0 holds 2
    assert s._admit_fast_pages(1, 0, 64) == 1          # only 1 slot left
    s.lane_tenant[1] = 0
    s._note_admit(1, 0, 1)
    assert s._admit_fast_pages(0, 0, 64) == 0          # partition full
    s._admitted[0] = 0                                 # lane 0 recycled
    s.lane_tenant[0] = -1
    assert s._admit_fast_pages(0, 0, 64) == 2


def test_unknown_tenant_rejected():
    book = TenantBook((TenantConfig("a"), TenantConfig("b")))
    with pytest.raises(KeyError, match="unknown tenant"):
        book.submit(Request(rid=0, prompt=np.zeros(1, np.int32), max_new=1,
                            tenant_id="zzz"))
