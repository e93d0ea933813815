"""Serving engine: continuous batching, straggler bucketing, real
prefill, and the full-model tiered decode loop (tiered logits within
``F32_PATH_TOL`` of the dense float32 reference at ragged per-lane
positions)."""

import functools

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.models import init_params
from repro.serve.engine import Engine, EngineConfig, Request

# two float32 code paths over the same math (XLA attention over a dense
# cache vs the paged read) agree to rounding, not bit for bit: the misses
# seen on XLA:CPU were at most 1.3e-5 in magnitude
F32_PATH_TOL = dict(rtol=1e-5, atol=5e-5)


@functools.lru_cache(maxsize=1)
def _smoke_model():
    cfg = reduce_for_smoke(get_config("llama3-8b"))
    return cfg, init_params(cfg, jax.random.key(0))


def test_engine_serves_all_requests():
    cfg, params = _smoke_model()
    eng = Engine(cfg, params, EngineConfig(batch=2, max_len=48))
    rng = np.random.default_rng(1)
    n = 5
    for rid in range(n):
        eng.submit(Request(rid=rid, prompt=rng.integers(0, cfg.vocab, 3),
                           max_new=4 + (rid % 2) * 4))
    done = eng.run()
    assert len(done) == n
    assert sorted(r.rid for r in done) == list(range(n))
    for r in done:
        assert 1 <= len(r.tokens) <= r.max_new
        assert all(0 <= t < cfg.vocab for t in r.tokens)


def test_bucketing_prefers_similar_lengths():
    cfg, params = _smoke_model()
    eng = Engine(cfg, params, EngineConfig(batch=1, max_len=32, bucket=2))
    rng = np.random.default_rng(2)
    eng.submit(Request(rid=0, prompt=rng.integers(0, cfg.vocab, 2), max_new=4))
    eng.submit(Request(rid=1, prompt=rng.integers(0, cfg.vocab, 2), max_new=20))
    eng.submit(Request(rid=2, prompt=rng.integers(0, cfg.vocab, 2), max_new=4))
    # after serving rid=0 (bucket 4), rid=2 (similar length) jumps rid=1
    done = eng.run()
    order = [r.rid for r in done]
    assert order.index(2) < order.index(1), order


def _tiered_cfg(**kw):
    from repro.tiered import kvcache as tk
    base = dict(n_seqs=2, max_pages_per_seq=64, page_tokens=16,
                n_kv_heads=2, head_dim=32, fast_data_slots=4,
                migrate_threshold=2, dtype="float32")
    base.update(kw)
    return tk.TieredConfig(**base)


def _filled_tiered(cfg, key):
    import jax.numpy as jnp
    from repro.tiered import kvcache as tk
    st = tk.init_state(cfg)
    return st._replace(
        slow_k=jax.random.normal(key, st.slow_k.shape, jnp.float32),
        slow_v=jax.random.normal(jax.random.fold_in(key, 1),
                                 st.slow_v.shape, jnp.float32))


def _presets():
    from repro.core.policy import PRESETS
    return sorted(PRESETS)


@pytest.mark.parametrize("preset", _presets())
def test_tiered_attend_invariant_under_serving(preset):
    """The zero-copy decode read (cached device table + split-pool
    kernel) must be BIT-IDENTICAL to the legacy path (full per-step
    re-translation + unified-pool concat) across append -> maintain ->
    evict interleavings, under every policy preset — the staleness /
    golden-equality regression for the cached table."""
    import dataclasses

    import jax.numpy as jnp
    from repro.core.policy import get_policy
    from repro.serve import tiered as srv
    from repro.tiered import kvcache as tk

    cfg = _tiered_cfg(policy=get_policy(preset, epoch_len=2),
                      migrate_threshold=None)
    cfg_legacy = dataclasses.replace(cfg, cache_device_table=False)
    key = jax.random.key(0)
    st = _filled_tiered(cfg, key)
    st_legacy = _filled_tiered(cfg_legacy, key)
    q = jax.random.normal(jax.random.fold_in(key, 2),
                          (cfg.n_seqs, cfg.n_kv_heads, 4, cfg.head_dim))
    seqs = jnp.arange(cfg.n_seqs)
    pos = 126                      # appends cross a page boundary mid-run
    for step in range(8):
        k1 = jax.random.normal(jax.random.fold_in(key, 100 + step),
                               (cfg.n_seqs, cfg.n_kv_heads, cfg.head_dim))
        v1 = jax.random.normal(jax.random.fold_in(key, 200 + step),
                               (cfg.n_seqs, cfg.n_kv_heads, cfg.head_dim))
        st = tk.append_token(cfg, st, seqs, k1, v1, pos)
        st_legacy = tk.append_token(cfg_legacy, st_legacy, seqs, k1, v1, pos)
        pos += 1
        sl = jnp.full((cfg.n_seqs,), pos, jnp.int32)
        out, st = srv.attend(cfg, st, q, sl)
        ref, st_legacy = srv.attend_concat(cfg_legacy, st_legacy, q, sl)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        st = srv.maintain(cfg, st, max_moves=3)
        st_legacy = srv.maintain(cfg_legacy, st_legacy, max_moves=3)
    assert int(st.migrations) + int(st.demotions) > 0


def test_tiered_server_decode_loop():
    """TieredServer: jitted zero-copy steps + maintain + lane release;
    steady-state steps are served from the device table, and a released
    lane's pages vanish from the metadata."""
    import jax.numpy as jnp
    from repro.serve.engine import TieredServer
    from repro.tiered import kvcache as tk

    cfg = _tiered_cfg()
    srv = TieredServer(cfg)
    key = jax.random.key(3)
    srv.state = _filled_tiered(cfg, key)
    q = jax.random.normal(jax.random.fold_in(key, 1),
                          (cfg.n_seqs, cfg.n_kv_heads, 4, cfg.head_dim))
    kv = jax.random.normal(jax.random.fold_in(key, 2),
                           (cfg.n_seqs, cfg.n_kv_heads, cfg.head_dim))
    out0 = srv.step(q, kv, kv, pos=100)
    for pos in range(101, 113):
        out = srv.step(q, kv, kv, pos)
        if pos % 4 == 0:
            srv.maintain()
    assert out.shape == out0.shape and np.isfinite(np.asarray(out)).all()
    c = srv.counters
    assert c["dev_hits"] > 0, "steady state never hit the device table"
    assert c["lookups"] < srv.steps * cfg.n_logical / 4, \
        "decode path is still translating every page every step"
    srv.release(0)
    lt = np.asarray(srv.state.leaf_table)
    assert (lt[:cfg.max_pages_per_seq] == tk.INVALID).all()
    out2 = srv.step(q, kv, kv, pos=113)
    assert np.isfinite(np.asarray(out2)).all()


def test_bucketing_anchors_to_wave_not_last_refill():
    """Straggler-bucket staleness regression: the bucket anchors to the
    first request of a batch wave and is NOT overwritten by every refill
    — after a fallback pop of a long straggler, subsequent picks still
    serve the wave's length class in FIFO order instead of chaining
    stragglers through the stale bucket."""
    cfg, params = _smoke_model()
    eng = Engine(cfg, params, EngineConfig(batch=1, max_len=32, bucket=2))
    rng = np.random.default_rng(3)
    for rid, mn in enumerate([4, 20, 4, 16, 18]):
        eng.submit(Request(rid=rid, prompt=rng.integers(0, cfg.vocab, 2),
                           max_new=mn))
    done = eng.run()
    order = [r.rid for r in done]
    # wave bucket 4: rid 2 jumps the stragglers; after the forced pop of
    # rid 1 (20) the stale-bucket bug would let rid 4 (18) jump rid 3 (16)
    assert order.index(2) < order.index(1), order
    assert order.index(3) < order.index(4), order
    # the wave drained and the queue is empty: the anchor resets
    assert eng.active_bucket is None


def test_engine_prefill_conditions_generation():
    """The fake-prefill regression (the prompt-replay loop whose body was
    ``pass``): the engine's greedy stream must equal the reference greedy
    loop built from ``models.prefill`` + ``decode_step`` — which by
    construction conditions on EVERY prompt token."""
    import jax.numpy as jnp

    from repro.models import decode_step, prefill

    cfg, params = _smoke_model()
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab, 6).astype(np.int32)
    eng = Engine(cfg, params, EngineConfig(batch=1, max_len=48))
    eng.submit(Request(rid=0, prompt=prompt, max_new=5))
    got = eng.run()[0].tokens

    logits, state = prefill(cfg, params, {"tokens": jnp.asarray(prompt)[None]},
                            max_len=48)
    ref = [int(jnp.argmax(logits[0, -1]))]
    for _ in range(4):
        logits, state = decode_step(cfg, params, state,
                                    jnp.asarray([ref[-1]], jnp.int32))
        ref.append(int(jnp.argmax(logits[0])))
    assert got == ref, (got, ref)


_STEPS, _B, _MAX_LEN = 12, 2, 64
_PREFILLS = ((0, 5), (1, 9), (0, 3))       # (lane, ctx len); ragged lanes


@functools.lru_cache(maxsize=1)
def _dense_reference():
    """The DenseBackend ground truth, computed ONCE for every preset:
    prompt K/V per ingest, the greedy token chain, and the per-step
    float32 logits the tiered run must reproduce within F32_PATH_TOL
    (the mid-stream ingest of _PREFILLS[2] recycles lane 0 at step 6)."""
    import jax.numpy as jnp
    from repro.models import decode_step, forward
    from repro.models.kv_backend import DenseBackend

    cfg, params = _smoke_model()
    rng = np.random.default_rng(11)
    kvs = []
    for _, n in _PREFILLS:
        ctx = jnp.asarray(rng.integers(0, cfg.vocab, (1, n)), jnp.int32)
        _, _, (k, v) = forward(cfg, params, {"tokens": ctx},
                               collect_cache=True)
        kvs.append((k[:, 0], v[:, 0]))
    dense = DenseBackend(cfg)
    sd = dense.init_state(_B, _MAX_LEN)
    for (lane, n), (k, v) in zip(_PREFILLS[:2], kvs):
        sd = dense.write_prefill(sd, lane, k, v, n)
    step = jax.jit(lambda p, s, t: decode_step(cfg, p, s, t, backend=dense))
    tok = jnp.asarray(rng.integers(0, cfg.vocab, (_B,)), jnp.int32)
    tokens, logits = [], []
    for i in range(_STEPS):
        tokens.append(np.asarray(tok))
        lg, sd = step(params, sd, tok)
        logits.append(np.asarray(lg))
        if i == 6:                         # recycle lane 0 mid-stream
            lane, n = _PREFILLS[2]
            sd = dense.write_prefill(sd, lane, *kvs[2], n)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
    return kvs, tokens, logits


@pytest.mark.parametrize("preset", _presets())
def test_full_model_dense_tiered_bit_identical(preset):
    """Acceptance: the full transformer decoded through the TieredBackend
    (one Trimma store per layer) produces logits within F32_PATH_TOL of
    the DenseBackend's float32 reference for the same token stream at
    ragged per-lane positions, under every policy preset, across maintain
    passes and a mid-stream lane release + re-prefill."""
    import jax.numpy as jnp
    from repro.core.policy import get_policy
    from repro.models import decode_step
    from repro.models.kv_backend import TieredBackend

    cfg, params = _smoke_model()
    kvs, tokens, ref_logits = _dense_reference()
    tiered = TieredBackend(cfg, _B, _MAX_LEN, page_tokens=8,
                           fast_data_slots=4,
                           policy=get_policy(preset, epoch_len=2))
    st = tiered.init_state(_B, _MAX_LEN)
    for (lane, n), (k, v) in zip(_PREFILLS[:2], kvs):
        st = tiered.write_prefill(st, lane, k, v, n)
    step = jax.jit(lambda p, s, t: decode_step(cfg, p, s, t,
                                               backend=tiered))
    maintain = jax.jit(lambda s: tiered.maintain(s, max_moves=3))
    release = jax.jit(tiered.release)
    for i in range(_STEPS):
        lt, st = step(params, st, jnp.asarray(tokens[i]))
        np.testing.assert_allclose(np.asarray(lt), ref_logits[i],
                                   **F32_PATH_TOL)
        if i % 3 == 2:
            st = maintain(st)
        if i == 6:                         # recycle lane 0 mid-stream
            lane, n = _PREFILLS[2]
            st = release(st, jnp.int32(lane))
            st = tiered.write_prefill(st, lane, *kvs[2], n)
    assert int(st.caches.migrations.sum()) + int(st.caches.demotions.sum()) > 0
    assert int(st.caches.dev_hits.sum()) > 0


def test_engine_dense_tiered_token_parity():
    """Engine level: the same request mix decoded with backend="tiered"
    yields token-for-token the dense engine's streams (scheduling is
    deterministic, logits are bit-identical)."""
    cfg, params = _smoke_model()

    def reqs():
        rng = np.random.default_rng(5)
        return [Request(rid=r, prompt=rng.integers(0, cfg.vocab, 3 + r % 3),
                        max_new=4 + (r % 2) * 4) for r in range(5)]

    outs = {}
    for kind in ("dense", "tiered"):
        eng = Engine(cfg, params, EngineConfig(
            batch=2, max_len=48, backend=kind, page_tokens=8,
            fast_data_slots=8, maintain_every=3))
        for r in reqs():
            eng.submit(r)
        done = eng.run()
        assert sorted(r.rid for r in done) == list(range(5))
        outs[kind] = {r.rid: r.tokens for r in done}
    assert outs["dense"] == outs["tiered"]


def test_engine_lane_recycle_releases_metadata():
    """Lane-recycle correctness at engine level: every finished request's
    pages leave the iRT / fast slots / iRC / device table (the
    ``release_seq`` invariants, driven by the engine's recycle path) —
    after the run every mapping is identity and no slot is owned."""
    import jax.numpy as jnp
    from repro.tiered import kvcache as tk

    cfg, params = _smoke_model()
    eng = Engine(cfg, params, EngineConfig(
        batch=2, max_len=48, backend="tiered", page_tokens=8,
        fast_data_slots=4, maintain_every=2))
    rng = np.random.default_rng(9)
    n = 5
    for rid in range(n):
        eng.submit(Request(rid=rid, prompt=rng.integers(0, cfg.vocab, 4),
                           max_new=10))
    done = eng.run()
    assert len(done) == n
    assert eng.releases == n               # one release per finished request
    st = eng.final_state.caches            # [L, ...] stacked TieredState
    t = eng.backend.tcfg
    assert (np.asarray(st.leaf_table) == tk.INVALID).all()
    assert (np.asarray(st.slot_owner) == tk.INVALID).all()
    assert (np.asarray(st.leaf_cnt) == 0).all()
    ident = t.fast_slots + np.arange(t.n_logical)
    dt_, dv = np.asarray(st.dev_table), np.asarray(st.dev_valid)
    assert (dt_[dv] == np.broadcast_to(ident, dt_.shape)[dv]).all()
    # the iRC agrees: a fresh lookup of every page resolves to identity
    ids = jnp.arange(t.n_logical).reshape(t.n_seqs, -1)
    layer0 = jax.tree.map(lambda x: x[0], st)
    table, _ = tk.lookup(t, layer0, ids)
    np.testing.assert_array_equal(np.asarray(table).reshape(-1), ident)
    # migration machinery actually ran during the serve
    assert eng.counters["migrations"] > 0
