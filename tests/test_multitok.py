"""Multi-token tiered decode (DESIGN.md §11): the fused k-token
append+attend path, the live-page attention bucket, and double-buffered
maintenance.

The contract under test everywhere: the fused k-token call is BITWISE
equal to k sequential single-token steps, no matter which policy preset
is migrating pages underneath (write-through makes the routing choice
invisible to the math), and the engine's overlapped maintenance changes
neither the token stream nor the end-state counters."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import PRESETS, get_policy
from repro.serve import tiered as srv
from repro.tiered import kvcache as tk

# two float32 code paths over the same math agree to rounding, not bit for
# bit: the misses seen on XLA:CPU were at most 1.3e-5 in magnitude
F32_PATH_TOL = dict(rtol=1e-5, atol=5e-5)


def _cfg(preset=None, **kw):
    base = dict(n_seqs=2, max_pages_per_seq=16, page_tokens=4,
                n_kv_heads=2, head_dim=8, fast_data_slots=4,
                dtype="float32")
    if preset is not None:
        base["policy"] = get_policy(preset, epoch_len=2)
        base["migrate_threshold"] = None
    base.update(kw)
    return tk.TieredConfig(**base)


def _filled(cfg, key):
    st = tk.init_state(cfg)
    return st._replace(
        slow_k=jax.random.normal(key, st.slow_k.shape, jnp.float32),
        slow_v=jax.random.normal(jax.random.fold_in(key, 1),
                                 st.slow_v.shape, jnp.float32))


def _qkv(cfg, key, k_tok, g=3):
    q = jax.random.normal(key, (cfg.n_seqs, k_tok, cfg.n_kv_heads, g,
                                cfg.head_dim))
    kn = jax.random.normal(jax.random.fold_in(key, 1),
                           (cfg.n_seqs, k_tok, cfg.n_kv_heads, cfg.head_dim))
    vn = jax.random.normal(jax.random.fold_in(key, 2), kn.shape)
    return q, kn, vn


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_attend_tokens_bitwise_vs_sequential(preset):
    """Fused k-token attend_tokens == k sequential single-token
    attend_tokens calls, bit for bit, under every policy preset — at
    ragged per-lane positions, across maintain passes (the two runs'
    tracker counters legitimately diverge: the fused call records one
    touch per live page per CALL, the sequential run one per token — so
    their migration choices may differ, and write-through must keep the
    outputs equal anyway) and across a mid-stream lane recycle."""
    cfg = _cfg(preset)
    key = jax.random.key(0)
    st_f = _filled(cfg, key)
    st_s = st_f
    K = 3
    pos = jnp.asarray([5, 2], jnp.int32)          # ragged lanes
    for rnd in range(4):
        q, kn, vn = _qkv(cfg, jax.random.fold_in(key, 10 + rnd), K)
        out_f, st_f = srv.attend_tokens(cfg, st_f, q, kn, vn, pos)
        outs = []
        for i in range(K):
            o, st_s = srv.attend_tokens(cfg, st_s, q[:, i:i + 1],
                                        kn[:, i:i + 1], vn[:, i:i + 1],
                                        pos + i)
            outs.append(o[:, 0])
        np.testing.assert_array_equal(np.asarray(out_f),
                                      np.asarray(jnp.stack(outs, axis=1)))
        st_f = srv.maintain(cfg, st_f, max_moves=3)
        st_s = srv.maintain(cfg, st_s, max_moves=3)
        if rnd == 1:                               # recycle lane 1 mid-run
            st_f = srv.release(cfg, st_f, 1)
            st_s = srv.release(cfg, st_s, 1)
            pos = jnp.asarray([int(pos[0]) + K, 0], jnp.int32)
        else:
            pos = pos + K


def test_attend_tokens_parked_lane_reads_nothing():
    """pos < 0 parks a lane: the fused call must neither write its rows
    nor heat its pages, and the live lane's output is unchanged by the
    parked lane's presence."""
    cfg = _cfg()
    key = jax.random.key(1)
    st = _filled(cfg, key)
    q, kn, vn = _qkv(cfg, jax.random.fold_in(key, 5), 2)
    pos_both = jnp.asarray([6, 3], jnp.int32)
    out_ref, _ = srv.attend_tokens(cfg, st, q, kn, vn, pos_both)
    pos = jnp.asarray([6, -1], jnp.int32)          # lane 1 parked
    before = np.asarray(st.slow_k).copy()
    out, st2 = srv.attend_tokens(cfg, st, q, kn, vn, pos)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out_ref[0]))
    # lane 1 wrote nothing anywhere
    half = cfg.max_pages_per_seq
    np.testing.assert_array_equal(np.asarray(st2.slow_k)[half:],
                                  before[half:])
    assert int(st2.touch[half:].sum()) == 0


@pytest.mark.parametrize("n_pages", [None, 8])
def test_attend_tokens_counts_fetched_pages(n_pages):
    """trimma_attn_pages_fetched_total after one fused call: each lane's
    pages under pos + K (the new rows included), none for a parked lane,
    at the full table width and at a live-page bucket alike."""
    cfg = _cfg(n_seqs=3)
    key = jax.random.key(4)
    st = _filled(cfg, key)
    K = 2
    q, kn, vn = _qkv(cfg, jax.random.fold_in(key, 9), K)
    pos = jnp.asarray([9, -1, 29], jnp.int32)      # pages: 3, 0, 8
    _, st2 = srv.attend_tokens(cfg, st, q, kn, vn, pos, n_pages=n_pages)
    m = srv.metrics(cfg, st2)
    assert int(m["trimma_attn_pages_fetched_total"]) == 3 + 0 + 8


def test_decode_step_counts_fetched_pages_per_layer():
    """Layer-stacked backend: one decode step's metadata pass counts each
    lane's attended pages once per layer (0 for a parked lane)."""
    from repro.configs import get_config, reduce_for_smoke
    from repro.models.kv_backend import TieredBackend

    cfg = reduce_for_smoke(get_config("llama3-8b"))
    B, max_len = 3, 64
    bk = TieredBackend(cfg, B, max_len, page_tokens=8, fast_data_slots=4)
    state = bk.init_state(B, max_len)
    pos = jnp.asarray([20, -1, 7], jnp.int32)      # pages: 3, 0, 1
    caches, _ = bk.begin_step(state.caches, pos, n_pages=4)
    m = bk.metrics(state._replace(caches=caches))
    assert m["trimma_attn_pages_fetched_total"] == cfg.n_layers * (3 + 0 + 1)


def _np_attend_tokens(cfg, st, q, kn, vn, pos):
    """Plain float64 NumPy reference of the k-token read on a store with
    no fast-resident page: each lane's pages from its identity slow
    homes, the new rows overlaid at pos..pos+K-1, token i attending
    positions < pos+1+i."""
    B, K, KV, G, hd = q.shape
    NP, P = cfg.max_pages_per_seq, cfg.page_tokens

    def seq(pool, new):
        x = np.asarray(pool, np.float64).reshape(B, NP, KV, P, hd)
        x = x.transpose(0, 2, 1, 3, 4).reshape(B, KV, NP * P, hd).copy()
        for b in range(B):
            for i in range(K):
                x[b, :, int(pos[b]) + i] = np.asarray(new[b, i], np.float64)
        return x

    k_seq, v_seq = seq(st.slow_k, kn), seq(st.slow_v, vn)
    qq = np.asarray(q, np.float64)
    out = np.zeros(q.shape)
    for b in range(B):
        for i in range(K):
            n = int(pos[b]) + 1 + i
            s = np.einsum("kgh,kth->kgt", qq[b, i],
                          k_seq[b, :, :n]) / np.sqrt(hd)
            w = np.exp(s - s.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            out[b, i] = np.einsum("kgt,kth->kgh", w, v_seq[b, :, :n])
    return out


def test_attend_tokens_bucket_bitwise():
    """The live-page attention bucket (n_pages) is invisible: the bucketed
    and the full-width read both agree with a NumPy reference within
    F32_PATH_TOL (they reduce over different lengths, so not bit for
    bit), and they leave the same updated state, bit for bit, provided
    every live/appended position fits in the bucket."""
    cfg = _cfg()
    key = jax.random.key(2)
    st = _filled(cfg, key)
    K = 2
    q, kn, vn = _qkv(cfg, jax.random.fold_in(key, 7), K)
    pos = jnp.asarray([9, 4], jnp.int32)           # fits in 4 pages of 4
    out_full, st_full = srv.attend_tokens(cfg, st, q, kn, vn, pos)
    out_b, st_b = srv.attend_tokens(cfg, st, q, kn, vn, pos, n_pages=4)
    ref = _np_attend_tokens(cfg, st, q, kn, vn, np.asarray(pos))
    np.testing.assert_allclose(np.asarray(out_full), ref, **F32_PATH_TOL)
    np.testing.assert_allclose(np.asarray(out_b), ref, **F32_PATH_TOL)
    for a, b in zip(jax.tree.leaves(st_full), jax.tree.leaves(st_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_step_bucket_logits_identical():
    """Model level: decode_step with the live-page bucket and the
    unbucketed step both produce logits within F32_PATH_TOL of the dense
    float32 reference fed the same token stream."""
    from repro.configs import get_config, reduce_for_smoke
    from repro.models import decode_step, init_params
    from repro.models.kv_backend import DenseBackend, TieredBackend

    cfg = reduce_for_smoke(get_config("llama3-8b"))
    params = init_params(cfg, jax.random.key(0))
    B, max_len = 2, 64
    bk = TieredBackend(cfg, B, max_len, page_tokens=8, fast_data_slots=4)
    st_a = bk.init_state(B, max_len)
    st_b = st_a
    rng = np.random.default_rng(3)
    tok = jnp.asarray(rng.integers(0, cfg.vocab, (B,)), jnp.int32)
    step_full = jax.jit(lambda p, s, t: decode_step(cfg, p, s, t,
                                                    backend=bk))
    step_bkt = jax.jit(lambda p, s, t: decode_step(cfg, p, s, t,
                                                   backend=bk, n_pages=2))
    dense = DenseBackend(cfg)
    st_d = dense.init_state(B, max_len)
    step_dense = jax.jit(lambda p, s, t: decode_step(cfg, p, s, t,
                                                     backend=dense))
    for i in range(6):
        la, st_a = step_full(params, st_a, tok)
        lb, st_b = step_bkt(params, st_b, tok)
        ld, st_d = step_dense(params, st_d, tok)
        np.testing.assert_allclose(np.asarray(la), np.asarray(ld),
                                   **F32_PATH_TOL)
        np.testing.assert_allclose(np.asarray(lb), np.asarray(ld),
                                   **F32_PATH_TOL)
        tok = jnp.argmax(ld, -1).astype(jnp.int32)


def test_engine_overlap_maintain_identity():
    """Double-buffered maintenance (EngineConfig.overlap_maintain): the
    overlapped plan applies one step late, which write-through makes
    invisible — identical token streams AND identical end-state
    migration counters vs synchronous maintenance."""
    from repro.configs import get_config, reduce_for_smoke
    from repro.models import init_params
    from repro.serve.engine import Engine, EngineConfig, Request

    cfg = reduce_for_smoke(get_config("llama3-8b"))
    params = init_params(cfg, jax.random.key(0))

    def reqs():
        rng = np.random.default_rng(5)
        return [Request(rid=r, prompt=rng.integers(0, cfg.vocab, 3 + r % 3),
                        max_new=4 + (r % 2) * 4) for r in range(5)]

    runs = {}
    for overlap in (False, True):
        eng = Engine(cfg, params, EngineConfig(
            batch=2, max_len=48, backend="tiered", page_tokens=8,
            fast_data_slots=8, maintain_every=3, overlap_maintain=overlap))
        for r in reqs():
            eng.submit(r)
        done = eng.run()
        runs[overlap] = ({r.rid: r.tokens for r in done},
                         {k: eng.counters[k] for k in
                          ("migrations", "demotions")})
    assert runs[False][0] == runs[True][0]         # token streams
    assert runs[False][1] == runs[True][1]         # end-state counters
    assert runs[True][1]["migrations"] + runs[True][1]["demotions"] > 0


def test_tiered_backend_rejects_window_and_ring():
    """Unsupported attention kwargs fail loudly instead of silently
    returning full-context attention."""
    from repro.configs import get_config, reduce_for_smoke
    from repro.models.kv_backend import TieredBackend

    cfg = reduce_for_smoke(get_config("llama3-8b"))
    bk = TieredBackend(cfg, 2, 64, page_tokens=8)
    st = bk.init_state(2, 64)
    cache = jax.tree.map(lambda x: x[0], st.caches)
    q = jnp.zeros((2, bk.tcfg.n_kv_heads, 2, bk.tcfg.head_dim))
    kv = jnp.zeros((2, bk.tcfg.n_kv_heads, bk.tcfg.head_dim))
    pos = jnp.zeros((2,), jnp.int32)
    with pytest.raises(NotImplementedError):
        bk.attend(cache, q, pos, window=4)
    with pytest.raises(NotImplementedError):
        bk.attend(cache, q, pos, ring=True)
    with pytest.raises(NotImplementedError):
        bk.append(cache, kv, kv, pos, ring=True)
    swcfg = dataclasses.replace(cfg, sliding_window=8)
    with pytest.raises(NotImplementedError):
        TieredBackend(swcfg, 2, 64, page_tokens=8)
