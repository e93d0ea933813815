"""Attention: GQA self-attention (causal / sliding-window / bidirectional),
cross-attention (VLM), and single-token decode against a KV cache.

The reference path is pure jnp (this is also the dry-run/roofline path); the
Pallas flash/paged kernels in repro.kernels are drop-in replacements selected
by ``attn_impl`` (see kernels/*/ops.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.sharding.specs import logical_constraint

from .layers import Param, apply_rope, dense_init, zeros_init

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ArchConfig, cross: bool = False) -> dict:
    d, hd, H, KV = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    # fan-in is d for the projections in and H*hd for the one out, not the
    # second-minor dim dense_init would take: scaled by that, q.k grows
    # with d/H and softmax saturates into a hard, chaotic argmax
    s_in, s_out = d ** -0.5, (H * hd) ** -0.5
    p = {
        "wq": dense_init(ks[0], (d, H, hd), ("embed", "heads", None), dt,
                         scale=s_in),
        "wk": dense_init(ks[1], (d, KV, hd), ("embed", "kv_heads", None), dt,
                         scale=s_in),
        "wv": dense_init(ks[2], (d, KV, hd), ("embed", "kv_heads", None), dt,
                         scale=s_in),
        "wo": dense_init(ks[3], (H, hd, d), ("heads", None, "embed"), dt,
                         scale=s_out),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros_init((H, hd), ("heads", None), dt)
        p["bk"] = zeros_init((KV, hd), ("kv_heads", None), dt)
        p["bv"] = zeros_init((KV, hd), ("kv_heads", None), dt)
    if cross:
        # gated cross-attention (Llama-3.2-Vision style)
        p["gate"] = zeros_init((), (), jnp.float32)
        p["q_norm"] = Param(jnp.ones((hd,), dt), (None,))
        p["k_norm"] = Param(jnp.ones((hd,), dt), (None,))
    return p


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------

def make_mask(seq_q: int, seq_k: int, *, causal: bool, window: int = 0,
              q_offset: int = 0) -> jnp.ndarray:
    """[seq_q, seq_k] additive mask; window>0 limits lookback (SWA)."""
    qi = jnp.arange(seq_q)[:, None] + q_offset
    ki = jnp.arange(seq_k)[None, :]
    ok = jnp.ones((seq_q, seq_k), jnp.bool_)
    if causal:
        ok &= ki <= qi
    w = jnp.asarray(window, jnp.int32)      # may be traced (hybrid layers)
    ok &= (w <= 0) | (ki > qi - w)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# core attention (reference / XLA path)
# ---------------------------------------------------------------------------

def _qkv(p, x, cfg: ArchConfig, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if positions is not None:                    # RoPE (decoder archs)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask):
    """q [B,S,H,hd]; k,v [B,T,KV,hd]; GQA by head grouping."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, S, KV, G, hd)
    # scores accumulate AND land in float32: rounded to bf16 they carry a
    # half-ulp error that grows with their magnitude, which the softmax
    # then exponentiates
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    if mask is not None:
        scores = scores + mask
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", w, v)
    return out.reshape(B, S, H, hd)


def chunked_sdpa(q, k, v, *, causal: bool, window: int = 0,
                 q_chunk: int = 1024, k_chunk: int = 1024):
    """Flash-style online-softmax attention in pure XLA (the long-context
    reference path; the Pallas kernel in kernels/flash_attention mirrors
    this tiling).  Never materialises more than a [B,KV,G,qc,kc] score
    block.  q [B,S,H,hd]; k,v [B,T,KV,hd]."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qc = min(q_chunk, S)
    kc = min(k_chunk, T)
    nq, nk = S // qc, T // kc
    assert S % qc == 0 and T % kc == 0, (S, qc, T, kc)
    qr = q.reshape(B, nq, qc, KV, G, hd)
    kr = k.reshape(B, nk, kc, KV, hd)
    vr = v.reshape(B, nk, kc, KV, hd)
    scale = 1.0 / np.sqrt(hd)

    def q_block(qi, qb):
        # online softmax over key blocks
        def k_block(carry, ki_kb):
            m, l, acc = carry
            ki, kb, vb = ki_kb
            s = jnp.einsum("bqkgh,btkh->bkgqt", qb, kb,
                           preferred_element_type=jnp.float32)
            s = s * scale
            qpos = qi * qc + jnp.arange(qc)
            kpos = ki * kc + jnp.arange(kc)
            ok = jnp.ones((qc, kc), jnp.bool_)
            if causal:
                ok &= kpos[None, :] <= qpos[:, None]
            w = jnp.asarray(window, jnp.int32)
            ok &= (w <= 0) | (kpos[None, :] > qpos[:, None] - w)
            s = jnp.where(ok, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] \
                + jnp.einsum("bkgqt,btkh->bkgqh", p.astype(vb.dtype),
                             vb).astype(jnp.float32)
            return (m_new, l, acc), None

        m0 = jnp.full((B, KV, G, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, qc), jnp.float32)
        a0 = jnp.zeros((B, KV, G, qc, hd), jnp.float32)  # f32 accumulator
        (m, l, acc), _ = jax.lax.scan(
            k_block, (m0, l0, a0),
            (jnp.arange(nk), kr.swapaxes(0, 1), vr.swapaxes(0, 1)))
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        return out.transpose(0, 3, 1, 2, 4)          # [B,qc,KV,G,hd]

    outs = jax.lax.map(lambda args: q_block(*args),
                       (jnp.arange(nq), qr.swapaxes(0, 1)))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, hd)
    return out


CHUNKED_THRESHOLD = 4096  # plain quadratic path below this


def sdpa_auto(q, k, v, *, causal: bool, window: int = 0):
    S = q.shape[1]
    if S > CHUNKED_THRESHOLD:
        return chunked_sdpa(q, k, v, causal=causal, window=window)
    mask = make_mask(S, k.shape[1], causal=causal, window=window)
    return _sdpa(q, k, v, mask)


def self_attention(p, x, cfg: ArchConfig, *, positions, causal: bool,
                   window: int = 0, kernel=None):
    q, k, v = _qkv(p, x, cfg, positions)
    q = logical_constraint(q, ("batch", "seq", "heads", None))
    if kernel is not None:
        out = kernel(q, k, v, causal=causal, window=window)
    else:
        out = sdpa_auto(q, k, v, causal=causal, window=window)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return logical_constraint(y, ("batch", "seq", "embed_act")), (k, v)


def cross_attention(p, x, image_kv, cfg: ArchConfig):
    """x [B,S,d] attends to precomputed image K/V [B,T,KV,hd] (read-only
    after prefill: the tiered KV cold-tier candidate, DESIGN.md §4)."""
    from .layers import rms_norm
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"]
    q = rms_norm(q, p["q_norm"], cfg.rms_eps)
    k, v = image_kv
    out = _sdpa(q, k, v, None)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return jnp.tanh(p["gate"]).astype(x.dtype) * y


def image_kv(p, img_embeds, cfg: ArchConfig):
    """Precompute the cross-attention K/V from stubbed patch embeddings."""
    from .layers import rms_norm
    k = jnp.einsum("btd,dhk->bthk", img_embeds, p["wk"].astype(img_embeds.dtype))
    v = jnp.einsum("btd,dhk->bthk", img_embeds, p["wv"].astype(img_embeds.dtype))
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    return k, v


# ---------------------------------------------------------------------------
# decode: one new token per lane against a pluggable KV backend
# ---------------------------------------------------------------------------

def block_decode_attention(p, x, cfg: ArchConfig, cache, pos, backend,
                           *, window=0, ring: bool = False):
    """One block's decode attention through a ``KVBackend``.

    x [B,1,d]; ``cache`` is this layer's backend-owned slice; ``pos``
    [B] int32 — each lane's current length (ragged positions supported;
    a negative position marks an idle lane: nothing written, nothing
    read).  QKV + RoPE and the output projection live here; the storage
    round-trip (``append`` the new K/V, ``attend`` the query against
    everything stored) is the backend's.

    ``window`` may be a traced int32 (hybrid archs switch SWA/global per
    scanned layer); 0 means unlimited lookback.  ``ring=True``
    (hillclimb, EXPERIMENTS.md §Perf): the dense cache is a ring buffer
    of the SWA window — slot s holds absolute position
    ``pos - ((pos - s) mod S)``; reads are masked by the true window, so
    the math is identical to the full-length cache while the memory
    sweep shrinks from context-length to window-length.

    Returns (y [B,1,d], new cache slice).
    """
    positions = pos[:, None]                                   # [B, 1]
    q, k, v = _qkv(p, x, cfg, positions)
    B, _, H, hd = q.shape
    KV = k.shape[2]
    cache = backend.append(cache, k[:, 0], v[:, 0], pos, ring=ring)
    out, cache = backend.attend(cache, q.reshape(B, KV, H // KV, hd), pos,
                                window=window, ring=ring)
    y = jnp.einsum("bshk,hkd->bsd", out.reshape(B, 1, H, hd),
                   p["wo"].astype(x.dtype))
    return y, cache


def block_decode_attention_fused(p, x, cfg: ArchConfig, cache, pos, backend,
                                 *, aux):
    """Fused-path variant of ``block_decode_attention`` for backends with
    a ``begin_step``/``append_attend``/``end_step`` step protocol
    (``models.kv_backend.TieredBackend``): the backend attends the new
    token against its store *and* the new K/V row in one fused read — no
    append write lands on the attention's critical path.  The cache slice
    is read-only here; the new rows return as ``knv`` for the backend's
    batched ``end_step`` persist, and all metadata moved in
    ``begin_step``.  ``aux`` is the backend's per-step routing bundle.

    Returns (y [B,1,d], (k_new, v_new) [B,KV,hd] each).
    """
    positions = pos[:, None]                                   # [B, 1]
    q, k, v = _qkv(p, x, cfg, positions)
    B, _, H, hd = q.shape
    KV = k.shape[2]
    out = backend.append_attend(cache, q.reshape(B, KV, H // KV, hd),
                                k[:, 0], v[:, 0], pos, aux)
    y = jnp.einsum("bshk,hkd->bsd", out.reshape(B, 1, H, hd),
                   p["wo"].astype(x.dtype))
    return y, (k[:, 0], v[:, 0])
