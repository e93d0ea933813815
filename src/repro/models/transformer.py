"""Model assembly: all assigned families on one scan-over-layers skeleton.

Families (configs/base.py):
  dense  — pre-norm GQA transformer (llama3 / qwen2 / codeqwen)
  moe    — dense attention + top-k expert FFN (granite, mixtral w/ SWA)
  hybrid — Hymba: parallel attention + Mamba heads per block
  ssm    — xLSTM: alternating mLSTM/sLSTM blocks, attention-free
  audio  — HuBERT: bidirectional encoder over stubbed frame embeddings
  vlm    — Llama-3.2-Vision: 20 super-blocks of (4 self-attn + 1 cross-attn)

The layer stack is scanned (compile-time O(1) in depth) with stacked
parameters; per-layer heterogeneity is expressed through *scanned flag
arrays* (hybrid: global-vs-SWA; ssm: mLSTM-vs-sLSTM) or through super-block
structure (vlm), keeping the pytree homogeneous.

Three entry points per arch:
  forward(cfg, params, batch)                 -> (logits, aux)   train
  prefill(cfg, params, batch)                 -> (logits, DecodeState)
  decode_step(cfg, params, state, tokens)     -> (logits, DecodeState)
"""

from __future__ import annotations

import functools
import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.sharding.specs import logical_constraint

from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from . import xlstm as xlstm_mod
from .layers import (Param, dense_init, embed_init, gelu_mlp, key_for,
                     ones_init, rms_norm, split_tree, swiglu, unembed,
                     zeros_init)


# ---------------------------------------------------------------------------
# per-layer parameter init
# ---------------------------------------------------------------------------

def _mlp_init(key, cfg: ArchConfig) -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    if cfg.family == "audio":   # GELU MLP with biases (HuBERT)
        return {
            "w_in": dense_init(ks[0], (d, ff), ("embed", "mlp"), dt),
            "b_in": zeros_init((ff,), ("mlp",), dt),
            "w_out": dense_init(ks[1], (ff, d), ("mlp", "embed"), dt),
            "b_out": zeros_init((d,), ("embed",), dt),
        }
    return {
        "w_gate": dense_init(ks[0], (d, ff), ("embed", "mlp"), dt),
        "w_up": dense_init(ks[1], (d, ff), ("embed", "mlp"), dt),
        "w_down": dense_init(ks[2], (ff, d), ("mlp", "embed"), dt),
    }


def _block_init(key, cfg: ArchConfig, cross: bool = False) -> dict:
    dt = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    p: dict[str, Any] = {
        "norm1": ones_init((d,), ("embed",), dt),
    }
    if cfg.family == "ssm":
        p.update(xlstm_mod.xlstm_init(key_for(key, "xlstm"), cfg))
        return p
    p["attn"] = attn.attn_init(key_for(key, "attn"), cfg, cross=cross)
    p["norm2"] = ones_init((d,), ("embed",), dt)
    if cfg.family == "hybrid":
        p["ssm"] = ssm_mod.ssm_init(key_for(key, "ssm"), cfg)
        p["norm_attn_out"] = ones_init((d,), ("embed",), dt)
        p["norm_ssm_out"] = ones_init((d,), ("embed",), dt)
    if cfg.family == "moe":
        p["moe"] = moe_mod.moe_init(key_for(key, "moe"), cfg)
    elif cfg.d_ff:
        p["mlp"] = _mlp_init(key_for(key, "mlp"), cfg)
    return p


def _stack_layers(key, cfg: ArchConfig, n: int, cross: bool = False):
    # one vmapped init over the layer keys: each layer draws exactly what
    # its own _block_init call would, and the program holds one block's
    # ops instead of n copies
    keys = jnp.stack([key_for(key, "layer", i) for i in range(n)])
    layers = jax.vmap(lambda k: _block_init(k, cfg, cross))(keys)
    return jax.tree.map(lambda p: Param(p.value, ("layers",) + p.axes),
                        layers, is_leaf=lambda x: isinstance(x, Param))


def _build_param_tree(cfg: ArchConfig, key) -> dict:
    dt = jnp.dtype(cfg.dtype)
    tree: dict[str, Any] = {}
    if not cfg.embed_inputs:
        tree["embed"] = embed_init(key_for(key, "embed"), cfg.vocab,
                                   cfg.d_model, dt)
    if cfg.family == "vlm":
        ns = cfg.n_layers // cfg.cross_attn_every       # super-blocks (20)
        inner = cfg.cross_attn_every - 1                # self layers each (4)
        self_stack = _stack_layers(key_for(key, "self"), cfg, ns * inner)
        # reshape leading dim [ns*inner, ...] -> [ns, inner, ...]
        self_stack = jax.tree.map(
            lambda p: Param(p.value.reshape((ns, inner) + p.value.shape[1:]),
                            ("layers",) + p.axes),
            self_stack, is_leaf=lambda x: isinstance(x, Param))
        cross_stack = _stack_layers(key_for(key, "cross"), cfg, ns, cross=True)
        tree["blocks"] = {"self": self_stack, "cross": cross_stack}
    else:
        tree["blocks"] = _stack_layers(key_for(key, "blocks"), cfg,
                                       cfg.n_layers)
    tree["final_norm"] = ones_init((cfg.d_model,), ("embed",), dt)
    if not cfg.tie_embeddings:
        tree["unembed"] = embed_init(key_for(key, "unembed"), cfg.vocab,
                                     cfg.d_model, dt)
    return tree


def init_params_and_axes(cfg: ArchConfig, key) -> tuple[dict, dict]:
    return split_tree(_build_param_tree(cfg, key))


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ArchConfig, key) -> dict:
    """Parameter values drawn from ``key``, built as one compiled program:
    built eagerly, ``_stack_layers`` holds every layer twice (the per-layer
    arrays and their stack), which a full-width model cannot afford on
    one chip."""
    return init_params_and_axes(cfg, key)[0]


def abstract_params_and_axes(cfg: ArchConfig) -> tuple[dict, dict]:
    """(ShapeDtypeStruct tree, logical-axes tree) — nothing materialised.
    Param's axes ride along as static pytree aux data, so this works for
    arbitrarily large configs (the 72B/90B dry-run path)."""
    tree = jax.eval_shape(functools.partial(_build_param_tree, cfg),
                          jax.random.key(0))
    return split_tree(tree)


# ---------------------------------------------------------------------------
# per-layer flags (scanned arrays expressing heterogeneity)
# ---------------------------------------------------------------------------

def layer_flags(cfg: ArchConfig) -> jnp.ndarray:
    L = cfg.n_layers
    if cfg.family == "hybrid" and cfg.global_attn_every:
        return (np.arange(L) % cfg.global_attn_every == 0)
    if cfg.family == "ssm":
        every = max(cfg.slstm_every, 1)
        return (np.arange(L) % every == every - 1)      # every Nth is sLSTM
    return np.zeros((L,), bool)


# ---------------------------------------------------------------------------
# forward blocks (full-sequence: train / prefill)
# ---------------------------------------------------------------------------

def _window_for(cfg: ArchConfig, is_global) -> int:
    return 0 if is_global else cfg.sliding_window


def _block_fwd(cfg: ArchConfig, p, x, positions, flag, *, collect_cache):
    """One decoder/encoder block.  Returns (x, aux, cache_kv)."""
    aux = jnp.float32(0.0)
    h = rms_norm(x, p["norm1"], cfg.rms_eps)
    cache = ()
    if cfg.family == "ssm":
        def do_slstm(h):
            return xlstm_mod.slstm_scan(p, h)

        def do_mlstm(h):
            return xlstm_mod.mlstm_parallel(p, h)
        x = x + jax.lax.cond(flag, do_slstm, do_mlstm, h)
        return x, aux, cache

    if cfg.family == "hybrid":
        # global layers use full attention, the rest SWA; the flag is a
        # traced scanned value, folded into the (traced) window argument
        window = jnp.where(flag, 0, cfg.sliding_window)
        q, k, v = attn._qkv(p["attn"], h, cfg, positions)
        out = attn.sdpa_auto(q, k, v, causal=True, window=window)
        a = jnp.einsum("bshk,hkd->bsd", out, p["attn"]["wo"].astype(x.dtype))
        xz = h @ p["ssm"]["in_proj"].astype(h.dtype)
        s = ssm_mod.ssm_scan(p["ssm"], xz, cfg)
        x = x + rms_norm(a, p["norm_attn_out"], cfg.rms_eps) \
            + rms_norm(s, p["norm_ssm_out"], cfg.rms_eps)
        cache = (k, v) if collect_cache else ()
    else:
        causal = cfg.causal
        window = cfg.sliding_window
        a, (k, v) = attn.self_attention(p["attn"], h, cfg,
                                        positions=positions if causal else None,
                                        causal=causal, window=window)
        x = x + a
        cache = (k, v) if collect_cache else ()

    h2 = rms_norm(x, p["norm2"], cfg.rms_eps)
    if cfg.family == "moe":
        y, aux = moe_mod.moe_ffn(p["moe"], h2, cfg)
    elif cfg.family == "audio":
        y = gelu_mlp(h2, p["mlp"]["w_in"], p["mlp"]["b_in"],
                     p["mlp"]["w_out"], p["mlp"]["b_out"])
    else:
        y = swiglu(h2, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                   p["mlp"]["w_down"])
    return x + y, aux, cache


def _cross_block_fwd(cfg: ArchConfig, p, x, image_embeds):
    h = rms_norm(x, p["norm1"], cfg.rms_eps)
    ikv = attn.image_kv(p["attn"], image_embeds, cfg)
    x = x + attn.cross_attention(p["attn"], h, ikv, cfg)
    h2 = rms_norm(x, p["norm2"], cfg.rms_eps)
    y = swiglu(h2, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"])
    return x + y, ikv


REMAT_POLICIES = {
    "none": None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}


def _scan_unroll():
    """Full layer-scan unroll for the dry-run (REPRO_SCAN_UNROLL=1): XLA
    cost_analysis counts a while-loop body once, so roofline accounting
    needs the unrolled graph; normal execution keeps the rolled scan."""
    return os.environ.get("REPRO_SCAN_UNROLL", "0") == "1"


def forward(cfg: ArchConfig, params, batch, *, remat: str = "none",
            collect_cache: bool = False):
    """Full-sequence forward.  batch: {"tokens" [B,S] | "embeds" [B,S,d],
    optional "image_embeds" [B,T,d]}.  Returns (logits, aux, caches)."""
    if cfg.embed_inputs:
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
    x = logical_constraint(x, ("batch", "seq", "embed_act"))
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    if cfg.family == "vlm":
        x, aux, caches = _vlm_forward(cfg, params, x, positions,
                                      batch["image_embeds"], remat,
                                      collect_cache)
    else:
        flags = jnp.asarray(layer_flags(cfg))

        def body(carry, layer):
            xx, aux = carry
            p, flag = layer
            xx = logical_constraint(xx, ("batch", "seq", "embed_act"))
            xx, aux_l, cache = _block_fwd(cfg, p, xx, positions, flag,
                                          collect_cache=collect_cache)
            return (xx, aux + aux_l), cache

        if remat != "none":
            body = jax.checkpoint(body, policy=REMAT_POLICIES[remat],
                                  prevent_cse=False)
        (x, aux), caches = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                        (params["blocks"], flags),
                                        unroll=_scan_unroll())

    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(x, table)
    return logits, aux, caches


def _vlm_forward(cfg, params, x, positions, image_embeds, remat,
                 collect_cache):
    def super_block(carry, p_sb):
        xx, aux = carry
        p_self, p_cross = p_sb

        def inner(xc, p):
            xc, a, cache = _block_fwd(cfg, p, xc, positions, False,
                                      collect_cache=collect_cache)
            return xc, cache

        xx, self_caches = jax.lax.scan(inner, xx, p_self,
                                       unroll=_scan_unroll())
        xx, ikv = _cross_block_fwd(cfg, p_cross, xx, image_embeds)
        cache = (self_caches, ikv if collect_cache else ())
        return (xx, aux), cache

    if remat != "none":
        super_block = jax.checkpoint(super_block,
                                     policy=REMAT_POLICIES[remat],
                                     prevent_cse=False)
    (x, aux), caches = jax.lax.scan(
        super_block, (x, jnp.float32(0.0)),
        (params["blocks"]["self"], params["blocks"]["cross"]),
        unroll=_scan_unroll())
    return x, aux, caches


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def lm_loss(cfg: ArchConfig, params, batch, *, remat: str = "none"):
    """Next-token CE for decoders; frame classification for encoders.
    Adds MoE load-balance aux (1e-2) and z-loss (1e-4).

    REPRO_SHARDED_CE=1 (hillclimb, EXPERIMENTS.md §Perf): keep the logits
    vocab-sharded end to end.  The baseline take_along_axis over the vocab
    axis makes XLA all-gather the [B,S,V] f32 logits (the dominant
    collective in every LM train cell); the sharded form reduces only
    [B,S]-sized partials (max / sum-exp / label pick), ~V/shards x less
    wire traffic."""
    logits, aux, _ = forward(cfg, params, batch, remat=remat)
    labels = batch["labels"]
    if cfg.causal:
        logits = logits[:, :-1]
        labels = labels[:, 1:]
    if os.environ.get("REPRO_SHARDED_CE", "0") == "1":
        logits = logical_constraint(logits, ("batch", None, "vocab"))
        m = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
        # elementwise over the sharded vocab axis; reductions are [B,S]
        sumexp = jnp.exp(logits - m).sum(axis=-1)
        vpos = jnp.arange(logits.shape[-1])[None, None, :]
        lab_logit = jnp.where(vpos == labels[..., None], logits, 0.0).sum(-1)
        lse = jnp.log(sumexp) + m[..., 0]
        ce = (lse - lab_logit).mean()
        z = jnp.square(lse).mean()
    else:
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()
        z = jnp.square(jax.nn.logsumexp(logits, axis=-1)).mean()
    return ce + 1e-2 * aux + 1e-4 * z, {"ce": ce, "aux": aux, "z": z}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

class DecodeState(NamedTuple):
    pos: jnp.ndarray          # [B] int32: tokens already in cache, per
                              # lane (ragged — lanes decode independently;
                              # negative marks an idle lane)
    caches: Any               # backend-owned pytree, layer-stacked


def _ring_cache_len(cfg: ArchConfig, max_len: int) -> int:
    """REPRO_WINDOW_CACHE=1 + all-SWA arch: cache = the window, not the
    context (hillclimb; mixtral long_500k goes from O(S) to O(W) KV)."""
    if (os.environ.get("REPRO_WINDOW_CACHE", "0") == "1"
            and cfg.sliding_window > 0 and cfg.global_attn_every == 0
            and cfg.family in ("dense", "moe")):
        return min(max_len, cfg.sliding_window)
    return max_len


def init_decode_state(cfg: ArchConfig, batch: int, max_len: int) -> DecodeState:
    dt = jnp.dtype(cfg.dtype)
    KV, hd, L = cfg.n_kv_heads, cfg.hd, cfg.n_layers
    max_len = _ring_cache_len(cfg, max_len)
    kv = lambda: jnp.zeros((L, batch, max_len, KV, hd), dt)  # noqa: E731
    if cfg.family == "ssm":
        H = cfg.n_heads
        dh = cfg.d_model // H
        caches = {
            "mC": jnp.zeros((L, batch, H, dh, dh), jnp.float32),
            "mn": jnp.zeros((L, batch, H, dh), jnp.float32),
            "mm": jnp.zeros((L, batch, H), jnp.float32),
            "s": jax.tree.map(lambda x: jnp.zeros((L,) + x.shape, x.dtype),
                              xlstm_mod.slstm_state_init(batch, H, dh)),
        }
    elif cfg.family == "hybrid":
        caches = {"k": kv(), "v": kv(),
                  "ssm": jax.tree.map(
                      lambda x: jnp.zeros((L,) + x.shape, x.dtype),
                      ssm_mod.ssm_state_init(cfg, batch))}
    elif cfg.family == "vlm":
        ns = cfg.n_layers // cfg.cross_attn_every
        inner = cfg.cross_attn_every - 1
        caches = {
            "k": jnp.zeros((ns, inner, batch, max_len, KV, hd), dt),
            "v": jnp.zeros((ns, inner, batch, max_len, KV, hd), dt),
            "ik": jnp.zeros((ns, batch, cfg.n_image_tokens, KV, hd), dt),
            "iv": jnp.zeros((ns, batch, cfg.n_image_tokens, KV, hd), dt),
        }
    else:
        caches = {"k": kv(), "v": kv()}
    return DecodeState(jnp.zeros((batch,), jnp.int32), caches)


def _block_decode(cfg: ArchConfig, p, x, cache, pos, flag, backend):
    """One block, one token per lane.  cache: this layer's backend-owned
    slice; pos [B] per-lane positions."""
    h = rms_norm(x, p["norm1"], cfg.rms_eps)
    if cfg.family == "ssm":
        def do_s(h):
            out, s = xlstm_mod.slstm_step(p, h, cache["s"])
            return out, {**cache, "s": s}

        def do_m(h):
            out, (C, n, m) = _mlstm_step_tuple(p, h, cache)
            return out, {**cache, "mC": C, "mn": n, "mm": m}
        out, cache = jax.lax.cond(flag, do_s, do_m, h)
        return x + out, cache

    # SWA semantics are part of the model: mask out-of-window keys.  The
    # cache itself stays full-length in the baseline (ring-buffer compaction
    # is a recorded hillclimb optimisation).
    if cfg.family == "hybrid":
        window = jnp.where(flag, 0, cfg.sliding_window)   # traced per layer
        ring = False
    else:
        window = cfg.sliding_window
        ring = backend.is_ring(cache)
    a, new_cache = attn.block_decode_attention(
        p["attn"], h, cfg, cache, pos, backend, window=window, ring=ring)
    if cfg.family == "hybrid":
        xz = h @ p["ssm"]["in_proj"].astype(h.dtype)
        s_out, s_state = ssm_mod.ssm_step(p["ssm"], xz, cache["ssm"], cfg)
        new_cache["ssm"] = s_state
        x = x + rms_norm(a, p["norm_attn_out"], cfg.rms_eps) \
            + rms_norm(s_out, p["norm_ssm_out"], cfg.rms_eps)
    else:
        x = x + a
    h2 = rms_norm(x, p["norm2"], cfg.rms_eps)
    if cfg.family == "moe":
        y, _ = moe_mod.moe_ffn(p["moe"], h2, cfg)
    else:
        y = swiglu(h2, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"])
    return x + y, new_cache


def _block_decode_fused(cfg: ArchConfig, p, x, cache, pos, backend, aux):
    """``_block_decode`` on the fused decode path (plain-KV families
    only): attention goes through the backend's fused append+attend read,
    the new K/V rows ride out as scan ys for the batched end-of-step
    persist, and the cache slice itself is read-only."""
    h = rms_norm(x, p["norm1"], cfg.rms_eps)
    a, knv = attn.block_decode_attention_fused(p["attn"], h, cfg, cache,
                                               pos, backend, aux=aux)
    x = x + a
    h2 = rms_norm(x, p["norm2"], cfg.rms_eps)
    if cfg.family == "moe":
        y, _ = moe_mod.moe_ffn(p["moe"], h2, cfg)
    else:
        y = swiglu(h2, p["mlp"]["w_gate"], p["mlp"]["w_up"], p["mlp"]["w_down"])
    return x + y, knv


def _mlstm_step_tuple(p, x, cache):
    out, st = xlstm_mod.mlstm_step(p, x, {"C": cache["mC"], "n": cache["mn"],
                                          "m": cache["mm"]})
    return out, (st["C"], st["n"], st["m"])


def decode_step(cfg: ArchConfig, params, state: DecodeState, tokens,
                backend=None, *, n_pages: int | None = None):
    """tokens [B] int32 -> (logits [B, vocab], new state).

    ``backend`` selects the KV storage (``models.kv_backend``): None /
    ``DenseBackend`` keeps today's contiguous caches; ``TieredBackend``
    decodes every attention layer through its own Trimma-managed
    two-tier store — same logits, bit for bit.

    ``n_pages`` (static, fused tiered path only) is the live-page
    attention bucket (DESIGN.md §11): each layer's fused read covers only
    that page prefix instead of ``max_len``; the caller must guarantee it
    holds every live position plus this step's append.  Bit-identical to
    the full-width read — the truncated tail is fully masked."""
    if backend is None:
        from .kv_backend import DenseBackend
        backend = DenseBackend(cfg)
    x = jnp.take(params["embed"], tokens[:, None], axis=0)
    x = logical_constraint(x, ("batch", None, "embed_act"))
    pos = state.pos
    flags = jnp.asarray(layer_flags(cfg))

    if cfg.family == "vlm":
        x, caches = _vlm_decode(cfg, params, x, state, backend)
    elif cfg.family in ("dense", "moe") and hasattr(backend, "begin_step"):
        # fused decode path: the backend hoists all per-step metadata work
        # into ONE stacked begin_step, every layer's attention is a single
        # fused append+attend kernel (no append write on the critical
        # path), and the new K/V rows persist in one batched end_step
        caches, aux = backend.begin_step(state.caches, pos,
                                         n_pages=n_pages)

        def body(x, layer):
            p, flag, cache = layer
            x, knv = _block_decode_fused(cfg, p, x, cache, pos, backend,
                                         aux)
            return x, knv

        # the scan slices only the pool arrays per layer (scan_operands):
        # routing/translation ride in aux and metadata stays outside, so
        # the body never pays per-layer slices of fields it doesn't read
        x, knv = jax.lax.scan(body, x,
                              (params["blocks"], flags,
                               backend.scan_operands(caches)),
                              unroll=_scan_unroll())
        caches = backend.end_step(caches, knv, pos, aux)
    else:
        def body(x, layer):
            p, flag, cache = layer
            x, new_cache = _block_decode(cfg, p, x, cache, pos, flag,
                                         backend)
            return x, new_cache

        x, caches = jax.lax.scan(body, x,
                                 (params["blocks"], flags, state.caches),
                                 unroll=_scan_unroll())

    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(x, table)[:, 0]
    return logits, DecodeState(pos + 1, caches)


def _vlm_decode(cfg, params, x, state: DecodeState, backend):
    pos = state.pos

    def super_block(x, layer):
        p_self, p_cross, ck, cv, ik, iv = layer

        def inner(x, l):
            p, k, v = l
            xx, cache = _block_decode(cfg, p, x, {"k": k, "v": v}, pos,
                                      False, backend)
            return xx, (cache["k"], cache["v"])

        x, (nk, nv) = jax.lax.scan(inner, x, (p_self, ck, cv),
                                   unroll=_scan_unroll())
        h = rms_norm(x, p_cross["norm1"], cfg.rms_eps)
        x = x + attn.cross_attention(p_cross["attn"], h, (ik, iv), cfg)
        h2 = rms_norm(x, p_cross["norm2"], cfg.rms_eps)
        x = x + swiglu(h2, p_cross["mlp"]["w_gate"], p_cross["mlp"]["w_up"],
                       p_cross["mlp"]["w_down"])
        return x, (nk, nv)

    c = state.caches
    x, (nk, nv) = jax.lax.scan(
        super_block, x,
        (params["blocks"]["self"], params["blocks"]["cross"],
         c["k"], c["v"], c["ik"], c["iv"]),
        unroll=_scan_unroll())
    return x, {**c, "k": nk, "v": nv}


# ---------------------------------------------------------------------------
# chunked prefill: one chunk of prompt K/V against a full-length key buffer
# ---------------------------------------------------------------------------

_CHUNK_FAMILIES = ("dense", "moe")


def forward_chunk(cfg: ArchConfig, params, tokens, buf_k, buf_v, start,
                  *, return_logits: bool = False):
    """One chunked-prefill step: compute K/V (and hidden math) for prompt
    tokens ``[start, start + C)`` attending to the previous chunks' K/V.

    tokens        [B, C] int32 chunk at absolute positions start..start+C-1
    buf_k, buf_v  [L, B, P, KV, hd] per-layer key/value buffers; rows
                  ``< start`` hold the previous chunks' K/V, later rows are
                  garbage (masked below).  ``P`` must equal the padded
                  length the one-shot ``forward`` would run at.
    start         traced int32, page/chunk aligned by the caller.

    Returns the updated (buf_k, buf_v) with rows [start, start+C) written;
    with ``return_logits=True``, (buf_k, buf_v, logits [B, C, vocab]) —
    the chunk rows' output logits, each bit-identical to the same row of
    the one-shot ``forward`` (the hidden states are, by the same
    induction as the K/V rows below), so the scheduler can emit an
    admitted prompt's first token straight off its final chunk with no
    extra decode step.

    Bit-identicality contract (tests/test_sched.py pins it): because each
    chunk's queries score against a key axis of the SAME length ``P`` the
    one-shot forward uses — prefix rows bitwise equal by induction, later
    rows additively masked to exact zeros (finite garbage + NEG_INF
    underflows to 0 in the softmax) — every per-position reduction has the
    same length, values and order as in ``forward(collect_cache=True)``,
    so the chunk K/V rows (and hence the downstream decode logits) are
    bit-identical to the one-shot prefill.  Total attention compute over
    all chunks is the one-shot C*P sum; per-step compute is bounded by one
    chunk (the chunk-budget math, DESIGN.md §9).

    Only the plain-KV decoder families qualify (the engine's prefill
    families); MoE is exact as long as routing stays under capacity —
    ``moe.capacity`` scales with the token count, so a chunk can only have
    MORE headroom than the one-shot pass (drops, when they happen at all,
    can differ; the smoke configs never drop).
    """
    if cfg.family not in _CHUNK_FAMILIES:
        raise NotImplementedError(
            f"forward_chunk supports plain-KV decoder families "
            f"{_CHUNK_FAMILIES}; got {cfg.family!r}")
    B, C = tokens.shape
    P = buf_k.shape[2]
    if P > attn.CHUNKED_THRESHOLD:
        # above the threshold the one-shot forward switches to the
        # online-softmax chunked_sdpa whose accumulation order differs —
        # plain _sdpa here would break the bit-identicality contract
        # (the scheduler falls back to one-shot prefill instead)
        raise NotImplementedError(
            f"forward_chunk is bit-identical to the one-shot forward only "
            f"below sdpa_auto's CHUNKED_THRESHOLD "
            f"({attn.CHUNKED_THRESHOLD}); padded length {P} exceeds it")
    x = jnp.take(params["embed"], tokens, axis=0)
    x = logical_constraint(x, ("batch", "seq", "embed_act"))
    start = jnp.asarray(start, jnp.int32)
    positions = start + jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32),
                                         (B, C))
    # same mask construction as the one-shot path (make_mask rows at
    # q_offset = start), window included for SWA archs
    mask = attn.make_mask(C, P, causal=cfg.causal, window=cfg.sliding_window,
                          q_offset=start)
    flags = jnp.asarray(layer_flags(cfg))

    def body(x, layer):
        p, flag, pk, pv = layer
        h = rms_norm(x, p["norm1"], cfg.rms_eps)
        q, k, v = attn._qkv(p["attn"], h, cfg, positions)
        pk = jax.lax.dynamic_update_slice(pk, k.astype(pk.dtype),
                                          (0, start, 0, 0))
        pv = jax.lax.dynamic_update_slice(pv, v.astype(pv.dtype),
                                          (0, start, 0, 0))
        out = attn._sdpa(q, pk, pv, mask)
        a = jnp.einsum("bshk,hkd->bsd", out, p["attn"]["wo"].astype(x.dtype))
        x = x + a
        h2 = rms_norm(x, p["norm2"], cfg.rms_eps)
        if cfg.family == "moe":
            y, _ = moe_mod.moe_ffn(p["moe"], h2, cfg)
        else:
            y = swiglu(h2, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                       p["mlp"]["w_down"])
        return x + y, (pk, pv)

    x, (nk, nv) = jax.lax.scan(body, x, (params["blocks"], flags,
                                         buf_k, buf_v),
                               unroll=_scan_unroll())
    if not return_logits:
        return nk, nv
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return nk, nv, unembed(x, table)


def init_chunk_buffers(cfg: ArchConfig, P: int, batch: int = 1):
    """Fresh per-layer K/V buffers for a chunked prefill ([L, B, P, KV,
    hd], the dtype ``forward`` collects its cache in)."""
    dt = jnp.dtype(cfg.dtype)
    shape = (cfg.n_layers, batch, P, cfg.n_kv_heads, cfg.hd)
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)


# ---------------------------------------------------------------------------
# prefill: forward + cache collection
# ---------------------------------------------------------------------------

def prefill(cfg: ArchConfig, params, batch, max_len: int | None = None):
    """Run the full prompt, return (logits, DecodeState) ready for decode.

    The dense caches collected from forward() cover the prompt; they are
    padded to ``max_len`` (default: prompt length) for subsequent decode
    appends.  SSM/xLSTM recurrent states are rebuilt with a short replay of
    the tail (simple and correct; a fused prefill-state path is a recorded
    optimisation)."""
    if cfg.family in ("ssm", "hybrid"):
        # recurrent families: replay the prompt through decode steps would
        # be O(S); instead run forward for logits and accept cold recurrent
        # state (documented simplification for the e2e example; the dry-run
        # lowers decode_step directly).
        logits, _, _ = forward(cfg, params, batch)
        B, S = batch["tokens"].shape
        state = init_decode_state(cfg, B, max_len or S)
        return logits, state

    logits, _, caches = forward(cfg, params, batch, collect_cache=True)
    B, S = (batch["tokens"].shape if "tokens" in batch
            else batch["embeds"].shape[:2])
    ml = max_len or S
    state = init_decode_state(cfg, B, ml)
    if cfg.family == "vlm":
        (self_caches, ikv) = caches
        k, v = self_caches
        ik, iv = ikv
        new = {
            "k": state.caches["k"].at[:, :, :, :S].set(k.astype(state.caches["k"].dtype)),
            "v": state.caches["v"].at[:, :, :, :S].set(v.astype(state.caches["v"].dtype)),
            "ik": ik.astype(state.caches["ik"].dtype),
            "iv": iv.astype(state.caches["iv"].dtype),
        }
        return logits, DecodeState(jnp.full((B,), S, jnp.int32), new)
    if caches != () and cfg.family != "audio":
        k, v = caches
        new = {
            "k": state.caches["k"].at[:, :, :S].set(k.astype(state.caches["k"].dtype)),
            "v": state.caches["v"].at[:, :, :S].set(v.astype(state.caches["v"].dtype)),
        }
        return logits, DecodeState(jnp.full((B,), S, jnp.int32), new)
    return logits, DecodeState(jnp.full((B,), S, jnp.int32), state.caches)
