"""Sharded serving steps: prefill (prompt -> KV cache) and decode
(one token against the cache).  Used by the serving engine, the examples
and the multi-pod dry-run.

Decode-state sharding: KV caches shard batch over the DP axes and the
*sequence* dimension over 'model' (kv_heads are often < model-axis size:
qwen2-72b has kv=8 on a 16-way axis, so sequence sharding wins — the
recorded hillclimb explores the alternatives).  Recurrent states (mamba /
xLSTM) shard batch only; they are O(1) per sequence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import (abstract_decode_state, abstract_params_and_axes,
                          decode_step, forward, prefill)
from repro.sharding.specs import spec_for, tree_shardings


def _cache_axes(path: str, ndim: int) -> tuple:
    leaf = path.split("/")[-1]
    if leaf in ("k", "v"):
        if ndim == 6:     # vlm: [ns, inner, B, S, KV, hd]
            return ("layers", None, "batch", "seq", None, None)
        return ("layers", "batch", "seq", None, None)
    if leaf in ("ik", "iv"):                    # image KV: [ns,B,T,KV,hd]
        return ("layers", "batch", None, None, None)
    # recurrent states: [L, B, ...]
    return ("layers", "batch") + (None,) * (ndim - 2)


def decode_state_shardings(cfg: ArchConfig, state_abs, mesh):
    """NamedSharding tree matching a DecodeState."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(state_abs)
    out = []
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        if name.endswith("pos") or leaf.ndim == 0:
            axes = ()
        else:
            axes = _cache_axes(name, leaf.ndim)
        out.append(NamedSharding(
            mesh, spec_for(axes, mesh=mesh, shape=tuple(leaf.shape))))
    return jax.tree_util.tree_unflatten(treedef, out)


def batch_shardings(batch_specs: dict, mesh):
    out = {}
    for k, v in batch_specs.items():
        axes = ("batch",) + (None,) * (len(v.shape) - 1)
        out[k] = NamedSharding(mesh, spec_for(axes, mesh=mesh,
                                              shape=tuple(v.shape)))
    return out


def make_decode_fn(cfg: ArchConfig):
    def fn(params, state, tokens):
        return decode_step(cfg, params, state, tokens)
    return fn


def make_tiered_decode_step(tcfg, *, path: str = "zero_copy",
                            impl: str = "auto",
                            n_pages: int | None = None):
    """Build one jitted serving decode step against the tiered KV store:
    append this step's per-sequence K/V token, then read attention through
    the Trimma-translated device table.

    ``path`` selects the data path (all produce bit-identical output —
    the golden-equality test pins it):
      "zero_copy"  cached device table + split-pool kernel — pool bytes
                   never move (the production path);
      "fused"      one fused append+attend kernel over k tokens per lane
                   per call (``serve.tiered.attend_tokens``; set ``k``);
      "concat"     the legacy baseline: full re-translation + unified-pool
                   concatenation per step (kept for the ``serve_decode``
                   benchmark; pair with ``cache_device_table=False``).

    Returned signature: step(state, q, k_new, v_new, pos) -> (out, state)
    with q [B, KV, G, hd], k_new/v_new [B, KV, hd] and ``pos`` the decode
    position — a shared scalar or a per-lane [B] vector (ragged lanes
    decode at independent positions; seq_lens becomes pos + 1, clamped at
    0 so a negative/idle lane reads nothing).  With ``path="fused"`` and
    k > 1 the token axis rides second: q [B, k, KV, G, hd], k_new/v_new
    [B, k, KV, hd], lane b's token i landing at position ``pos[b] + i``.

    ``n_pages`` (fused path only) is the static live-page attention
    bucket (DESIGN.md §11; ``serve.tiered.attend_tokens``) — the caller
    guarantees every live and appended position fits inside it.
    """
    import jax.numpy as jnp

    from repro.serve import tiered as srv
    from repro.tiered import kvcache as tk

    seq_ids = jnp.arange(tcfg.n_seqs, dtype=jnp.int32)

    if path == "fused":
        def step(st, q, k_new, v_new, pos):
            pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                                   (tcfg.n_seqs,))
            if q.ndim == 4:            # k = 1 with the flat signature
                q, k_new, v_new = (q[:, None], k_new[:, None],
                                   v_new[:, None])
            return srv.attend_tokens(tcfg, st, q, k_new, v_new, pos,
                                     n_pages=n_pages, impl=impl)
        return jax.jit(step)
    if n_pages is not None:
        raise ValueError(
            f"n_pages (live-page bucket) only applies to path='fused'; "
            f"got path={path!r}")

    fn = srv.attend if path == "zero_copy" else srv.attend_concat

    def step(st, q, k_new, v_new, pos):
        pos = jnp.asarray(pos, jnp.int32)
        st = tk.append_token(tcfg, st, seq_ids, k_new, v_new, pos)
        seq_lens = jnp.broadcast_to(jnp.maximum(pos + 1, 0),
                                    (tcfg.n_seqs,))
        return fn(tcfg, st, q, seq_lens, impl=impl)

    return jax.jit(step)


def make_chunk_prefill_fn(cfg: ArchConfig, *, logits: bool = False):
    """Build one jitted chunked-prefill step (DESIGN.md §9): one prompt
    chunk's K/V computed against the accumulated per-layer key buffers.

    Returned signature: engine_chunk_fwd(params, chunk_tokens [B, C],
    buf_k, buf_v, start) -> (buf_k, buf_v) with rows [start, start + C)
    written.  The buffers ([L, B, P, KV, hd],
    ``models.init_chunk_buffers``) must be padded to the SAME length P the
    one-shot prefill forward would run at — that is what makes every
    chunk's reductions (and therefore the ingested K/V and all downstream
    decode logits) bit-identical to the one-shot
    ``forward(collect_cache=True)`` pass.  One jit key covers
    every (P, C) pair the caller uses it at (shapes re-trace as usual).

    ``logits=True`` appends the chunk's LM-head logits [B, C, vocab] to
    the return — the final chunk's last prompt row is exactly the first
    decode step's distribution, so the scheduler can emit an admitted
    prompt's first token straight from ingest.
    """
    from repro.models import forward_chunk

    def engine_chunk_fwd(params, chunk_tokens, buf_k, buf_v, start):
        return forward_chunk(cfg, params, chunk_tokens, buf_k, buf_v,
                             start, return_logits=logits)

    return jax.jit(engine_chunk_fwd)


def make_prefill_fn(cfg: ArchConfig, shape: ShapeConfig):
    if cfg.is_encoder:
        def fn(params, batch):          # encode: logits over frames
            logits, aux, _ = forward(cfg, params, batch)
            return logits
        return fn

    def fn(params, batch):
        logits, state = prefill(cfg, params, batch, max_len=shape.seq_len)
        return logits[:, -1], state
    return fn


def jit_decode(cfg: ArchConfig, shape: ShapeConfig, mesh, donate=True):
    """Returns (jitted fn, (params_abs, state_abs, tokens_abs))."""
    params_abs, axes = abstract_params_and_axes(cfg)
    p_sh = tree_shardings(axes, mesh, params_abs)
    state_abs = abstract_decode_state(cfg, shape)
    s_sh = decode_state_shardings(cfg, state_abs, mesh)
    t_abs = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
    t_sh = NamedSharding(mesh, spec_for(("batch",), mesh=mesh,
                                        shape=t_abs.shape))
    logits_sh = NamedSharding(
        mesh, spec_for(("batch", "vocab"), mesh=mesh,
                       shape=(shape.global_batch, cfg.vocab)))
    kwargs = dict(in_shardings=(p_sh, s_sh, t_sh),
                  out_shardings=(logits_sh, s_sh))
    if donate:
        kwargs["donate_argnums"] = (1,)
    return jax.jit(make_decode_fn(cfg), **kwargs), (params_abs, state_abs,
                                                    t_abs)


def jit_prefill(cfg: ArchConfig, shape: ShapeConfig, mesh):
    from repro.models import input_specs
    params_abs, axes = abstract_params_and_axes(cfg)
    p_sh = tree_shardings(axes, mesh, params_abs)
    specs = input_specs(cfg, shape)
    b_sh = batch_shardings(specs, mesh)
    if cfg.is_encoder:
        out_sh = NamedSharding(
            mesh, spec_for(("batch", None, "vocab"), mesh=mesh,
                           shape=(shape.global_batch, shape.seq_len,
                                  cfg.vocab)))
    else:
        state_abs = jax.eval_shape(
            lambda p, b: make_prefill_fn(cfg, shape)(p, b)[1],
            params_abs, specs)
        s_sh = decode_state_shardings(cfg, state_abs, mesh)
        out_sh = (NamedSharding(
            mesh, spec_for(("batch", "vocab"), mesh=mesh,
                           shape=(shape.global_batch, cfg.vocab))), s_sh)
    return jax.jit(make_prefill_fn(cfg, shape),
                   in_shardings=(p_sh, b_sh),
                   out_shardings=out_sh), (params_abs, specs)
