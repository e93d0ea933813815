"""Plain reference of a pre-norm decoder: GQA attention with rotary
positions (optional QKV bias), then a SwiGLU MLP or a token-choice top-k
mixture of SwiGLU experts, RMSNorm before each, untied or tied output head.

Written from the published description in straightforward ``jax.numpy``,
in float32 at the highest matmul precision, with no cache, no paging, no
batching of requests and no kernels.  It reads the configuration file's
keys and the benchmark's own weights (``chipbench/model.py`` draws them).
Every expert is computed for every token and weighted by the router's
renormalised top-k gates, so no token is ever dropped.

``quant="fp8"`` is the control: the same computation with every weight
matrix rounded to float8 (e4m3) under a scale per output channel, the step
below the bfloat16 the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block
T_BLOCK = 512          # token rows per MLP block


def _dims(mc):
    d = mc["hidden_size"]
    H = mc["num_attention_heads"]
    return d, H, mc["num_key_value_heads"], mc.get("head_dim") or d // H


def _fp8(w, axes):
    """Round ``w`` to float8 e4m3 with one scale per output channel
    (absmax over the contraction ``axes``), back in float32."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


# contraction axes of each weight matrix, per layer
_CONTRACT = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
             "router": (0,), "w_gate": (-2,), "w_up": (-2,), "w_down": (-2,)}


def _weights(p, quant):
    out = {}
    for k, v in p.items():
        if isinstance(v, dict):
            out[k] = _weights(v, quant)
        elif quant == "fp8" and k in _CONTRACT:
            out[k] = _fp8(v, _CONTRACT[k])
        else:
            out[k] = v.astype(jnp.float32)
    return out


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotate-half rotary embedding; x [S, n, hd], pos [S]."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _attention(mc, p, h, n_real):
    d, H, KV, hd = _dims(mc)
    p = p["attn"]
    S = h.shape[0]
    pos = jnp.arange(S)
    q = jnp.einsum("sd,dhk->shk", h, p["wq"], precision=HI)
    k = jnp.einsum("sd,dhk->shk", h, p["wk"], precision=HI)
    v = jnp.einsum("sd,dhk->shk", h, p["wv"], precision=HI)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q, k = _rope(q, pos, mc["rope_theta"]), _rope(k, pos, mc["rope_theta"])
    scale = mc.get("attention_multiplier", hd ** -0.5)
    G = H // KV
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        qb = qb.reshape(Q_BLOCK, KV, G, hd)
        sc = jnp.einsum("qkgh,tkh->kgqt", qb, k, precision=HI) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        ok = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < n_real)
        sc = jnp.where(ok, sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("kgqt,tkh->qkgh", w, v, precision=HI)
        return o.reshape(Q_BLOCK, H, hd)

    o = jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, H, hd)
    return jnp.einsum("shk,hkd->sd", o, p["wo"], precision=HI)


def _swiglu(x, wg, wu, wd):
    g = jnp.einsum("td,df->tf", x, wg, precision=HI)
    u = jnp.einsum("td,df->tf", x, wu, precision=HI)
    return jnp.einsum("tf,fd->td", jax.nn.silu(g) * u, wd, precision=HI)


def _mlp(mc, p, h):
    if "moe" not in p:
        m = p["mlp"]
        return _swiglu(h, m["w_gate"], m["w_up"], m["w_down"])
    m = p["moe"]
    E, K = mc["num_local_experts"], mc["num_experts_per_tok"]

    def block(x):
        probs = jax.nn.softmax(
            jnp.einsum("td,de->te", x, m["router"], precision=HI), -1)
        top, idx = jax.lax.top_k(probs, K)
        top = top / jnp.sum(top, -1, keepdims=True)
        gates = jnp.sum(jax.nn.one_hot(idx, E) * top[..., None], 1)  # [T, E]
        g = jnp.einsum("td,edf->tef", x, m["w_gate"], precision=HI)
        u = jnp.einsum("td,edf->tef", x, m["w_up"], precision=HI)
        y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, m["w_down"],
                       precision=HI)
        return jnp.einsum("ted,te->td", y, gates, precision=HI)

    S, d = h.shape
    return jax.lax.map(block, h.reshape(S // T_BLOCK, T_BLOCK, d)
                       ).reshape(S, d)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _logit_rows(mcs, params, tokens, rows, quant):
    """Logits [R, vocab] at positions ``rows`` of ``tokens`` [S] (padded;
    rows at or past the real length see only real positions)."""
    mc = dict(mcs)
    eps = mc["rms_norm_eps"]
    res = mc.get("residual_multiplier", 1.0)
    n_real = jnp.max(rows) + 1
    x = params["embed"][tokens].astype(jnp.float32) \
        * mc.get("embedding_multiplier", 1.0)

    def layer(x, p):
        p = _weights(p, quant)
        x = x + res * _attention(mc, p, _rms(x, p["norm1"], eps), n_real)
        x = x + res * _mlp(mc, p, _rms(x, p["norm2"], eps))
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    h = _rms(x[rows], params["final_norm"].astype(jnp.float32), eps)
    table = params["embed"] if mc["tie_word_embeddings"] \
        else params["unembed"]
    if quant == "fp8":
        table = _fp8(table, (1,))
    logits = jnp.einsum("rd,vd->rv", h, table.astype(jnp.float32),
                        precision=HI)
    return logits / mc.get("logits_scaling", 1.0)


def _freeze(mc: dict) -> tuple:
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "num_local_experts", "num_experts_per_tok",
            "rope_theta", "rms_norm_eps", "tie_word_embeddings",
            "attention_multiplier", "embedding_multiplier",
            "residual_multiplier", "logits_scaling")
    return tuple((k, mc[k]) for k in keep if k in mc)


def logit_rows(mc: dict, params, tokens, rows, quant: str | None = None):
    """Reference logits at ``rows`` (float32, [len(rows), vocab])."""
    return _logit_rows(_freeze(mc), params, tokens, rows, quant)
