"""A benchmark configuration file -> the program's model config and weights.

A configuration file (``chipbench/configs/<name>.json``) holds the model's
published keys as they are run, under the names of its ``config.json``.
``arch_config`` maps them onto the program's ``ArchConfig``; ``make_params``
draws the weights from the seed on the device, in the layout the program
serves and in the dtype the file states, as one jitted call.  The plain
reference (``chipbench/references``) reads the same arrays, so neither side
takes weights the other made.
"""

from __future__ import annotations

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent

# published multipliers the program does not model: a file may hold them
# only at the value that leaves the plain decoder unchanged
NEUTRAL = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
           "logits_scaling": 1.0}


def load_json(kind: str, name: str, root: Path = HERE) -> dict:
    """``<root>/<kind>/<name>.json`` (kind: configs, traffic or cells)."""
    path = root / kind / f"{name}.json"
    with open(path) as f:
        return json.load(f)


def arch_config(mc: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig
    for key, neutral in NEUTRAL.items():
        if mc.get(key, neutral) != neutral:
            raise ValueError(f"{mc['name']}: {key}={mc[key]} is not modelled "
                             f"by the program (only {neutral} is)")
    hd = mc.get("head_dim") or mc["hidden_size"] // mc["num_attention_heads"]
    if abs(mc.get("attention_multiplier", hd ** -0.5) - hd ** -0.5) > 1e-12:
        raise ValueError(f"{mc['name']}: the program scales attention "
                         f"scores by head_dim ** -0.5 only")
    serve = mc["serve"]
    moe = mc.get("num_local_experts", 0)
    return ArchConfig(
        name=mc["name"], family=serve["family"],
        n_layers=mc["num_hidden_layers"], d_model=mc["hidden_size"],
        n_heads=mc["num_attention_heads"],
        n_kv_heads=mc["num_key_value_heads"],
        d_ff=mc["intermediate_size"], vocab=mc["vocab_size"], head_dim=hd,
        qkv_bias=bool(mc.get("qkv_bias", False)),
        rope_theta=float(mc["rope_theta"]), rms_eps=float(mc["rms_norm_eps"]),
        tie_embeddings=bool(mc["tie_word_embeddings"]),
        n_experts=moe, top_k=mc.get("num_experts_per_tok", 0),
        capacity_factor=float(serve.get("capacity_factor", 1.25)),
        dtype=mc["torch_dtype"])


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _layer(key, cfg):
    """One block's weights in the program's layout."""
    d, H, KV, hd, ff = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                        cfg.d_ff)
    dt = jnp.dtype(cfg.dtype)
    ks = iter(jax.random.split(key, 16))
    p = {
        "norm1": (1.0 + _normal(next(ks), (d,), 0.1, jnp.float32)).astype(dt),
        "norm2": (1.0 + _normal(next(ks), (d,), 0.1, jnp.float32)).astype(dt),
        "attn": {
            "wq": _normal(next(ks), (d, H, hd), d ** -0.5, dt),
            "wk": _normal(next(ks), (d, KV, hd), d ** -0.5, dt),
            "wv": _normal(next(ks), (d, KV, hd), d ** -0.5, dt),
            "wo": _normal(next(ks), (H, hd, d), (H * hd) ** -0.5, dt),
        },
    }
    if cfg.qkv_bias:
        p["attn"]["bq"] = _normal(next(ks), (H, hd), 0.1, dt)
        p["attn"]["bk"] = _normal(next(ks), (KV, hd), 0.1, dt)
        p["attn"]["bv"] = _normal(next(ks), (KV, hd), 0.1, dt)
    if cfg.n_experts:
        E = cfg.n_experts
        p["moe"] = {
            # the program keeps the router in float32
            "router": _normal(next(ks), (d, E), d ** -0.5, jnp.float32),
            "w_gate": _normal(next(ks), (E, d, ff), d ** -0.5, dt),
            "w_up": _normal(next(ks), (E, d, ff), d ** -0.5, dt),
            "w_down": _normal(next(ks), (E, ff, d), ff ** -0.5, dt),
        }
    else:
        p["mlp"] = {
            "w_gate": _normal(next(ks), (d, ff), d ** -0.5, dt),
            "w_up": _normal(next(ks), (d, ff), d ** -0.5, dt),
            "w_down": _normal(next(ks), (ff, d), ff ** -0.5, dt),
        }
    return p


@functools.partial(jax.jit, static_argnums=0)
def _params(cfg, key):
    dt = jnp.dtype(cfg.dtype)
    k_emb, k_out, k_norm, k_layers = jax.random.split(key, 4)
    keys = jax.random.split(k_layers, cfg.n_layers)
    # one layer's draw at a time: the float32 draws of a whole stack would
    # not fit beside the weights
    tree = {"embed": _normal(k_emb, (cfg.vocab, cfg.d_model), 0.02, dt),
            "blocks": jax.lax.map(lambda k: _layer(k, cfg), keys),
            "final_norm": (1.0 + _normal(k_norm, (cfg.d_model,), 0.1,
                                         jnp.float32)).astype(dt)}
    if not cfg.tie_embeddings:
        tree["unembed"] = _normal(k_out, (cfg.vocab, cfg.d_model), 0.02, dt)
    return tree


def make_params(cfg, seed: int):
    """Weights drawn from ``seed`` on the default device."""
    key = jax.random.fold_in(jax.random.key(seed % 2 ** 31), seed // 2 ** 31)
    return _params(cfg, key)
