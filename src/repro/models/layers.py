"""Shared model building blocks (pure JAX, no flax).

Parameters are nested dicts of arrays.  Every initializer returns a matching
*logical-axes* tree used by the sharding layer (sharding/specs.py); the two
trees always share structure because they are built together: leaves of the
init tree are ``Param(value, axes)`` pairs split by ``split_tree``.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.specs import logical_constraint


@jax.tree_util.register_pytree_node_class
class Param:
    """Array + static logical-axes metadata.

    Registered as a pytree with ``axes`` as aux data, so trees of Params
    trace cleanly under jit/eval_shape (72B+ configs are shape-evaluated,
    never materialised, for the dry-run)."""

    __slots__ = ("value", "axes")

    def __init__(self, value, axes):
        self.value = value
        self.axes = tuple(axes)

    def tree_flatten(self):
        return (self.value,), self.axes

    @classmethod
    def tree_unflatten(cls, axes, children):
        return cls(children[0], axes)

    @property
    def shape(self):
        return self.value.shape

    def __repr__(self):
        return f"Param({getattr(self.value, 'shape', self.value)}, {self.axes})"


def is_param(x) -> bool:
    return isinstance(x, Param)


def split_tree(tree):
    """Split a tree of Param leaves into (values, logical_axes) trees."""
    values = jax.tree.map(lambda p: p.value, tree, is_leaf=is_param)
    axes = jax.tree.map(lambda p: p.axes, tree, is_leaf=is_param)
    return values, axes


def key_for(key, *path) -> jax.Array:
    # crc32, not hash(): str hashing is salted per process, so hash() would
    # draw different weights from the same seed in every process
    for p in path:
        key = jax.random.fold_in(key,
                                 zlib.crc32(str(p).encode()) & 0x7FFFFFFF)
    return key


def dense_init(key, shape, axes, dtype, scale=None) -> Param:
    fan_in = shape[-2] if len(shape) > 1 else shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    v = (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
         * scale).astype(dtype)
    return Param(v, axes)


def zeros_init(shape, axes, dtype) -> Param:
    return Param(jnp.zeros(shape, dtype), axes)


def ones_init(shape, axes, dtype) -> Param:
    return Param(jnp.ones(shape, dtype), axes)


# ---------------------------------------------------------------------------
# normalisation / activations
# ---------------------------------------------------------------------------

def rms_norm(x, weight, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt) * weight


def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def gelu_mlp(x, w_in, b_in, w_out, b_out):
    return jax.nn.gelu(x @ w_in + b_in) @ w_out + b_out


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, hd]; positions: [..., S] int32."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                      # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(angles)[..., None, :]                      # [..., S, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------

def embed_init(key, vocab: int, d: int, dtype) -> Param:
    return dense_init(key, (vocab, d), ("vocab", "embed"), dtype, scale=0.02)


def embed_lookup(table, ids):
    out = jnp.take(table, ids, axis=0)
    return logical_constraint(out, ("batch", None, "embed_act"))


def unembed(x, table):
    """Logits projection (tied or untied table [vocab, d])."""
    logits = x.astype(jnp.float32) @ table.astype(jnp.float32).T
    return logical_constraint(logits, ("batch", None, "vocab"))
