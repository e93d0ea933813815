"""Flash attention (GQA, causal, sliding-window) as a Pallas TPU kernel.

Tiling: grid (batch, q_heads, q_blocks, k_blocks); the k dimension is the
innermost sequential ("arbitrary") axis so the online-softmax state lives in
VMEM scratch across k steps.  GQA is expressed in the K/V BlockSpec index
maps (kv_head = q_head // group) — grouped heads are never materialised.
Block shapes default to (128, head_dim): MXU-aligned (multiples of 128 on
the contracting/lane dims) and a working set of
  q(128*hd) + k(128*hd) + v(128*hd) + acc(128*hd) * 4B  ~= 0.4 MB at hd=128
comfortably inside the ~16 MB v5e VMEM with double buffering.

Validated on CPU via interpret=True against ref.py (pure jnp oracle);
compiled for TPU (Mosaic) on real hardware.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            nk: int):
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # k block (sequential)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # [bq, hd]
    k = k_ref[0, 0].astype(jnp.float32)            # [bk, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        ok &= kpos <= qpos
    if window > 0:
        ok &= kpos > qpos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                            # [bq, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q [B,H,S,hd]; k,v [B,KV,T,hd] -> [B,H,S,hd]."""
    B, H, S, hd = q.shape
    KV, T = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(block_q, S)
    bk = min(block_k, T)
    assert S % bq == 0 and T % bk == 0
    nq, nk = S // bq, T // bk
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
