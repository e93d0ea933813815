"""jit'd public wrappers for paged decode attention (unified + split-pool)."""

from __future__ import annotations

import functools

import jax

from .paged_attention import (paged_attention, paged_attention_fused,
                              paged_attention_split)
from .ref import (paged_attention_fused_ref, paged_attention_ref,
                  paged_attention_split_ref)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("impl",))
def paged_attention_op(q, k_pool, v_pool, page_table, seq_lens,
                       *, impl: str = "auto"):
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return paged_attention_ref(q, k_pool, v_pool, page_table, seq_lens)
    return paged_attention(q, k_pool, v_pool, page_table, seq_lens,
                           interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("impl",))
def paged_attention_split_op(q, fast_k, fast_v, slow_k, slow_v, page_table,
                             seq_lens, *, impl: str = "auto"):
    """The zero-copy decode read: fast and slow pools stay separate operands
    (different memory kinds at deployment) and each page is routed by
    ``slot < fast_slots``.  Bit-identical to ``paged_attention_op`` over the
    concatenated pools."""
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return paged_attention_split_ref(q, fast_k, fast_v, slow_k, slow_v,
                                         page_table, seq_lens)
    return paged_attention_split(q, fast_k, fast_v, slow_k, slow_v,
                                 page_table, seq_lens,
                                 interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("impl",))
def paged_attention_fused_op(q, fast_k, fast_v, slow_k, slow_v, entries,
                             k_new, v_new, pos, *, impl: str = "auto"):
    """Fused k-token append+attend (q [B,K,KV,G,hd] -> [B,K,KV,G,hd]).

    Both backends route by the same forward map: ``entries`` [B,npages]
    (leaf rows — >= 0 names a page's fast slot, < 0 means the identity
    slow home; the TPU index maps fetch each attended page from that one
    tier, the CPU oracle gathers by it).  New rows are cast to the pool
    dtype *here* so the attended values are bitwise the values a prior
    ``append_token`` would have stored.  A parked lane's rows are
    meaningless (0 from the kernel, which reads nothing for it).

    ``entries`` may be sliced to the live-page bucket (its second dim is
    the number of pages attended, DESIGN.md §11): both backends read only
    that page prefix, and the caller guarantees every live position fits
    inside it — the truncated tail is fully masked so the output stays
    bit-identical to the full-width read."""
    k_new = k_new.astype(fast_k.dtype)
    v_new = v_new.astype(fast_v.dtype)
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return paged_attention_fused_ref(q, fast_k, fast_v, slow_k, slow_v,
                                         entries, k_new, v_new, pos)
    return paged_attention_fused(q, fast_k, fast_v, slow_k, slow_v,
                                 entries, k_new, v_new, pos,
                                 interpret=not _on_tpu())
