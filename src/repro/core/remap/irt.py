"""Batch-first iRT: the multi-level indexed remap table (Section 3.2).

One implementation of the walk + table-maintenance ops, shared by the
tiered KV-cache (page granularity) and the Pallas kernel layer.  The table
is a pure pytree of three arrays:

    entries [n_leaf * E] int32 : id -> device slot, INVALID when identity
    l1_bits [n_words]    int32 : 1 bit per leaf, "is the leaf allocated?"
    leaf_cnt [n_leaf]    int32 : live entries per leaf (drives saved-space
                                 lending + metadata priority, Section 3.3)

``walk`` probes both levels in parallel (fixed entry locations mean no
serial dependency) and falls back to the identity mapping when the leaf is
unallocated or the entry invalid.  For large batches on TPU, over a table
that fits the kernel's scalar memory, it dispatches to the Pallas kernel
(``kernels/irt_lookup``); otherwise it runs the pure-jnp reference — the
same oracle the kernel is tested against, so the two backends are
interchangeable.

``fill`` / ``invalidate`` maintain entries + leaf counts and re-derive the
level-1 bit vector from ``leaf_cnt > 0``.  (The seed kept l1 bits sticky
once set; deriving them from the counts is observationally identical —
a cleared leaf's entries are all INVALID, so the walk result never
differs — and keeps the bit vector exactly "allocated?", the paper's
definition.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.irt_lookup.irt_lookup import irt_lookup
from repro.kernels.irt_lookup.ref import irt_lookup_ref
from repro.obs.registry import MetricSpec, register

# canonical metric names for the walk path (DESIGN.md §10): translated
# pages are the lookup lanes the metadata engine actually resolved; a
# walk is one parallel two-level probe (per-level touches == walks x
# levels, both levels probed concurrently — Section 3.2)
register(
    MetricSpec("trimma_translated_pages_total", "counter",
               "logical pages translated by the metadata engine (iRC "
               "probe + iRT walk; cached device-table rows never reach "
               "it)"),
    MetricSpec("trimma_irt_walks_total", "counter",
               "two-level iRT walks (one per iRC miss; each walk "
               "touches both levels in parallel)"),
)

INVALID = -1
E = 64                     # entries per leaf block (256 B / 4 B, Section 3.2)
KERNEL_MIN_BATCH = 1024    # below this the gather is launch-overhead bound
KERNEL_BLOCK = 1024        # ids per grid step: XLA tiles an s32 vector by
                           # 1024, and an SMEM block must match that tiling
KERNEL_MAX_TABLE = 1 << 17  # leaf entries held in SMEM: 512 KiB of the
                            # v5e's 1 MiB, the rest holds the id blocks


def n_words(n_leaf: int) -> int:
    return -(-n_leaf // 32)


def init_tables(n_ids: int) -> dict:
    """Empty iRT covering ``n_ids`` logical ids (rounded up to whole leaves)."""
    nl = -(-n_ids // E)
    return {
        "entries": jnp.full((nl * E,), INVALID, jnp.int32),
        "l1_bits": jnp.zeros((n_words(nl),), jnp.int32),
        "leaf_cnt": jnp.zeros((nl,), jnp.int32),
    }


def pack_alloc_bits(leaf_cnt: jnp.ndarray) -> jnp.ndarray:
    """Level-1 bit vector from per-leaf live counts (bit == allocated)."""
    nl = leaf_cnt.shape[0]
    nw = n_words(nl)
    alloc = jnp.zeros((nw * 32,), jnp.uint32).at[:nl].set(
        (leaf_cnt > 0).astype(jnp.uint32))
    vec = (alloc.reshape(nw, 32)
           << jnp.arange(32, dtype=jnp.uint32)[None, :]).sum(
        -1, dtype=jnp.uint32)
    return vec.astype(jnp.int32)


# ---------------------------------------------------------------------------
# walk
# ---------------------------------------------------------------------------

def walk(ids: jnp.ndarray, home: jnp.ndarray, l1_bits, entries,
         *, levels: int = 2, impl: str = "auto") -> jnp.ndarray:
    """Translate ids [N] -> device slots [N], defaulting to ``home``.

    levels == 1 models a linear (always-allocated) table: only the entry
    validity is checked.  impl: "auto" picks the Pallas kernel for large
    batches over a table of at most ``KERNEL_MAX_TABLE`` entries on TPU
    and the jnp reference elsewhere; "ref" / "kernel" force a backend
    ("kernel" runs in interpret mode off-TPU, for tests).
    """
    (N,) = ids.shape
    if levels == 1:
        return jnp.where(entries[ids] != INVALID, entries[ids],
                         home).astype(jnp.int32)
    on_tpu = jax.default_backend() == "tpu"
    use_kernel = impl == "kernel" or (
        impl == "auto" and on_tpu and N >= KERNEL_MIN_BATCH
        and entries.shape[0] <= KERNEL_MAX_TABLE)
    if not use_kernel:
        return irt_lookup_ref(ids, home, l1_bits, entries)
    bn = min(KERNEL_BLOCK, N)
    pad = (-N) % bn
    if pad:
        ids = jnp.pad(ids, (0, pad))
        home = jnp.pad(home, (0, pad))
    out = irt_lookup(ids, home, l1_bits, entries, block=bn,
                     interpret=not on_tpu)
    return out[:N]


# ---------------------------------------------------------------------------
# fill / invalidate (table maintenance)
# ---------------------------------------------------------------------------

def _refresh_words(l1_bits, leaf_cnt, leaves, enable):
    """Re-derive only the l1 words covering ``leaves`` [N] — O(N*32), not
    O(n_leaf).  Duplicate words across lanes write identical values (both
    derive from the same post-update counts), so collisions are benign."""
    nl = leaf_cnt.shape[0]
    words = leaves // 32
    offs = words[:, None] * 32 + jnp.arange(32, dtype=jnp.int32)[None, :]
    alloc = jnp.where(offs < nl,
                      leaf_cnt[jnp.clip(offs, 0, nl - 1)] > 0, False)
    vec = (alloc.astype(jnp.uint32)
           << jnp.arange(32, dtype=jnp.uint32)[None, :]).sum(
        -1, dtype=jnp.uint32).astype(jnp.int32)
    idx = jnp.where(enable, words, l1_bits.shape[0])     # OOB -> dropped
    return l1_bits.at[idx].set(vec, mode="drop")


def fill(tab: dict, ids: jnp.ndarray, slots: jnp.ndarray,
         enable: jnp.ndarray) -> dict:
    """Install id -> slot entries for enabled lanes (batch scatter;
    duplicate enabled ids are a caller error, counts would double)."""
    n = tab["entries"].shape[0]
    nl = tab["leaf_cnt"].shape[0]
    idx = jnp.where(enable, ids, n)                      # OOB -> dropped
    entries = tab["entries"].at[idx].set(slots, mode="drop")
    leaf_cnt = tab["leaf_cnt"].at[jnp.where(enable, ids // E, nl)].add(
        1, mode="drop")
    return {"entries": entries, "leaf_cnt": leaf_cnt,
            "l1_bits": _refresh_words(tab["l1_bits"], leaf_cnt, ids // E,
                                      enable)}


def invalidate(tab: dict, ids: jnp.ndarray, enable: jnp.ndarray) -> dict:
    """Clear id entries for enabled lanes (migration undo / eviction)."""
    n = tab["entries"].shape[0]
    nl = tab["leaf_cnt"].shape[0]
    idx = jnp.where(enable, ids, n)
    entries = tab["entries"].at[idx].set(INVALID, mode="drop")
    leaf_cnt = tab["leaf_cnt"].at[jnp.where(enable, ids // E, nl)].add(
        -1, mode="drop")
    return {"entries": entries, "leaf_cnt": leaf_cnt,
            "l1_bits": _refresh_words(tab["l1_bits"], leaf_cnt, ids // E,
                                      enable)}
