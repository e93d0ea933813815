"""2-level iRT walk as a Pallas TPU kernel.

The paper's metadata lookup (Section 3.2): given logical page ids, probe the
intermediate-level bit vector and the leaf remap table *in parallel* (fixed
entry locations mean no serial dependency between levels), and fall back to
the identity mapping (device slot = home slot) when the leaf is unallocated
or the entry invalid.

TPU adaptation (DESIGN.md §3): both levels live in SMEM — the bit vector is
1 bit per leaf (tiny), the leaf table is the fast-tier-proportional Trimma
structure — and each grid step walks one block of ids with scalar loads.
Mosaic has no vector gather from a VMEM ref by a vector of indices, while
a scalar load from SMEM at a dynamic address is one instruction, so the
walk is a scalar loop: per id one load from each level (issued
independently — the paper's parallel probe), one select.

Layout: ids [N] int32; l1_bits [n_words] int32 (bit per leaf);
leaf_table [n_leaf * E] int32 (INVALID = -1); home [N] int32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INVALID = -1
E = 64  # entries per leaf block (256 B / 4 B, Section 3.2)


def _kernel(ids_ref, home_ref, bits_ref, leaf_ref, out_ref, *, bn: int):
    def body(i, carry):
        idx = ids_ref[i]
        leaf = idx // E
        # level-1 probe: intermediate bit vector (is the leaf allocated?)
        word = bits_ref[leaf // 32]
        allocated = (jax.lax.shift_right_logical(word, leaf % 32) & 1) == 1
        # level-2 probe: leaf entry (independent of level 1)
        entry = leaf_ref[idx]
        hit = allocated & (entry != INVALID)
        out_ref[i] = jnp.where(hit, entry, home_ref[i])
        return carry

    jax.lax.fori_loop(0, bn, body, 0)


def irt_lookup(ids, home, l1_bits, leaf_table, *, block: int = 1024,
               interpret: bool = False):
    """ids, home [N] int32; l1_bits [n_words] int32;
    leaf_table [n_leaf*E] int32 -> device slots [N] int32."""
    (N,) = ids.shape
    bn = min(block, N)
    assert N % bn == 0
    smem = pltpu.SMEM
    id_block = pl.BlockSpec((bn,), lambda i: (i,), memory_space=smem)
    return pl.pallas_call(
        lambda *refs: _kernel(*refs, bn=bn),
        grid=(N // bn,),
        in_specs=[id_block, id_block,
                  pl.BlockSpec(memory_space=smem),
                  pl.BlockSpec(memory_space=smem)],
        out_specs=id_block,
        out_shape=jax.ShapeDtypeStruct((N,), jnp.int32),
        interpret=interpret,
    )(ids, home, l1_bits, leaf_table)
