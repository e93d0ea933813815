"""core/remap engine: batched ops vs the seed scalar semantics, golden
counters across the refactor, and the vmapped ``run_many`` sweep.

Three layers of protection:
  1. an independent numpy oracle transliterating the *seed* scalar
     ``core/irc.py`` algorithms drives the same op stream as the batched
     engine (batch size 1) — every probe triple and the final state arrays
     must agree element-wise;
  2. ``tests/golden/sim_counters.json`` (generated at the seed commit)
     pins ``core/simulator.run`` counters bit-for-bit for every scheme;
  3. ``run_many`` must reproduce N sequential ``run`` calls exactly.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (HBM3_DDR5, WORKLOADS, generate_trace,
                        relabel_first_touch, run, run_many)
from repro.core.remap import irt as irt_ops
from repro.core.remap import rcache as rc_ops
from repro.core.remap.rcache import IDENTITY, RemapCacheGeometry
from repro.kernels.irt_lookup.ref import irt_lookup_ref
from tests.golden.gen_golden import SCHEMES, TRACE_LEN, WL
from tests.golden.gen_golden import SEED as GOLD_SEED

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "sim_counters.json")


# ---------------------------------------------------------------------------
# numpy oracle: the seed's scalar remap-cache semantics, transliterated
# ---------------------------------------------------------------------------

class ScalarOracle:
    """Direct numpy port of the seed ``core/irc.py`` (pre-refactor)."""

    def __init__(self, g: RemapCacheGeometry):
        self.g = g
        if g.kind == "conventional":
            self.rc_tag = np.full((g.rc_sets, g.rc_ways), -1, np.int32)
            self.rc_val = np.full((g.rc_sets, g.rc_ways), IDENTITY, np.int32)
            self.rc_fifo = np.zeros(g.rc_sets, np.int32)
        elif g.kind == "irc":
            self.nid_tag = np.full((g.nid_sets, g.nid_ways), -1, np.int32)
            self.nid_val = np.full((g.nid_sets, g.nid_ways), IDENTITY,
                                   np.int32)
            self.nid_fifo = np.zeros(g.nid_sets, np.int32)
            self.id_tag = np.full((g.id_sets, g.id_ways), -1, np.int32)
            self.id_bits = np.zeros((g.id_sets, g.id_ways), np.uint32)
            self.id_fifo = np.zeros(g.id_sets, np.int32)

    def _id_index(self, sb):
        h = ((sb * 2654435761) & 0xFFFFFFFF) >> 16
        return h % self.g.id_sets

    def probe(self, b):
        g = self.g
        if g.kind == "conventional":
            s = b % g.rc_sets
            match = self.rc_tag[s] == b
            hit = bool(match.any())
            val = int(self.rc_val[s][match].sum()) if hit else IDENTITY
            return hit, val, False
        s_n = b % g.nid_sets
        n_match = self.nid_tag[s_n] == b
        nid_hit = bool(n_match.any())
        nid_val = int(self.nid_val[s_n][n_match].sum()) if nid_hit else 0
        sb, bit = b // g.sector, b % g.sector
        s_i = self._id_index(sb)
        i_match = self.id_tag[s_i] == sb
        line = int(self.id_bits[s_i][i_match].sum(dtype=np.uint32))
        id_hit = bool(i_match.any()) and ((line >> bit) & 1) == 1
        return nid_hit or id_hit, nid_val if nid_hit else IDENTITY, id_hit

    def fill(self, b, dev, table, enable):
        g = self.g
        if not enable:
            return
        if g.kind == "conventional":
            s = b % g.rc_sets
            w = self.rc_fifo[s] % g.rc_ways
            self.rc_tag[s, w] = b
            self.rc_val[s, w] = dev
            self.rc_fifo[s] += 1
            return
        if dev != IDENTITY:
            s = b % g.nid_sets
            w = self.nid_fifo[s] % g.nid_ways
            self.nid_tag[s, w] = b
            self.nid_val[s, w] = dev
            self.nid_fifo[s] += 1
            return
        sb = b // g.sector
        vec = np.uint32(0)
        for j in range(g.sector):
            idx = sb * g.sector + j
            if idx < len(table) and table[idx] == IDENTITY:
                vec |= np.uint32(1) << np.uint32(j)
        s_i = self._id_index(sb)
        present = self.id_tag[s_i] == sb
        if present.any():
            w = int(np.argmax(present))
        else:
            w = self.id_fifo[s_i] % g.id_ways
            self.id_fifo[s_i] += 1
        self.id_tag[s_i, w] = sb
        self.id_bits[s_i, w] = vec

    def invalidate(self, b, enable, becomes_identity=False):
        g = self.g
        if not enable:
            return
        if g.kind == "conventional":
            s = b % g.rc_sets
            self.rc_tag[s][self.rc_tag[s] == b] = -1
            return
        s_n = b % g.nid_sets
        self.nid_tag[s_n][self.nid_tag[s_n] == b] = -1
        sb, bit = b // g.sector, b % g.sector
        s_i = self._id_index(sb)
        present = self.id_tag[s_i] == sb
        new_bit = np.uint32(1 if becomes_identity else 0)
        line = self.id_bits[s_i]
        upd = ((line & ~(np.uint32(1) << np.uint32(bit)))
               | (new_bit << np.uint32(bit)))
        self.id_bits[s_i] = np.where(present, upd, line)

    def arrays(self):
        if self.g.kind == "conventional":
            return {"rc_tag": self.rc_tag, "rc_val": self.rc_val,
                    "rc_fifo": self.rc_fifo}
        return {"nid_tag": self.nid_tag, "nid_val": self.nid_val,
                "nid_fifo": self.nid_fifo, "id_tag": self.id_tag,
                "id_bits": self.id_bits, "id_fifo": self.id_fifo}


def _op_stream(rng, n_ops, n_blocks, n_slots):
    """Random interleaving of fill/invalidate/probe over an evolving table."""
    table = np.full(n_blocks, IDENTITY, np.int32)
    ops = []
    for _ in range(n_ops):
        b = int(rng.integers(n_blocks))
        kind = rng.choice(["fill", "invalidate", "probe"])
        if kind == "fill":
            dev = IDENTITY if rng.random() < 0.5 else int(
                rng.integers(n_slots))
            table[b] = dev
            ops.append(("fill", b, dev, table.copy(),
                        bool(rng.random() < 0.9)))
        elif kind == "invalidate":
            becomes_id = bool(rng.random() < 0.5)
            if becomes_id:
                table[b] = IDENTITY
            ops.append(("invalidate", b, becomes_id, None,
                        bool(rng.random() < 0.9)))
        else:
            ops.append(("probe", b, None, None, True))
    return ops


@pytest.mark.parametrize("kind", ["conventional", "irc"])
def test_batched_engine_matches_seed_scalar_semantics(kind):
    g = RemapCacheGeometry(kind=kind, rc_sets=8, rc_ways=4, nid_sets=8,
                           nid_ways=3, id_sets=4, id_ways=2)
    oracle = ScalarOracle(g)
    st = {k: v for k, v in rc_ops.init_state(g).items()}
    rng = np.random.default_rng(7)
    n_blocks = 512
    for op in _op_stream(rng, 400, n_blocks, n_slots=64):
        name, b, x, table, enable = op
        ids = jnp.asarray([b], jnp.int32)
        en = jnp.asarray([enable])
        if name == "fill":
            oracle.fill(b, x, table, enable)
            st.update(rc_ops.fill(g, st, ids, jnp.asarray([x], jnp.int32),
                                  jnp.asarray(table), en))
        elif name == "invalidate":
            oracle.invalidate(b, enable, becomes_identity=x)
            st.update(rc_ops.invalidate(g, st, ids, en, becomes_identity=x))
        else:
            hit, val, id_hit = rc_ops.probe(g, st, ids)
            o_hit, o_val, o_id = oracle.probe(b)
            assert bool(hit[0]) == o_hit, (op,)
            assert bool(id_hit[0]) == o_id, (op,)
            if o_hit and not o_id:
                assert int(val[0]) == o_val, (op,)
    for k, ref in oracle.arrays().items():
        np.testing.assert_array_equal(np.asarray(st[k]), ref, err_msg=k)


def test_batched_probe_equals_elementwise_scalar_probe():
    """One batched probe over N ids == N independent batch-1 probes."""
    g = RemapCacheGeometry(kind="irc", nid_sets=8, nid_ways=3, id_sets=4,
                           id_ways=2)
    st = rc_ops.init_state(g)
    rng = np.random.default_rng(3)
    table = np.where(rng.random(512) < 0.5, IDENTITY,
                     rng.integers(0, 64, 512)).astype(np.int32)
    ids = jnp.asarray(rng.integers(0, 512, 64), jnp.int32)
    st = {**st, **rc_ops.fill(g, st, ids, jnp.asarray(table)[ids],
                              jnp.asarray(table),
                              jnp.ones(64, bool))}
    probe_ids = jnp.asarray(rng.integers(0, 512, 128), jnp.int32)
    hit, val, id_hit = rc_ops.probe(g, st, probe_ids)
    for i, b in enumerate(np.asarray(probe_ids)):
        h1, v1, i1 = rc_ops.probe(g, st, jnp.asarray([b], jnp.int32))
        assert bool(hit[i]) == bool(h1[0])
        assert int(val[i]) == int(v1[0])
        assert bool(id_hit[i]) == bool(i1[0])


def test_batched_fill_without_collisions_equals_sequential():
    """A batch of ids hitting pairwise-distinct sets must equal N
    sequential batch-1 fills (the engine's only relaxation is in-batch
    set collisions)."""
    g = RemapCacheGeometry(kind="irc", nid_sets=32, nid_ways=3, id_sets=16,
                           id_ways=2)
    rng = np.random.default_rng(11)
    table = np.where(rng.random(2048) < 0.5, IDENTITY,
                     rng.integers(0, 64, 2048)).astype(np.int32)
    # pick ids with unique nid sets AND unique IdCache sets/sectors
    picked, seen_n, seen_i = [], set(), set()
    for b in rng.permutation(2048):
        s_n, sb = int(b) % g.nid_sets, int(b) // g.sector
        h = (((sb * 2654435761) & 0xFFFFFFFF) >> 16) % g.id_sets
        if s_n not in seen_n and h not in seen_i:
            picked.append(int(b)); seen_n.add(s_n); seen_i.add(h)
        if len(picked) == 8:
            break
    ids = jnp.asarray(picked, jnp.int32)
    dev = jnp.asarray(table)[ids]
    st_batch = rc_ops.init_state(g)
    st_batch = {**st_batch, **rc_ops.fill(g, st_batch, ids, dev,
                                          jnp.asarray(table),
                                          jnp.ones(len(picked), bool))}
    st_seq = rc_ops.init_state(g)
    for b in picked:
        one = jnp.asarray([b], jnp.int32)
        st_seq = {**st_seq, **rc_ops.fill(g, st_seq, one,
                                          jnp.asarray(table)[one],
                                          jnp.asarray(table),
                                          jnp.ones(1, bool))}
    for k in st_batch:
        np.testing.assert_array_equal(np.asarray(st_batch[k]),
                                      np.asarray(st_seq[k]), err_msg=k)


def test_batched_invalidate_same_set_does_not_resurrect():
    """Two lanes hitting the same set in one invalidate batch: the lane
    without a matching tag must not rebroadcast the pre-call row and
    resurrect the entry the other lane killed (cell-granular scatter)."""
    g = RemapCacheGeometry(kind="irc", nid_sets=4, nid_ways=3, id_sets=2,
                           id_ways=2)
    st = rc_ops.init_state(g)
    table = jnp.asarray([7] * 64, jnp.int32)   # all non-identity
    b, b2 = 5, 9                                # 5 % 4 == 9 % 4 == 1
    st = {**st, **rc_ops.fill(g, st, jnp.asarray([b], jnp.int32),
                              jnp.asarray([7], jnp.int32), table,
                              jnp.ones(1, bool))}
    hit, _, _ = rc_ops.probe(g, st, jnp.asarray([b], jnp.int32))
    assert bool(hit[0])
    # batch: lane 0 kills b, lane 1 targets b2 (same set, not cached)
    st = {**st, **rc_ops.invalidate(g, st,
                                    jnp.asarray([b, b2], jnp.int32),
                                    jnp.ones(2, bool))}
    hit, _, _ = rc_ops.probe(g, st, jnp.asarray([b], jnp.int32))
    assert not bool(hit[0]), "same-set lane resurrected a killed entry"


# ---------------------------------------------------------------------------
# iRT walk + table maintenance
# ---------------------------------------------------------------------------

def test_walk_matches_ref_and_pads_ragged_batches():
    rng = np.random.default_rng(5)
    n_leaf = 16
    entries = jnp.asarray(np.where(rng.random(n_leaf * irt_ops.E) < 0.3,
                                   rng.integers(0, 99, n_leaf * irt_ops.E),
                                   irt_ops.INVALID), jnp.int32)
    bits = jnp.asarray(rng.integers(-2**31, 2**31 - 1, -(-n_leaf // 32)),
                       jnp.int32)
    for n in (7, 1500):  # 1500 > KERNEL_BLOCK exercises the padding path
        ids = jnp.asarray(rng.integers(0, n_leaf * irt_ops.E, n), jnp.int32)
        home = ids + 1000
        ref = irt_lookup_ref(ids, home, bits, entries)
        np.testing.assert_array_equal(
            np.asarray(irt_ops.walk(ids, home, bits, entries, impl="ref")),
            np.asarray(ref))
        np.testing.assert_array_equal(
            np.asarray(irt_ops.walk(ids, home, bits, entries,
                                    impl="kernel")),
            np.asarray(ref))


def test_walk_level1_is_linear_table():
    entries = jnp.asarray([5, irt_ops.INVALID, 7, irt_ops.INVALID],
                          jnp.int32)
    ids = jnp.asarray([0, 1, 2, 3], jnp.int32)
    home = jnp.asarray([100, 101, 102, 103], jnp.int32)
    out = irt_ops.walk(ids, home, None, entries, levels=1)
    np.testing.assert_array_equal(np.asarray(out), [5, 101, 7, 103])


def test_irt_fill_invalidate_roundtrip():
    tab = irt_ops.init_tables(4 * irt_ops.E)
    ids = jnp.asarray([3, 70, 200], jnp.int32)
    slots = jnp.asarray([0, 1, 2], jnp.int32)
    tab = irt_ops.fill(tab, ids, slots, jnp.ones(3, bool))
    assert [int(x) for x in tab["entries"][ids]] == [0, 1, 2]
    assert [int(x) for x in tab["leaf_cnt"]] == [1, 1, 0, 1]
    assert int(tab["l1_bits"][0]) == 0b1011
    home = jnp.arange(4 * irt_ops.E, dtype=jnp.int32) + 500
    walked = irt_ops.walk(jnp.arange(4 * irt_ops.E, dtype=jnp.int32), home,
                          tab["l1_bits"], tab["entries"])
    assert int(walked[3]) == 0 and int(walked[70]) == 1
    assert int(walked[4]) == 504          # unallocated entry -> home
    tab = irt_ops.invalidate(tab, ids[:1], jnp.ones(1, bool))
    assert int(tab["entries"][3]) == irt_ops.INVALID
    assert int(tab["l1_bits"][0]) == 0b1010


# ---------------------------------------------------------------------------
# golden counters: the refactor must be bit-identical to the seed simulator
# ---------------------------------------------------------------------------

with open(GOLDEN) as _f:
    _GOLDEN = json.load(_f)


@pytest.mark.parametrize("scheme", sorted(SCHEMES))
def test_golden_counters(scheme):
    from tests.golden.gen_golden import golden_run
    got = golden_run(scheme)
    assert got == _GOLDEN[scheme], {
        k: (v, got[k]) for k, v in _GOLDEN[scheme].items() if got[k] != v}


# ---------------------------------------------------------------------------
# run_many: one jitted vmap == N sequential runs
# ---------------------------------------------------------------------------

def test_run_many_matches_sequential_runs():
    from repro.core import trimma_cache
    cfg = trimma_cache(fast_total_blocks=512, ratio=8, n_sets=4)
    specs = [("pr", 0), ("lbm", 1), ("ycsb_a", 2), ("tc", 3)]
    traces = [generate_trace(WORKLOADS[w], cfg.slow_blocks, 2048, s)
              for w, s in specs]
    blocks = np.stack([t[0] for t in traces])
    writes = np.stack([t[1] for t in traces])
    outs = run_many(cfg, HBM3_DDR5, blocks, writes)
    assert len(outs) == 4
    for t, (bl, wr) in enumerate(traces):
        ref = run(cfg, HBM3_DDR5, bl, wr)
        for k, v in outs[t].items():
            assert v == ref[k], (specs[t], k, v, ref[k])


def test_run_many_flat_mode():
    from repro.core import trimma_flat
    cfg = trimma_flat(fast_total_blocks=512, ratio=8, n_sets=4)
    traces = []
    for s in range(4):
        bl, wr = generate_trace(WORKLOADS["pr"], cfg.slow_blocks, 1024, s)
        traces.append((relabel_first_touch(bl), wr))
    blocks = np.stack([t[0] for t in traces])
    writes = np.stack([t[1] for t in traces])
    outs = run_many(cfg, HBM3_DDR5, blocks, writes)
    for t, (bl, wr) in enumerate(traces):
        ref = run(cfg, HBM3_DDR5, bl, wr)
        assert outs[t]["serve_fast"] == ref["serve_fast"]
        assert outs[t]["swaps"] == ref["swaps"]
        assert outs[t]["metadata_blocks"] == ref["metadata_blocks"]
