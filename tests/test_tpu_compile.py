"""Main-path kernels and one whole tiered decode step, compiled for a TPU
v5e that is described and not attached, at granite-moe-3b-a800m widths.

Nothing runs: these catch what the chip's compiler refuses (block shapes
off the tiling, scalar or vector memory over capacity) without a chip.
The topology is described inside a fixture, never at import, so that only
the worker that runs this file loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config

GRANITE = "granite-moe-3b-a800m"
B, MAX_LEN, PAGE, FAST_SLOTS = 8, 1024, 16, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize("k_tok", [1, 4])
def test_fused_paged_attention_compiles(one_chip, k_tok):
    from repro.kernels.paged_attention.paged_attention import \
        paged_attention_fused
    cfg = get_config(GRANITE)
    KV, hd = cfg.n_kv_heads, cfg.hd
    G = cfg.n_heads // KV
    npages = MAX_LEN // PAGE
    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = (FAST_SLOTS, KV, PAGE, hd)
    homes = (B * npages, KV, PAGE, hd)
    new = (B, k_tok, KV, hd)
    c = _compile(paged_attention_fused,
                 _sds(one_chip, (B, k_tok, KV, G, hd), bf16),
                 _sds(one_chip, pool, bf16), _sds(one_chip, pool, bf16),
                 _sds(one_chip, homes, bf16), _sds(one_chip, homes, bf16),
                 _sds(one_chip, (B, npages), i32),
                 _sds(one_chip, new, bf16), _sds(one_chip, new, bf16),
                 _sds(one_chip, (B,), i32))
    assert "tpu_custom_call" in c.as_text()


def test_remap_gather_compiles(one_chip):
    from repro.kernels.remap_gather.remap_gather import remap_gather
    cfg = get_config(GRANITE)
    # the stacked migration copy: one page per layer out of [L*homes] rows
    rows = cfg.n_layers * B * (MAX_LEN // PAGE)
    c = _compile(remap_gather,
                 _sds(one_chip, (rows, cfg.n_kv_heads * PAGE, cfg.hd),
                      jnp.bfloat16),
                 _sds(one_chip, (cfg.n_layers,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_irt_lookup_compiles(one_chip):
    from repro.core.remap import irt
    from repro.kernels.irt_lookup.irt_lookup import irt_lookup
    n_ids, table = irt.KERNEL_MIN_BATCH, irt.KERNEL_MAX_TABLE
    i32 = jnp.int32
    c = _compile(lambda ids, home, bits, leaf: irt_lookup(
                     ids, home, bits, leaf, block=irt.KERNEL_BLOCK),
                 _sds(one_chip, (n_ids,), i32), _sds(one_chip, (n_ids,), i32),
                 _sds(one_chip, (irt.n_words(table // irt.E),), i32),
                 _sds(one_chip, (table,), i32))
    assert "tpu_custom_call" in c.as_text()


def test_tiered_decode_step_compiles(one_chip, monkeypatch):
    """One full-width tiered decode step holds the fused Pallas kernel:
    the op's backend choice is steered to the TPU branch, as it is on the
    chip, since the compiling process itself only sees the CPU."""
    from repro.kernels.paged_attention import ops
    from repro.models import decode_step
    from repro.models.kv_backend import TieredBackend
    from repro.models.transformer import abstract_params_and_axes
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = get_config(GRANITE)
    bk = TieredBackend(cfg, B, MAX_LEN, page_tokens=PAGE,
                       fast_data_slots=FAST_SLOTS)

    def place(tree):
        return jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), tree)

    params = place(abstract_params_and_axes(cfg)[0])
    state = place(jax.eval_shape(lambda: bk.init_state(B, MAX_LEN)))
    c = _compile(lambda p, s, t: decode_step(cfg, p, s, t, backend=bk,
                                             n_pages=32),
                 params, state, _sds(one_chip, (B,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()
