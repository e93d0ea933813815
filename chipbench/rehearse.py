#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py <cell> [<cell> ...]

For each cell of ``BENCHMARK.json`` it builds the engine as ``run.py``
does, with weights and KV state as shapes placed on one chip of a
described v5e:2x2, and compiles the programs a run drives: the weight
draw, the decode step at the widest attention bucket, the chunked-prefill
forward and chunk write at the longest padded prompt (or the one-shot
prefill), the maintenance plan and apply, and the reference's forward at
the longest sequence.  It prints each program's ``memory_analysis()`` and
the most that any serving program holds while it runs, the weights
included, against the chip's memory.  What the chip's compiler would
refuse, it raises here.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent


def _gb(n) -> str:
    return f"{n / 1e9:.3f} GB"


def rehearse(bench: dict, name: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import model, run
    from chipbench.references import decoder
    from repro.kernels.irt_lookup import ops as irt_ops
    from repro.kernels.paged_attention import ops as pa_ops
    from repro.kernels.remap_gather import ops as rg_ops
    from repro.serve.engine import Engine, EngineConfig, padded_len
    from repro.serve.sched import make_scheduler

    # the compiling process sees only the CPU: steer the kernel ops to
    # their TPU branch, as they take it on the chip
    for m in (irt_ops, pa_ops, rg_ops):
        m._on_tpu = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    cell = run.load_cell(bench, name, trace=False)
    mc, serve = cell.mc, cell.mix["serve"]
    cfg = model.arch_config(mc)
    key = jax.random.key(0)
    params = place(jax.eval_shape(model._params, cfg, key))
    ec = EngineConfig(
        batch=serve["batch"], max_len=serve["max_len"],
        backend=serve["backend"], page_tokens=serve["page_tokens"],
        fast_data_slots=serve["fast_data_slots"], policy=serve["policy"],
        maintain_every=serve["maintain_every"],
        scheduler=serve["scheduler"], prefill_chunk=serve["prefill_chunk"])
    eng = Engine(cfg, params, ec, scheduler=make_scheduler(ec))
    state = place(jax.eval_shape(
        lambda: eng.backend.init_state(ec.batch, ec.max_len)))
    tokens = sds((ec.batch,), jnp.int32)
    i32 = sds((), jnp.int32)
    size = lambda t: sum(x.size * x.dtype.itemsize  # noqa: E731
                         for x in jax.tree.leaves(t))
    print(f"== {name}: weights {_gb(size(params))}, KV state "
          f"{_gb(size(state))}", flush=True)

    progs = {"weights": (model._params, (cfg, place(key)))}
    progs["decode step"] = (eng._step_fn(None), (params, state, tokens))
    P = padded_len(cell.mix["prompt_len"]["hi"], ec.max_len)
    if ec.scheduler == "chunked" and ec.prefill_chunk > 0:
        C = min(eng.scheduler.chunk if hasattr(eng.scheduler, "chunk")
                else ec.prefill_chunk, P)
        bk = place(jax.eval_shape(lambda: eng.chunk_buffers(P)))
        fwd = eng.chunk_fwd(P, C, logits=True)
        progs["prefill chunk"] = (fwd, (params, sds((1, C), jnp.int32),
                                        bk[0], bk[1], i32))
        eng.write_chunk(C)
        progs["chunk write"] = (eng._write_chunk_fns[C],
                                (state, i32, bk[0], bk[1], i32, i32))
    else:
        progs["prefill"] = (eng._prefill_fn(P), (params, state, i32,
                                                 sds((1, P), jnp.int32),
                                                 i32))
    if serve["backend"] == "tiered":
        plan = jax.eval_shape(eng._plan_fn, state)
        progs["maintain plan"] = (eng._plan_fn, (state,))
        progs["maintain apply"] = (eng._apply_fn, (state, place(plan)))
    rows = 1 << (cell.mix["output_len"]["hi"] - 1).bit_length()
    progs["reference"] = (
        decoder._logit_rows,
        (decoder._freeze(mc), params, sds((ec.max_len,), jnp.int32),
         sds((rows,), jnp.int32), None))

    peak = {}
    for label, (fn, args) in progs.items():
        compiled = fn.lower(*args).compile()
        m = compiled.memory_analysis()
        # resident while it runs: its buffers, and the weights when they
        # are not among them
        held = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        peak[label] = held + (0 if any(a is params for a in args)
                              or label == "weights"
                              else size(params))
        pallas = "tpu_custom_call" in compiled.as_text()
        print(f"{label:>15}: args {_gb(m.argument_size_in_bytes)}, out "
              f"{_gb(m.output_size_in_bytes)}, temp "
              f"{_gb(m.temp_size_in_bytes)}, alias "
              f"{_gb(m.alias_size_in_bytes)}"
              f"{', Pallas kernel' if pallas else ''}", flush=True)
    top = max((k for k in peak if k != "reference"), key=peak.get)
    print(f"   largest while serving: {top}, {_gb(peak[top])} with the "
          f"weights, of the chip's 16 GB; the reference after the KV is "
          f"freed: {_gb(peak['reference'])}", flush=True)


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(REPO / "src"), str(REPO)]
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    with open(REPO / "BENCHMARK.json") as f:
        bench = json.load(f)
    names = argv or [w["name"] for w in bench["workloads"]]
    for name in names:
        rehearse(bench, name)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
