#!/usr/bin/env python3
"""Bring-up smoke: serve granite-moe-3b-a800m on one TPU through the engine.

    python chip_smoke.py [--seed N]

Builds the model at its published widths in bf16 with weights drawn from
the seed, then serves the same 8 requests through two ``Engine``s: the
dense KV backend and the tiered Trimma backend (prefill, fused paged
decode with iRT/iRC translation, promotion and demotion, release).  The
fast pool is set well below the live pages so that pages really move.

It fails, printing no result, unless JAX's first device is a TPU, every
request returns ``MAX_NEW`` tokens from both backends, the tiered run
promoted and released pages, the tiered decode step's compiled program
holds the Pallas kernel, the fused kernel agrees with its float32 oracle
on this run's own layer-0 decode inputs within ``KERNEL_TOL``, and the
tiered first-decode-step logits agree with the dense ones within
``LOGITS_TOL``.  The lines it prints are smoke facts, not benchmark
numbers.  The last line of stdout is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "granite-moe-3b-a800m"
BATCH, MAX_LEN, PAGE_TOKENS, FAST_SLOTS = 8, 1024, 16, 64
N_REQUESTS, MAX_NEW = 8, 32
# prompt lengths, inclusive: contexts of 257..383 tokens all pad to the
# one 512-row prefill program; ~26 live pages per lane, ~200 in all,
# against 64 fast slots
PROMPT_LEN = (258, 384)
# fused kernel (bf16 in, f32 accumulate, bf16 out) vs the oracle on the
# same inputs in float32: max |difference| over max |oracle|.  Rounding the
# output to bf16 alone costs up to 2^-9 of it
KERNEL_TOL = 1e-2
# tiered vs dense first-step logits, per lane: |l_t - l_d|_2 / |l_d|_2.
# The two read the same bf16 cache through different attention code.  At
# full width in bf16 that rounding alone leaves ~0.7% at any depth, and a
# top-8 expert choice it tips over adds ~3% more (both seen on XLA:CPU at
# 1-8 layers); in float32 the two agree to 1e-5
LOGITS_TOL = 1e-1

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Seconds spent in XLA compilation (or reading a compiled program
    back from the persistent cache) and persistent-cache hits, as JAX's
    monitoring events report them."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == _COMPILE_EVENT:
            self.seconds += secs

    def _event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1


def make_prompts(vocab: int, seed: int, n: int, lo: int, hi: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi + 1, size=n)
    return [rng.integers(0, vocab, size=int(m)).astype(np.int32)
            for m in lens]


def serve(cfg, params, ec, prompts, max_new: int, clock: CompileClock):
    """Serve ``prompts`` twice through one engine: a cold run that
    compiles, then a warm run whose outputs are checked.  Returns the
    engine and the warm run's books."""
    from repro.serve.engine import Engine, Request

    eng = Engine(cfg, params, ec)

    def run(log_logits: bool):
        eng.logits_log = [] if log_logits else None
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid=rid, prompt=p, max_new=max_new))
        t0, c0 = time.perf_counter(), clock.seconds
        done = eng.run()
        return done, time.perf_counter() - t0, clock.seconds - c0

    _, cold_s, cold_compile_s = run(False)
    done, warm_s, warm_compile_s = run(True)
    done = sorted(done, key=lambda r: r.rid)
    return eng, {
        "cold_wall_s": cold_s, "compile_s": cold_compile_s,
        "run_s": warm_s, "warm_compile_s": warm_compile_s,
        "tokens": [list(r.tokens) for r in done],
        "first_logits": eng.logits_log[0].astype("float32"),
    }


def step_has_pallas(eng, params) -> bool:
    """Does every decode-step program the tiered engine compiled hold the
    Pallas custom call?"""
    import jax
    import jax.numpy as jnp
    ec = eng.ec
    state = jax.eval_shape(lambda: eng.backend.init_state(ec.batch,
                                                          ec.max_len))
    tokens = jax.ShapeDtypeStruct((ec.batch,), jnp.int32)
    texts = [eng._step_fn(n).lower(params, state, tokens).compile().as_text()
             for n in eng._step_fns]
    return bool(texts) and all("tpu_custom_call" in t for t in texts)


def kernel_error(cfg, params, state, prompts, n_pages: int, seed: int):
    """Max |fused kernel - float32 oracle| / max |oracle| over the first
    decode step's layer-0 inputs: the query and new K/V row of each
    prompt's last token
    at its decode position, against the tiered run's layer-0 pools (the
    prompt pages sit at their slow homes).  Read twice: routed as the
    first step read them (all slow), and with a seeded set of pages
    routed to distinct fast slots so the kernel's fast-tier fetch runs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attention.ops import paged_attention_fused_op
    from repro.kernels.paged_attention.ref import paged_attention_fused_ref
    from repro.models.attention import _qkv
    from repro.models.layers import rms_norm

    B = len(prompts)
    KV, hd = cfg.n_kv_heads, cfg.hd
    G = cfg.n_heads // KV
    p0 = jax.tree.map(lambda x: x[0], params["blocks"])
    last = jnp.asarray([int(p[-1]) for p in prompts], jnp.int32)
    pos = jnp.asarray([len(p) - 1 for p in prompts], jnp.int32)
    x = jnp.take(params["embed"], last[:, None], axis=0)
    h = rms_norm(x, p0["norm1"], cfg.rms_eps)
    q, k, v = _qkv(p0["attn"], h, cfg, pos[:, None])
    q = q.reshape(B, 1, KV, G, hd)
    pools = [x[0] for x in (state.caches.fast_k, state.caches.fast_v,
                            state.caches.slow_k, state.caches.slow_v)]
    n_fast = pools[0].shape[0]

    slow_only = np.full((B, n_pages), -1, np.int32)
    routed = slow_only.copy()
    pick = np.random.default_rng(seed).permutation(B * n_pages)[:n_fast]
    routed.reshape(-1)[pick] = np.arange(n_fast, dtype=np.int32)

    err = 0.0
    for entries in (slow_only, routed):
        args = (q, *pools, jnp.asarray(entries), k, v, pos)
        got = paged_attention_fused_op(*args, impl="kernel")
        with jax.default_matmul_precision("highest"):
            want = paged_attention_fused_ref(
                *[a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a
                  for a in args])
        err = max(err, float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                              - want))
                             / jnp.max(jnp.abs(want))))
    return err


def smoke(cfg, *, seed: int, batch: int, max_len: int, page_tokens: int,
          fast_slots: int, n_requests: int, max_new: int,
          prompt_len: tuple[int, int], log=print) -> dict:
    """Serve one request mix through the dense and the tiered engine and
    return the facts ``main`` checks."""
    import jax
    import numpy as np
    from repro.models import init_params
    from repro.serve.engine import EngineConfig

    clock = CompileClock()
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg, jax.random.key(seed)))
    facts = {"param_bytes": sum(x.nbytes for x in jax.tree.leaves(params)),
             "init_s": time.perf_counter() - t0}
    log(f"smoke: {cfg.name} L={cfg.n_layers} d={cfg.d_model} "
        f"H={cfg.n_heads} KV={cfg.n_kv_heads} hd={cfg.hd} "
        f"experts={cfg.n_experts} top{cfg.top_k} vocab={cfg.vocab} "
        f"{cfg.dtype}, {facts['param_bytes']} parameter bytes, "
        f"init {facts['init_s']}s")
    prompts = make_prompts(cfg.vocab, seed, n_requests, *prompt_len)

    runs = {}
    for backend in ("dense", "tiered"):
        ec = EngineConfig(batch=batch, max_len=max_len, backend=backend,
                          page_tokens=page_tokens,
                          fast_data_slots=fast_slots, scheduler="greedy")
        eng, r = serve(cfg, params, ec, prompts, max_new, clock)
        r["served"] = sum(len(t) for t in r["tokens"])
        log(f"smoke: {backend}: {len(r['tokens'])} requests, "
            f"{r['served']} tokens; compile {r['compile_s']}s (cold run "
            f"{r['cold_wall_s']}s), warm run {r['run_s']}s with "
            f"{r['warm_compile_s']}s compiling")
        if backend == "tiered":
            c = eng.counters
            r["counters"] = {k: c[k] for k in ("lookups", "irc_hits",
                                               "migrations", "demotions")}
            r["releases"] = eng.releases
            log(f"smoke: tiered counters (summed over layers): "
                f"translated pages {c['lookups']}, iRC hits "
                f"{c['irc_hits']}, pages promoted {c['migrations']}, "
                f"demoted {c['demotions']}; lane releases {eng.releases}")
            r["pallas_in_step"] = step_has_pallas(eng, params)
            n_pages = max(n for n in eng._step_fns if n is not None)
            r["kernel_err"] = kernel_error(cfg, params, eng.final_state,
                                           prompts, n_pages, seed)
        runs[backend] = r
        del eng

    d, t = runs["dense"], runs["tiered"]
    diff = np.linalg.norm(t["first_logits"] - d["first_logits"], axis=-1)
    logits_rel = float((diff / np.linalg.norm(d["first_logits"],
                                              axis=-1)).max())
    pairs = [(a, b) for ta, tb in zip(d["tokens"], t["tokens"])
             for a, b in zip(ta, tb)]
    agree = sum(a == b for a, b in pairs) / max(len(pairs), 1)
    facts.update(runs=runs, logits_rel_err=logits_rel, token_agreement=agree,
                 compile_cache_hits=clock.cache_hits)
    log(f"smoke: fused kernel vs float32 oracle max |err|/max|oracle| "
        f"{t['kernel_err']} (tol {KERNEL_TOL}); first-step logits tiered "
        f"vs dense max rel L2 err {logits_rel} (tol {LOGITS_TOL}); "
        f"greedy token agreement {agree}; "
        f"Pallas call in tiered step: {t['pallas_in_step']}; "
        f"persistent compile-cache hits {clock.cache_hits}")
    return facts


def failures(facts: dict, max_new: int, n_requests: int) -> list[str]:
    out = []
    for name, r in facts["runs"].items():
        if (len(r["tokens"]) != n_requests
                or any(len(t) != max_new for t in r["tokens"])):
            out.append(f"{name}: not every request returned {max_new} "
                       f"tokens")
    t = facts["runs"]["tiered"]
    if not (t["counters"]["migrations"] > 0 and t["releases"] > 0):
        out.append("tiered: no page was promoted or no lane released")
    if not t["pallas_in_step"]:
        out.append("tiered decode step holds no Pallas custom call")
    if not t["kernel_err"] <= KERNEL_TOL:
        out.append(f"fused kernel off its oracle by {t['kernel_err']}")
    if not facts["logits_rel_err"] <= LOGITS_TOL:
        out.append(f"tiered logits off dense by {facts['logits_rel_err']}")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform} ({dev.device_kind})", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.utils.compile_cache import use_compile_cache
    print(f"smoke: device_kind={dev.device_kind}, compile cache "
          f"{use_compile_cache()}")
    facts = smoke(get_config(ARCH), seed=args.seed, batch=BATCH,
                  max_len=MAX_LEN, page_tokens=PAGE_TOKENS,
                  fast_slots=FAST_SLOTS, n_requests=N_REQUESTS,
                  max_new=MAX_NEW, prompt_len=PROMPT_LEN)
    stats = dev.memory_stats() or {}
    print(f"smoke: peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    bad = failures(facts, MAX_NEW, N_REQUESTS)
    if bad:
        for b in bad:
            print(f"chip_smoke: FAIL {b}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
