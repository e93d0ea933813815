"""Serving launcher: batched greedy decode with the engine.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \\
      --requests 8 --batch 4 --max-new 16

Request scheduling (DESIGN.md §9): ``--scheduler chunked`` enables
chunked prefill (``--prefill-chunk`` tokens per step) and, with
``--tenants``, multi-tenant QoS admission with a per-tenant fast-slot /
move-budget partition and direct-to-fast ingest for on-demand tenants.
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def _parse_tenants(spec: str):
    """"name[:weight[:policy]],..." -> tuple of TenantConfig, e.g.
    "interactive:2:on_demand,batch:1"."""
    from repro.serve.sched import TenantConfig
    out = []
    for part in spec.split(","):
        bits = part.strip().split(":")
        if not bits[0]:
            raise SystemExit(f"--tenants: empty tenant name in {spec!r}")
        weight = int(bits[1]) if len(bits) > 1 and bits[1] else 1
        policy = bits[2] if len(bits) > 2 and bits[2] else None
        out.append(TenantConfig(bits[0], weight=weight, policy=policy))
    return tuple(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--backend", choices=("dense", "tiered"),
                    default="dense",
                    help="KV backend: dense caches or per-layer Trimma "
                         "tiered stores (identical tokens, bit for bit)")
    ap.add_argument("--policy", default=None,
                    help="core/policy preset for --backend tiered")
    ap.add_argument("--scheduler", choices=("greedy", "chunked", "wave"),
                    default=None,
                    help="request scheduler (serve/sched, DESIGN.md §9): "
                         "greedy = PR 4 wave-refill bit for bit; chunked = "
                         "chunked prefill + multi-tenant QoS admission; "
                         "omitting the flag keeps greedy but the implicit "
                         "wave-refill default is deprecated ('wave' is a "
                         "deprecated greedy alias)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="--scheduler chunked: prompt tokens ingested per "
                         "engine step (page-aligned for tiered; 0 = "
                         "one-shot prefill, QoS-only)")
    ap.add_argument("--tenants", default=None,
                    help="multi-tenant QoS spec 'name[:weight[:policy]],"
                         "...' (e.g. 'interactive:2:on_demand,batch:1'); "
                         "requests are assigned round-robin across tenants "
                         "in this demo driver")
    ap.add_argument("--admit-pages", type=int, default=2,
                    help="direct-to-fast pages per ingest for on-demand "
                         "tenants (DESIGN.md §9 invalidation note)")
    ap.add_argument("--prom-out", default=None,
                    help="write the Prometheus text exposition here at "
                         "drain (DESIGN.md §10)")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append one JSON metrics sample per "
                         "--obs-every steps to this file")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace-event JSON (open in "
                         "https://ui.perfetto.dev) of the engine phases")
    ap.add_argument("--obs-every", type=int, default=4,
                    help="engine steps between metric samples")
    ap.add_argument("--flight", action="store_true",
                    help="page-lifecycle flight recorder (tiered only, "
                         "DESIGN.md §12): bounded in-graph event ring, "
                         "drained into residency / reuse / ping-pong "
                         "analytics at drain")
    ap.add_argument("--flight-capacity", type=int, default=2048,
                    help="--flight: event-ring slots (oldest drop first)")
    ap.add_argument("--slo", default=None,
                    help="per-tenant SLO spec "
                         "'tenant:stat:target_ms[:objective[:window]]"
                         ",...' (tenant '*' matches all; stat latency|"
                         "ttft), e.g. '*:latency:2000:0.9:64'")
    ap.add_argument("--http-port", type=int, default=None,
                    help="serve live /metrics + /healthz + /debug/state "
                         "on this port for the whole run (0 = ephemeral)")
    ap.add_argument("--hold", type=float, default=0.0,
                    help="--http-port: keep the endpoints up this many "
                         "seconds after drain (curl smoke window)")
    args = ap.parse_args()

    import jax
    from repro.configs import get_config, reduce_for_smoke
    from repro.models import init_params
    from repro.serve.engine import Engine, EngineConfig, Request
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    if cfg.is_encoder:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode serving")
    if args.scheduler is None:
        print("serve: note — no --scheduler given; keeping the greedy "
              "wave-refill default (deprecated as an implicit choice; "
              "pass --scheduler greedy or chunked; see DESIGN.md §9)",
              file=sys.stderr)
    tenants = _parse_tenants(args.tenants) if args.tenants else ()
    params = init_params(cfg, jax.random.key(0))
    obs = None
    if (args.prom_out or args.metrics_jsonl or args.trace_out
            or args.http_port is not None):
        from repro.obs import ObsConfig
        obs = ObsConfig(sample_every=args.obs_every,
                        prom_path=args.prom_out,
                        jsonl_path=args.metrics_jsonl,
                        trace_path=args.trace_out,
                        http_port=args.http_port)
    flight = None
    if args.flight:
        if args.backend != "tiered":
            raise SystemExit("--flight needs --backend tiered (the "
                             "recorder taps the Trimma move descriptors)")
        from repro.obs import FlightConfig
        flight = FlightConfig(capacity=args.flight_capacity)
    slos = ()
    if args.slo:
        from repro.obs import parse_slos
        slos = parse_slos(args.slo)
    try:
        eng = Engine(cfg, params, EngineConfig(
            batch=args.batch, max_len=args.max_len, backend=args.backend,
            policy=args.policy, scheduler=args.scheduler or "greedy",
            prefill_chunk=args.prefill_chunk, tenants=tenants,
            admit_pages=args.admit_pages, obs=obs, flight=flight,
            slos=slos))
    except NotImplementedError as e:
        raise SystemExit(f"{cfg.name}: {e}")
    if eng.obs_server is not None:
        print(f"obs: live endpoints at {eng.obs_server.url} "
              f"(/metrics /healthz /debug/state)")
    rng = np.random.default_rng(0)
    t0 = time.time()
    names = [t.name for t in tenants] or ["default"]
    for rid in range(args.requests):
        eng.submit(Request(rid=rid,
                           prompt=rng.integers(0, cfg.vocab, size=4),
                           max_new=args.max_new,
                           tenant_id=names[rid % len(names)]))
    done = eng.run(log=print)
    dt = time.time() - t0
    tok = sum(len(r.tokens) for r in done)
    print(f"served {len(done)} requests, {tok} tokens in {dt:.1f}s "
          f"({tok/dt:.1f} tok/s)")
    stats = eng.request_stats(done)
    lat = stats["aggregate"]["latency_ms"]
    ttft = stats["aggregate"]["ttft_ms"]
    if lat and ttft:     # empty when no request finished (e.g. 0 requests)
        print(f"latency p50 {lat['p50']:.1f} ms, p99 {lat['p99']:.1f} ms "
              f"(ttft p50 {ttft['p50']:.1f} ms)")
    if "fairness" in stats:
        print(f"fairness: {stats['fairness']}")
    if eng.counters:
        print(f"tiered counters: {eng.counters}")
    if eng.slo is not None:
        rows = eng.slo.summary()
        if not rows:
            print("slo: no completed requests observed")
        for r in rows:
            print(f"slo: {r['tenant']}/{r['stat']} target {r['target_ms']:g}"
                  f" ms obj {r['objective']:g} -> burn {r['burn_rate']:.2f}"
                  f" ({r['window_violations']}/{r['window_n']} violating "
                  f"in window) {'OK' if r['ok'] else 'BURNING'}")
    fs = eng.flight_stats()
    if fs is not None:
        if fs["n_events"] == 0:
            print("flight: no events recorded")
        else:
            res, pp = fs["residency"], fs["pingpong"]
            print(f"flight: {fs['n_events']} events "
                  f"({fs['dropped']} dropped) by_kind={fs['by_kind']}")
            if res.get("count"):
                print(f"flight: residency mean {res['mean_steps']:.1f} "
                      f"steps (p50 {res['p50_steps']:g}, max "
                      f"{res['max_steps']}), ping-pong {pp['events']} "
                      f"re-promotions within {pp['window_steps']} steps")
    if obs is not None:
        for label, path in (("prometheus", args.prom_out),
                            ("metrics jsonl", args.metrics_jsonl),
                            ("perfetto trace", args.trace_out)):
            if path:
                print(f"obs: {label} -> {path}")
    if eng.obs_server is not None:
        if args.hold > 0:
            print(f"obs: holding endpoints at {eng.obs_server.url} "
                  f"for {args.hold:g}s")
            time.sleep(args.hold)
        eng.obs_server.close()


if __name__ == "__main__":
    main()
