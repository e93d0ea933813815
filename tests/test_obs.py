"""Unified telemetry layer (DESIGN.md §10): registry declarations,
in-graph metric ops, taps vs legacy counters, hub snapshot/delta +
Prometheus round-trip, the step tracer's Perfetto JSON, and the
end-to-end engine contract (artifacts emitted, tokens bit-identical to a
metrics-off run)."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (MetricsHub, ObsConfig, StepTracer, metrics,
                       parse_prometheus, registry, trace)


@functools.lru_cache(maxsize=1)
def _smoke_model():
    from repro.configs import get_config, reduce_for_smoke
    from repro.models import init_params
    cfg = reduce_for_smoke(get_config("llama3-8b"))
    return cfg, init_params(cfg, jax.random.key(0))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_covers_the_stack():
    # declarations live next to the code that owns them — importing the
    # owning modules populates the registry
    import repro.core.policy.scheduler  # noqa: F401
    import repro.core.remap.irt  # noqa: F401
    import repro.core.remap.rcache  # noqa: F401
    import repro.serve.engine  # noqa: F401
    import repro.serve.sched.qos  # noqa: F401
    import repro.tiered.kvcache  # noqa: F401
    names = set(registry.registered())
    required = {
        "trimma_translated_pages_total", "trimma_irc_hits_total",
        "trimma_irc_misses_total", "trimma_irt_walks_total",
        "trimma_dev_table_hits_total", "trimma_migrations_total",
        "trimma_promoted_bytes_total", "trimma_demoted_bytes_total",
        "trimma_fast_resident_pages", "trimma_metadata_pages",
        "engine_steps_total", "engine_tokens_total",
        "engine_request_latency_ms", "engine_token_latency_ms",
        "engine_tenant_admitted_total",
    }
    assert required <= names, sorted(required - names)
    assert len(names) >= 12
    for n in required:
        assert registry.spec(n).help, n


def test_register_conflict_raises():
    registry.register(registry.MetricSpec("obs_test_metric_x", "counter",
                                          "a test metric"))
    # idempotent re-registration is fine
    registry.register(registry.MetricSpec("obs_test_metric_x", "counter",
                                          "a test metric"))
    with pytest.raises(ValueError):
        registry.register(registry.MetricSpec("obs_test_metric_x", "gauge",
                                              "a different spec"))


def test_unregistered_spec_inferred():
    s = registry.spec("obs_never_declared_total")
    assert s.kind == "counter"
    assert registry.spec("obs_never_declared").kind == "gauge"


def test_sim_counter_order_is_golden_order():
    from repro.core import simulator
    assert simulator.COUNTERS == registry.sim_counter_keys()
    assert len(simulator.COUNTERS) == 19


# ---------------------------------------------------------------------------
# in-graph ops
# ---------------------------------------------------------------------------

def test_hist_bucket_edges():
    assert metrics.HIST_EDGES_MS[0] == 0.25
    assert metrics.HIST_BUCKETS == 13
    assert metrics.bucket_index(0.0) == 0
    assert metrics.bucket_index(0.2499) == 0
    assert metrics.bucket_index(0.25) == 1        # edge opens its bucket
    assert metrics.bucket_index(511.9) == 11      # [256, 512)
    assert metrics.bucket_index(512.0) == 12      # last edge -> +Inf bucket
    assert metrics.bucket_index(1e9) == 12


def test_hist_observe_jit_vmap_safe():
    @jax.jit
    def step(counts, vals, en):
        return metrics.hist_observe(counts, vals, en)

    counts = step(metrics.hist_zeros(),
                  jnp.asarray([0.1, 0.25, 600.0, 3.0]),
                  jnp.asarray([True, True, True, False]))
    counts = np.asarray(counts)
    assert counts.sum() == 3                      # disabled lane dropped
    assert counts[0] == 1 and counts[1] == 1 and counts[12] == 1

    batched = jax.vmap(lambda v: metrics.hist_observe(
        metrics.hist_zeros(), v))(jnp.ones((4, 2)))
    assert batched.shape == (4, metrics.HIST_BUCKETS)
    assert np.asarray(batched).sum() == 8


def test_counter_ops_in_graph():
    m = metrics.zeros(["a_total", "b_total"])

    @jax.jit
    def f(m):
        m = metrics.inc(m, "a_total")
        m = metrics.inc(m, "b_total", delta=2,
                        enable=jnp.asarray([True, False, True]))
        return m

    out = f(m)
    assert int(out["a_total"]) == 1
    assert int(out["b_total"]) == 4
    d = metrics.delta(out, m)
    assert int(d["a_total"]) == 1
    merged = metrics.merge(out, out)
    assert int(merged["b_total"]) == 8


# ---------------------------------------------------------------------------
# taps
# ---------------------------------------------------------------------------

def _tiny_store():
    from repro.tiered import kvcache as tk
    cfg = tk.TieredConfig(n_seqs=2, max_pages_per_seq=16, page_tokens=8,
                          n_kv_heads=2, head_dim=16, fast_data_slots=4,
                          migrate_threshold=1, dtype="float32")
    st = tk.init_state(cfg)
    ids = tk.logical_page(cfg, jnp.arange(cfg.n_seqs)[:, None],
                          jnp.arange(4)[None, :])
    for _ in range(3):                    # touch -> hot -> migrate
        _, st = tk.lookup(cfg, st, ids)
    st = tk.migrate_hot(cfg, st, max_moves=2)
    _, st = tk.lookup(cfg, st, ids)       # post-migration: iRC/iRT traffic
    return cfg, st


def test_tiered_tap_matches_legacy_counters():
    from repro.serve import tiered as srv
    cfg, st = _tiny_store()
    m = {k: int(v) for k, v in srv.metrics(cfg, st).items()}
    legacy = metrics.legacy_counters(m)
    assert legacy["lookups"] == m["trimma_translated_pages_total"]
    assert legacy["migrations"] == m["trimma_migrations_total"]
    assert m["trimma_irc_misses_total"] == m["trimma_irt_walks_total"] == \
        m["trimma_translated_pages_total"] - m["trimma_irc_hits_total"]
    assert m["trimma_promoted_bytes_total"] % cfg.page_bytes == 0
    assert m["trimma_fast_resident_pages"] >= 0
    assert m["trimma_metadata_pages"] > 0


def test_tiered_tap_sums_stacked_axis():
    from repro.serve import tiered as srv
    cfg, st = _tiny_store()
    stacked = jax.tree.map(lambda x: jnp.stack([x, x]), st)
    one = {k: float(v) for k, v in srv.metrics(cfg, st).items()}
    two = {k: float(v) for k, v in srv.metrics(cfg, stacked).items()}
    # ratio gauges are scale-invariant over stacking (metadata is
    # layer-uniform); every counter/byte metric sums the stacked axis
    invariant = {"trimma_identity_entry_ratio", "trimma_irt_leaf_occupancy"}
    for k in one:
        if k in invariant:
            assert two[k] == one[k], k
        else:
            assert two[k] == 2 * one[k], k


def test_stashed_metrics_equals_direct_tap():
    from repro.serve import tiered as srv
    cfg, st = _tiny_store()
    direct = {k: float(v) for k, v in srv.metrics(cfg, st).items()}
    stash = metrics.tap_stash(st)
    from repro.tiered import kvcache as tk
    via = {k: float(v) for k, v in
           metrics.stashed_metrics(stash, cfg.page_bytes,
                                   n_logical=cfg.n_logical,
                                   fast_slots=cfg.fast_slots,
                                   leaf_entries=tk.E).items()}
    assert via == direct


# ---------------------------------------------------------------------------
# hub
# ---------------------------------------------------------------------------

def test_hub_snapshot_delta_and_jsonl(tmp_path):
    jsonl = tmp_path / "m.jsonl"
    hub = MetricsHub(ObsConfig(jsonl_path=str(jsonl)))
    hub.record({"trimma_irc_hits_total": 10})
    hub.set("engine_queue_depth", 3)
    row1 = hub.sample(step=1)
    assert row1["metrics"]["trimma_irc_hits_total"] == 10
    assert row1["deltas"]["trimma_irc_hits_total"] == 10
    assert "engine_queue_depth" not in row1["deltas"]   # gauges: no delta
    hub.record({"trimma_irc_hits_total": 25})
    row2 = hub.sample(step=2)
    assert row2["deltas"]["trimma_irc_hits_total"] == 15
    hub.finalize(step=3)
    rows = [json.loads(line) for line in
            jsonl.read_text().strip().splitlines()]
    assert len(rows) == 3
    assert [r["step"] for r in rows] == [1, 2, 3]


def test_hub_prometheus_round_trip(tmp_path):
    hub = MetricsHub(ObsConfig(prom_path=str(tmp_path / "p.txt")))
    hub.record({"trimma_irc_hits_total": 7, "trimma_fast_resident_pages": 3})
    hub.set("engine_tenant_tokens_total", 11, labels={"tenant": "a"})
    hub.observe_hist("engine_token_latency_ms", metrics.HIST_EDGES_MS,
                     [1] * metrics.HIST_BUCKETS, 123.5)
    path = hub.write_prometheus()
    parsed = parse_prometheus(open(path).read())
    fams = parsed["families"]
    assert fams["trimma_irc_hits_total"] == "counter"
    assert fams["trimma_fast_resident_pages"] == "gauge"
    assert fams["engine_token_latency_ms"] == "histogram"
    s = parsed["samples"]
    assert s["trimma_irc_hits_total"] == 7
    assert s['engine_tenant_tokens_total{tenant="a"}'] == 11
    assert s['engine_token_latency_ms_bucket{le="+Inf"}'] == 13  # cumulative
    assert s["engine_token_latency_ms_count"] == 13
    assert s["engine_token_latency_ms_sum"] == 123.5


def test_parse_prometheus_labeled_series_round_trip():
    """The structural (name, labels, value) view: every emitted sample
    must decompose into its labels and re-render to the exact flat key —
    the exposition/parsing asymmetry regression (labelled families used
    to come back only as opaque flat strings)."""
    from repro.obs.hub import _labels_key, _render_name
    hub = MetricsHub()
    hub.set("engine_tenant_tokens_total", 11, labels={"tenant": "a"})
    hub.set("engine_tenant_tokens_total", 22, labels={"tenant": "b"})
    hub.set("engine_slo_burn_rate", 1.5,
            labels={"tenant": "a", "stat": "latency"})
    hub.record({"engine_steps_total": 4})
    parsed = parse_prometheus(hub.to_prometheus())
    series = parsed["series"]
    assert [e["labels"]["tenant"]
            for e in series["engine_tenant_tokens_total"]] == ["a", "b"]
    assert series["engine_slo_burn_rate"][0] == {
        "labels": {"tenant": "a", "stat": "latency"}, "value": 1.5}
    assert series["engine_steps_total"] == [{"labels": {}, "value": 4.0}]
    # structural view and flat view agree sample for sample
    flat = dict(parsed["samples"])
    for name, entries in series.items():
        for e in entries:
            key = _render_name(name, _labels_key(e["labels"]))
            assert flat.pop(key) == e["value"], key
    assert not flat                       # nothing the series view missed


def test_label_escaping_round_trips():
    """Label values containing the exposition format's escape set
    (backslash, double-quote, newline) must survive emit -> parse —
    previously the renderer emitted them raw, producing an exposition
    the parser (and any real scraper) could not read back."""
    from repro.obs.hub import parse_labels
    evil = 'a"b\\c\nd'
    hub = MetricsHub()
    hub.set("engine_queue_depth", 1, labels={"tenant": evil})
    text = hub.to_prometheus()
    assert '\\n' in text and '\\"' in text      # escaped on the wire
    parsed = parse_prometheus(text)
    e = parsed["series"]["engine_queue_depth"][0]
    assert e["labels"]["tenant"] == evil
    # the low-level inverse as well
    name, labels = parse_labels(
        'x_total{a="q\\"uote",b="back\\\\slash",c="new\\nline"}')
    assert name == "x_total"
    assert labels == {"a": 'q"uote', "b": "back\\slash", "c": "new\nline"}


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_perfetto_json(tmp_path):
    tr = StepTracer()
    with tr.span("decode_step", step=1):
        pass
    with tr.span("maintain", step=2):
        pass
    tr.counter("trimma_pages", {"fast_resident": 4.0}, ts=10.0)
    path = tr.save(str(tmp_path / "t.json"))
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in spans} == {"decode_step", "maintain"}
    for e in spans:
        assert e["dur"] >= 0 and e["ts"] >= 0
        assert e["tid"] == StepTracer.TIDS[e["name"]]
    cnt = next(e for e in evs if e["ph"] == "C")
    assert cnt["ts"] == 10.0
    assert any(e["ph"] == "M" for e in evs)       # process/thread names
    # clear(): fresh trace, metadata kept
    tr.clear()
    assert all(e["ph"] == "M" for e in tr.events)


def test_null_tracer_is_inert():
    nt = trace.NULL_TRACER
    with nt.span("decode_step"):
        pass
    nt.counter("x", {})
    nt.clear()
    with pytest.raises(RuntimeError):
        nt.save("/dev/null")


# ---------------------------------------------------------------------------
# end to end: engine run with obs enabled
# ---------------------------------------------------------------------------

def _run_engine(obs, seed=3, **cfg_kw):
    from repro.serve.engine import Engine, EngineConfig, Request
    cfg, params = _smoke_model()
    eng = Engine(cfg, params, EngineConfig(
        batch=2, max_len=64, backend="tiered", page_tokens=8,
        fast_data_slots=4, maintain_every=2, obs=obs, **cfg_kw))
    rng = np.random.default_rng(seed)
    for rid in range(4):
        eng.submit(Request(rid=rid, prompt=rng.integers(0, cfg.vocab, 4),
                           max_new=8))
    return eng, eng.run()


def test_engine_emits_artifacts(tmp_path):
    prom = tmp_path / "prom.txt"
    jsonl = tmp_path / "m.jsonl"
    tr = tmp_path / "trace.json"
    obs = ObsConfig(sample_every=2, prom_path=str(prom),
                    jsonl_path=str(jsonl), trace_path=str(tr))
    eng, done = _run_engine(obs)
    assert len(done) == 4

    parsed = parse_prometheus(prom.read_text())
    assert len(parsed["families"]) >= 12
    s = parsed["samples"]
    assert s["trimma_translated_pages_total"] > 0
    assert s["engine_steps_total"] == eng.steps
    assert s["engine_tokens_total"] == sum(len(r.tokens) for r in done)
    assert any(k.startswith("engine_request_latency_ms") for k in s)

    doc = json.loads(tr.read_text())
    phases = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"decode_step", "prefill", "maintain", "release"} <= phases

    rows = [json.loads(line) for line in
            jsonl.read_text().strip().splitlines()]
    assert len(rows) >= 2
    # counter deltas are non-negative and sum to the final total
    deltas = [r["deltas"].get("engine_tokens_total", 0) for r in rows]
    assert all(d >= 0 for d in deltas)
    assert sum(deltas) == s["engine_tokens_total"]


def test_engine_tokens_identical_with_obs(tmp_path):
    obs = ObsConfig(sample_every=2, prom_path=str(tmp_path / "p.txt"))
    _, done_on = _run_engine(obs)
    _, done_off = _run_engine(None)
    toks_on = {r.rid: r.tokens for r in done_on}
    toks_off = {r.rid: r.tokens for r in done_off}
    assert toks_on == toks_off


def test_engine_trace_covers_one_run(tmp_path):
    tr = tmp_path / "trace.json"
    obs = ObsConfig(sample_every=4, trace_path=str(tr))
    eng, done = _run_engine(obs)
    n1 = len(json.loads(tr.read_text())["traceEvents"])
    # second run through the same engine: the trace is reset, not grown
    from repro.serve.engine import Request
    rng = np.random.default_rng(5)
    cfg, _ = _smoke_model()
    for rid in range(4):
        eng.submit(Request(rid=rid, prompt=rng.integers(0, cfg.vocab, 4),
                           max_new=8))
    eng.run()
    n2 = len(json.loads(tr.read_text())["traceEvents"])
    assert n2 <= n1 + 8                  # same-shaped run, not doubled
