"""Idle time by owning span and device time by program, on synthetic
events, on a trace recorded here and on a tiny cell's traced run."""

import pytest

from chipbench import spans, trace

MS = 1_000_000


def _span(name, s, e, **stats):
    return (name, s * MS, e * MS, stats)


def _reduce(window, ops=None, prog=(), waits=(), mods=None):
    return spans.reduce_spans((window[0] * MS, window[1] * MS),
                              {"/device:TPU:0": ops or []}, list(prog),
                              list(waits), mods or {})


def test_an_idle_instant_goes_to_the_innermost_program_span():
    ops = [("op", 0, 40 * MS), ("op", 70 * MS, 100 * MS)]
    prog = [_span("engine.step", 0, 100, step_num=7),
            _span("engine.sync", 35, 60, step=7)]
    r = _reduce((0, 100), ops, prog)
    assert r.idle_by_owner == pytest.approx({"engine.sync": 0.02,
                                             "engine.step": 0.01})
    assert r.program_spans == prog


def test_gate_wait_wins_over_the_refill_around_it():
    ops = [("op", 0, 10 * MS), ("op", 50 * MS, 60 * MS)]
    prog = [_span("engine.step", 0, 60), _span("engine.refill", 10, 50)]
    r = _reduce((0, 60), ops, prog, [("gate.wait", 20 * MS, 40 * MS)])
    assert r.idle_by_owner == pytest.approx({"engine.refill": 0.02,
                                             "gate.wait": 0.02})


def test_idle_no_span_covers_is_uncovered_and_owners_sum_to_the_idle_time():
    ops = [("op", 10 * MS, 20 * MS), ("op", 30 * MS, 35 * MS),
           ("op", 80 * MS, 90 * MS)]
    prog = [_span("engine.step", 15, 50), _span("engine.harvest", 20, 25),
            _span("sched.park", 22, 24), _span("engine.step", 60, 85)]
    r = _reduce((0, 100), ops, prog)
    own = r.idle_by_owner
    # idle [0,10) [20,30) [35,80) [90,100)
    assert own["sched.park"] == pytest.approx(0.002)
    assert own["engine.harvest"] == pytest.approx(0.003)
    assert own["engine.step"] == pytest.approx(0.005 + 0.015 + 0.020)
    assert own[spans.UNCOVERED] == pytest.approx(0.010 + 0.010 + 0.010)
    assert sum(own.values()) == pytest.approx(r.window_s - r.busy_s)
    # each gap under the owner of most of it, longest first: [20,30) is
    # 5 ms of engine.step against 3 of harvest and 2 of park
    assert r.gap_owners == [("engine.step", pytest.approx(0.045)),
                            (spans.UNCOVERED, pytest.approx(0.01)),
                            ("engine.step", pytest.approx(0.01)),
                            (spans.UNCOVERED, pytest.approx(0.01))]


def test_busy_time_agrees_with_the_benchmark_reduction():
    evs = {"/device:TPU:0": [("a", 0, 10 * MS), ("b", 5 * MS, 20 * MS),
                             ("c", 95 * MS, 130 * MS)],
           "/device:TPU:1": [("x", 0, 30 * MS)], "/device:TPU:2": []}
    window = (2 * MS, 100 * MS)
    r = spans.reduce_spans(window, evs, [], [], {})
    ref = trace.reduce_events(window, evs, [])
    assert (r.window_s, r.n_devices) == (ref.window_s, ref.n_devices)
    assert r.busy_s == pytest.approx(ref.busy_s)


def test_module_seconds_sum_per_program_clipped_to_the_window():
    mods = {"/device:TPU:0": [("jit_engine_decode(12)", 0, 30 * MS),
                              ("jit_engine_decode(12)", 40 * MS, 70 * MS),
                              ("jit_engine_maintain_apply.3", 70 * MS,
                               80 * MS),
                              ("jit__lambda(9)", 95 * MS, 120 * MS)],
            "/device:TPU:1": [("jit_engine_decode(40)", 10 * MS, 20 * MS)]}
    r = spans.reduce_spans((10 * MS, 100 * MS), {}, [], [], mods)
    assert r.module_seconds == pytest.approx({
        "jit_engine_decode": 0.02 + 0.03 + 0.01,
        "jit_engine_maintain_apply": 0.01, "jit__lambda": 0.005})


@pytest.mark.parametrize("name, program", [
    ("jit_engine_decode(12)", "jit_engine_decode"),
    ("jit_engine_maintain_apply.3", "jit_engine_maintain_apply"),
    ("jit_engine_decode(12).1", "jit_engine_decode"),
    ("jit_engine_chunk_fwd", "jit_engine_chunk_fwd"),
])
def test_module_name_drops_the_trailing_id_or_count(name, program):
    assert spans.module_name(name) == program


def test_a_recorded_trace_reads_program_spans_and_their_stats(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            with jax.profiler.StepTraceAnnotation("engine.step", step_num=7):
                with jax.profiler.TraceAnnotation("engine.sync", step=7,
                                                  rid=3):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("sched.admit", rid=4):
                    pass
    finally:
        jax.profiler.stop_trace()
    r = spans.read_spans(trace.find_xplane(str(tmp_path)))
    got = {n: (s, e, st) for n, s, e, st in r.program_spans}
    assert set(got) == {"engine.step", "engine.sync", "sched.admit"}
    assert got["engine.sync"][2] == {"step": 7, "rid": 3}
    assert got["engine.step"][2]["step_num"] == 7
    assert got["engine.step"][0] <= got["engine.sync"][0]
    assert got["engine.sync"][1] <= got["engine.step"][1]
    assert r.idle_by_owner == {}     # the CPU writes no TPU device plane


def test_loop_idle_leaves_the_arrival_wait_out_and_splits_by_owner():
    ops = [("op", 0, 12 * MS), ("op", 50 * MS, 60 * MS)]
    prog = [_span("engine.step", 0, 60), _span("engine.refill", 10, 50),
            _span("engine.sync", 52, 58)]
    r = _reduce((0, 100), ops, prog, [("gate.wait", 20 * MS, 40 * MS)])
    got = spans.report(r, steps=4)
    # idle: [12,20) [40,50) refill, [20,40) wait, [60,100) uncovered
    assert got["loop_idle_ms_per_step"] == pytest.approx((18 + 40) / 4)
    assert got["arrival_wait_share"] == pytest.approx(20.0)
    assert got["loop_idle_share"] == pytest.approx(58.0)
    assert got["uncovered_ms_per_step"] == pytest.approx(10.0)
    assert got["by_span"] == pytest.approx({"engine.refill": 4.5,
                                            spans.UNCOVERED: 10.0})
    assert got["spans_per_step"] == pytest.approx(3 / 4)
    assert got["long_gaps"] == [(spans.UNCOVERED, pytest.approx(40.0)),
                                ("gate.wait", pytest.approx(38.0))]
    # the wait and the loop make up the whole idle share
    idle = 100.0 * (1 - r.busy_s / r.window_s)
    assert got["arrival_wait_share"] + got["loop_idle_share"] == \
        pytest.approx(idle)


def test_readings_are_silent_without_program_spans_or_named_programs():
    mods = {"/device:TPU:0": [("jit__lambda(1)", 0, 40 * MS),
                              ("jit_maintain(2)", 40 * MS, 80 * MS)]}
    r = _reduce((0, 100), [("op", 0, 80 * MS)], mods=mods)
    assert spans.report(r, steps=10) is None
    assert spans.busy_share(r, spans.MAINTAIN) is None
    assert spans.busy_share(r, spans.PREFILL) is None


def test_module_shares_are_device_seconds_over_busy():
    mods = {"/device:TPU:0": [
        ("jit_engine_decode(1)", 0, 40 * MS),
        ("jit_engine_maintain_plan(2)", 40 * MS, 44 * MS),
        ("jit_engine_maintain_apply(3)", 44 * MS, 48 * MS),
        ("jit_engine_chunk_fwd(4)", 48 * MS, 60 * MS),
        ("jit_engine_write_chunk(5)", 60 * MS, 64 * MS),
        ("jit_argmax(6)", 64 * MS, 80 * MS)]}
    r = _reduce((0, 100), [("op", 0, 80 * MS)],
                [_span("engine.step", 0, 100)], mods=mods)
    got = spans.report(r, steps=10)
    assert got["maintain_busy_share"] == pytest.approx(10.0)
    assert got["prefill_busy_share"] == pytest.approx(20.0)
    assert got["named_share"] == pytest.approx(80.0)
    assert got["modules"][0] == ("jit_engine_decode", pytest.approx(0.04))


def test_a_traced_tiny_run_reads_the_engines_spans(monkeypatch):
    from chipbench.tests.helpers import tiny_cell
    seen = []
    report = spans.report
    monkeypatch.setattr(spans, "report",
                        lambda s, steps: seen.append(s) or report(s, steps))
    read_xplane = trace.read_xplane
    res = spans.run_traced(tiny_cell("tiny-moe", "tiny-closed"), seed=13,
                           seconds=1.0, peak=None, device={})
    assert trace.read_xplane is read_xplane
    assert res["correct"], res["check"]
    names = {n for n, *_ in seen[0].program_spans}
    assert {"engine.step", "engine.bucket", "engine.decode_step",
            "engine.sync", "engine.harvest", "engine.refill"} <= names
    assert res["spans"] is None      # the CPU writes no TPU device plane
