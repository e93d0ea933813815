"""The reduction from a profiler trace to busy time, operation times and
idle gaps, on synthetic events and on a trace recorded here."""

import pytest

from chipbench import trace

MS = 1_000_000


def test_busy_is_the_union_of_overlapping_ops_clipped_to_the_window():
    evs = {"/device:TPU:0": [("a", 0, 10 * MS), ("b", 5 * MS, 20 * MS),
                             ("a", 30 * MS, 40 * MS),
                             ("c", 95 * MS, 130 * MS)]}
    r = trace.reduce_events((2 * MS, 100 * MS), evs, [])
    assert r.window_s == pytest.approx(0.098)
    # [2,20) + [30,40) + [95,100)
    assert r.busy_s == pytest.approx(0.033)
    assert r.op_seconds["a"] == pytest.approx(0.018)
    assert r.op_seconds["b"] == pytest.approx(0.015)
    assert r.op_seconds["c"] == pytest.approx(0.005)
    assert r.n_devices == 1


def test_idle_gaps_take_the_host_span_that_overlaps_them_most():
    evs = {"/device:TPU:0": [("step", 0, 10 * MS), ("step", 30 * MS,
                                                    40 * MS)]}
    spans = [("gate.refill", 9 * MS, 14 * MS),
             ("gate.wait", 14 * MS, 29 * MS)]
    r = trace.reduce_events((0, 50 * MS), evs, spans)
    assert [(n, round(s, 6)) for n, s in r.gaps] == [
        ("gate.wait", 0.02), (trace.ENGINE_LOOP, 0.01)]
    assert r.busy_s + sum(s for _, s in r.gaps) == pytest.approx(r.window_s)


def test_busy_time_averages_over_devices_that_ran():
    evs = {"/device:TPU:0": [("x", 0, 10 * MS)],
           "/device:TPU:1": [("x", 0, 30 * MS)],
           "/device:TPU:2": []}
    r = trace.reduce_events((0, 40 * MS), evs, [])
    assert r.n_devices == 2
    assert r.busy_s == pytest.approx(0.02)
    assert r.op_seconds["x"] == pytest.approx(0.04)


def test_merge_and_gaps_edges():
    assert trace.merge([(5, 6), (0, 2), (1, 3), (3, 4), (7, 7)]) == [
        [0, 4], [5, 6]]
    assert trace.idle_gaps([[0, 4], [5, 6]], 0, 10) == [(4, 5), (6, 10)]
    assert trace.idle_gaps([], 0, 10) == [(0, 10)]


def test_a_recorded_trace_reads_its_window_and_host_spans(tmp_path):
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
            with jax.profiler.TraceAnnotation("gate.refill"):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    r = trace.read_xplane(trace.find_xplane(str(tmp_path)))
    assert r.window_s > 0
    assert r.n_devices == 0          # the CPU writes no TPU device plane
    assert r.busy_s == 0.0


def test_op_names_are_the_hlo_instruction_names():
    assert trace.op_name(
        "%paged_attention_fused_op.6 = bf16[4,8,3,64]{3,2,1,0} custom-call("
        "s32[4,256] %a, s32[4] %b)") == "paged_attention_fused_op.6"
    assert trace.op_name("fusion.3") == "fusion.3"


def test_a_gap_inside_nested_spans_takes_the_innermost():
    spans = [("gate.refill", 0, 100 * MS), ("gate.wait", 10 * MS, 90 * MS)]
    assert trace.label_gap((20 * MS, 80 * MS), spans) == "gate.wait"
    assert trace.label_gap((0, 5 * MS), spans) == "gate.refill"
