"""The engine loop's spans on the profiler clock (DESIGN.md §10): with no
``ObsConfig`` every phase of ``Engine.run`` reaches an active
``jax.profiler`` session as an ``engine.*`` / ``sched.*`` annotation, the
phases cover each ``engine.step``, every program the loop dispatches is
named, and neither donation nor the tokens change."""

import glob

import jax
import numpy as np
import pytest

# the top-level phases of one loop iteration; every statement of an
# ``Engine.run`` iteration runs inside exactly one of them
PHASES = ("engine.maintain_apply", "engine.bucket", "engine.decode_step",
          "engine.maintain", "engine.sync", "engine.harvest",
          "engine.refill")
NESTED = ("sched.release", "sched.advance", "sched.admit", "sched.park",
          "engine.release", "engine.prefill_chunk")


def _submit(eng, vocab):
    from repro.serve.engine import Request
    rng = np.random.default_rng(3)
    for rid in range(4):
        eng.submit(Request(rid=rid, prompt=rng.integers(0, vocab, 20),
                           max_new=6))


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One engine, two runs of the same requests: the first with no
    profiler (it also compiles every program), the second, warm, under
    ``jax.profiler``.  Returns the engine, both runs' tokens and the host
    events of the traced run as [(name, start_ns, end_ns, stats)]."""
    from jax.profiler import ProfileData
    from repro.configs import get_config, reduce_for_smoke
    from repro.models import init_params
    from repro.serve.engine import Engine, EngineConfig
    cfg = reduce_for_smoke(get_config("llama3-8b"))
    eng = Engine(cfg, init_params(cfg, jax.random.key(0)), EngineConfig(
        batch=2, max_len=64, backend="tiered", page_tokens=8,
        fast_data_slots=4, maintain_every=2, scheduler="chunked",
        prefill_chunk=8))
    _submit(eng, cfg.vocab)
    off = {r.rid: r.tokens for r in eng.run()}
    _submit(eng, cfg.vocab)
    tdir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        on = {r.rid: r.tokens for r in eng.run()}
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    return eng, off, on, events


def _named(events, name):
    return [e for e in events if e[0] == name]


def test_every_phase_span_reaches_the_profiler_without_obs(runs):
    eng, _, _, events = runs
    assert eng.ec.obs is None
    names = {e[0] for e in events}
    missing = set(("engine.step",) + PHASES + NESTED) - names
    assert not missing
    steps = _named(events, "engine.step")
    assert len(steps) == eng.steps // 2      # the second of two like runs
    assert all(isinstance(e[3]["step_num"], int) for e in steps)
    for name in ("sched.release", "sched.advance", "sched.admit"):
        assert all(isinstance(e[3]["rid"], int) for e in _named(events, name))


def _covered(intervals) -> int:
    """Length of the union of [(start, end)] (a phase may nest in another,
    as ``maintain_apply`` does in ``refill`` when a lane is released)."""
    total, reach = 0, None
    for s, e in sorted(intervals):
        if reach is None or s > reach:
            total, reach = total + e - s, e
        elif e > reach:
            total, reach = total + e - reach, e
    return total


def test_phase_spans_cover_each_step(runs):
    _, _, _, events = runs
    phases = [e for e in events if e[0] in PHASES]
    for _, s, e, _ in _named(events, "engine.step"):
        covered = _covered((max(ps, s), min(pe, e)) for _, ps, pe, _ in phases
                           if ps < e and pe > s)
        assert covered >= 0.9 * (e - s), (covered, e - s)


def test_every_program_the_loop_dispatches_is_named(runs):
    _, _, _, events = runs
    steps = _named(events, "engine.step")
    progs = {name[len("PjitFunction("):-1] for name, s, e, _ in events
             if name.startswith("PjitFunction(")
             and any(ss <= s and e <= se for _, ss, se, _ in steps)}
    assert {"engine_decode", "engine_maintain_plan", "engine_maintain_apply",
            "engine_chunk_fwd", "engine_write_chunk", "engine_set_pos",
            "engine_park_idle", "engine_release"} <= progs
    assert not [p for p in progs if "lambda" in p]


def test_spans_keep_donation_and_tokens(runs):
    eng, off, on, _ = runs
    assert eng._donate
    assert len(off) == 4 and all(len(t) == 6 for t in off.values())
    assert on == off
