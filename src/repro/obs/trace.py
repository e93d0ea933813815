"""Structured step tracing: one set of span sites for the serving loop's
phases, with two sinks (DESIGN.md §10).

- The profiler.  Every span enters ``jax.profiler.TraceAnnotation(
  "<cat>.<name>", **args)`` (``engine.*`` for the engine loop, ``sched.*``
  for the scheduler), and each loop iteration is a
  ``StepTraceAnnotation("engine.step", step_num=...)``.  These cost about a
  microsecond when no profiler session is active and land on the
  profiler's host plane, on the same clock as the device's operations,
  whenever one is.  No ``ObsConfig`` is needed and donation is untouched.
- Chrome-trace-event JSON (Perfetto-loadable), written by ``StepTracer``
  when ``ObsConfig.trace_path`` is set.

Span semantics: a span times the host side of one phase: the dispatch of
its device programs plus whatever host work the phase does.  Dispatch
returns before the device finishes, so a span waits on the device only
where the phase reads from it: ``sync`` (the loop's two host reads of
tokens and positions), ``bucket`` (its read of positions), the maintenance
apply's counter snapshot, and the final prefill chunk's first-token read.
Span arguments are host ints already at hand (step, lane, rid, tokens,
pages); a span never reads a device value.

Event schema (Trace Event Format, the subset Perfetto ingests):
  {"ph": "X", "name": ..., "cat": ..., "pid": 1, "tid": ...,
   "ts": <µs since tracer start>, "dur": <µs>, "args": {...}}     spans
  {"ph": "C", "name": ..., "ts": ..., "args": {metric: value}}  counters
  {"ph": "M", ...}                                    process/thread names

Open a saved trace at https://ui.perfetto.dev ("Open trace file") or
chrome://tracing — the file is a standard ``{"traceEvents": [...]}``
JSON object.
"""

from __future__ import annotations

import contextlib
import json
import time

from jax.profiler import StepTraceAnnotation, TraceAnnotation

STEP_SPAN = "engine.step"


class StepTracer:
    """Collects trace events in memory; ``save`` writes the JSON."""

    #: lanes (Perfetto "threads") the engine phases render on — spans on
    #: separate tids stack visually instead of overlapping
    TIDS = {"decode_step": 0, "prefill": 1, "prefill_chunk": 1,
            "admit_fast": 1, "maintain": 2, "release": 3}
    #: the scheduler's spans (``cat="sched"``) render on a lane of their
    #: own, so its ``release`` does not stack on the engine's
    SCHED_TID = 4

    def __init__(self, process_name: str = "repro.serve.engine"):
        self._t0 = time.perf_counter()
        self.events: list[dict] = [
            {"ph": "M", "name": "process_name", "pid": 1, "tid": 0,
             "args": {"name": process_name}},
        ]
        for name, tid in (("decode", 0), ("prefill", 1),
                          ("maintain", 2), ("release", 3),
                          ("scheduler", self.SCHED_TID)):
            self.events.append(
                {"ph": "M", "name": "thread_name", "pid": 1, "tid": tid,
                 "args": {"name": name}})
        self._n_meta = len(self.events)

    def clear(self) -> None:
        """Reset to an empty trace (fresh t0, metadata events kept): the
        engine clears at the top of each ``run`` so the saved file covers
        exactly that run instead of growing across runs."""
        self._t0 = time.perf_counter()
        del self.events[self._n_meta:]

    def now_us(self) -> float:
        """µs since tracer start — the timebase of every event ``ts``
        (callers stash it to emit deferred events at the right spot)."""
        return (time.perf_counter() - self._t0) * 1e6

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "engine", tid: int | None = None,
             **args):
        """Complete-event span around one phase, also entered as the
        profiler annotation ``<cat>.<name>``; ``args`` annotate both."""
        if tid is None:
            tid = self.SCHED_TID if cat == "sched" \
                else self.TIDS.get(name, 0)
        ts = self.now_us()
        try:
            with TraceAnnotation(f"{cat}.{name}", **args):
                yield
        finally:
            self.events.append({
                "ph": "X", "name": name, "cat": cat, "pid": 1,
                "tid": tid,
                "ts": ts, "dur": self.now_us() - ts,
                "args": args,
            })

    @contextlib.contextmanager
    def step(self, step: int):
        """One loop iteration: the profiler's step annotation and a
        ``step`` span, the parent of every phase span in it."""
        ts = self.now_us()
        try:
            with StepTraceAnnotation(STEP_SPAN, step_num=step):
                yield
        finally:
            self.events.append({
                "ph": "X", "name": "step", "cat": "engine", "pid": 1,
                "tid": 0, "ts": ts, "dur": self.now_us() - ts,
                "args": {"step": step}})

    def counter(self, name: str, values: dict,
                ts: float | None = None) -> None:
        """Counter track (Perfetto renders a stacked area chart).  ``ts``
        lets deferred emitters stamp the time the value was observed."""
        self.events.append({"ph": "C", "name": name, "pid": 1, "tid": 0,
                            "ts": self.now_us() if ts is None else ts,
                            "args": {k: float(v) for k, v in values.items()}})

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump({"traceEvents": self.events,
                       "displayTimeUnit": "ms"}, f)
        return path


class NullTracer:
    """The tracer without a JSON sink: spans and steps still reach the
    profiler (about a microsecond each when no session is active), so the
    engine's hot loop stays branch-free."""

    def span(self, name, cat="engine", tid=None, **args):
        return TraceAnnotation(f"{cat}.{name}", **args)

    def step(self, step):
        return StepTraceAnnotation(STEP_SPAN, step_num=step)

    def clear(self):
        pass

    def now_us(self):
        return 0.0

    def counter(self, *a, **k):
        pass

    def save(self, path):
        raise RuntimeError("tracing is disabled (NullTracer)")


NULL_TRACER = NullTracer()


@contextlib.contextmanager
def profiler_trace(log_dir: str | None):
    """Optionally wrap a block in a ``jax.profiler`` trace: when
    ``log_dir`` is set, device-side activity (including the Pallas
    kernels) lands in a TensorBoard/Perfetto-compatible trace under it,
    beside the engine's spans; ``None`` is a no-op."""
    if not log_dir:
        yield
        return
    import jax
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
