#!/usr/bin/env python3
"""Who owns the device's idle time, and which programs fill its busy time,
in one traced run of a cell.

    python3 chipbench/spans.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``run.py --trace 1`` does and prints the same result line
with one more key, ``spans``, read from the same profiler trace of the
window (it reads the trace where ``run.py`` reads it, before the run
deletes it):

- each idle instant of the window goes to the innermost span that covers
  it: a program span (``engine.*``, ``sched.*``; ``engine.step`` owns what
  none of its phases covers), the gate's ``gate.wait``, or none
  (``uncovered``).  ``gate.wait`` is the sleep until the next arrival, the
  traffic's time and not the loop's: ``loop_idle_ms_per_step`` leaves it
  out, per decode step of the window, and ``arrival_wait_share`` gives it
  as % of the window; ``by_span`` splits the loop's ms per step by owner
  and ``long_gaps`` names the owner of most of each of the longest gaps;
- device seconds per program come from each device plane's ``XLA Modules``
  line, under the module's name without its trailing ``(<id>)`` or
  ``.<n>``: ``maintain_busy_share`` (``jit_engine_maintain*``),
  ``prefill_busy_share`` (the chunk forward and write, the admission and
  the one-shot prefill) and ``named_share`` (every ``jit_engine_*``) are %
  of busy time, and ``modules`` the ten programs that ran longest.

Benchmark runs never run it: ``BENCHMARK.json``'s metrics read only what
``chipbench/trace.py`` keeps.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import run, trace  # noqa: E402

PROGRAM_SPAN_PREFIXES = ("engine.", "sched.")
ARRIVAL_WAIT = "gate.wait"
UNCOVERED = "uncovered"
MODULES_LINE = "XLA Modules"
LONG_GAPS = 10
ENGINE = "jit_engine_"
MAINTAIN = ("jit_engine_maintain",)
PREFILL = ("jit_engine_chunk_fwd", "jit_engine_write_chunk",
           "jit_engine_admit_fast", "jit_engine_prefill")
_MODULE_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)+$")


@dataclasses.dataclass
class Spans:
    window_s: float
    busy_s: float                 # averaged over devices
    n_devices: int
    program_spans: list           # [(name, start_ns, end_ns, stats)]
    idle_by_owner: dict           # owner -> idle seconds, averaged
    gap_owners: list              # [(owner of most of it, seconds)]
    module_seconds: dict          # program -> seconds, summed over devices


def module_name(name: str) -> str:
    """A program's name without the id or count the trace appends:
    ``jit_engine_decode(12)`` and ``jit_engine_decode.3`` are both
    ``jit_engine_decode``."""
    return _MODULE_SUFFIX.sub("", name)


def idle_owners(idle: list, spans: list) -> dict:
    """Nanoseconds of the sorted, disjoint stretches ``idle`` [(s, e)] owned
    by each span of ``spans`` [(name, start, end)]: an instant belongs to
    the innermost span that covers it (the one that started last, the
    shorter of two that started together), or to ``UNCOVERED``."""
    if not idle:
        return {}
    marks = sorted([(e, 0, i) for i, (_, _, e) in enumerate(spans)]
                   + [(s, 1, i) for i, (_, s, _) in enumerate(spans)])
    marks.append((idle[-1][1], 2, -1))
    out: dict = {}
    active: dict = {}
    j, t = 0, idle[0][0]
    for m, kind, i in marks:
        if m > t:
            while j < len(idle) and idle[j][1] <= t:
                j += 1
            owned, k = 0, j
            while k < len(idle) and idle[k][0] < m:
                owned += min(m, idle[k][1]) - max(t, idle[k][0])
                k += 1
            if owned > 0:
                inner = max(active.values(), key=lambda sp: (sp[1], -sp[2]),
                            default=None)
                key = UNCOVERED if inner is None else inner[0]
                out[key] = out.get(key, 0) + owned
            t = m
        if kind == 1:
            active[i] = spans[i]
        elif kind == 0:
            active.pop(i, None)
    return out


def reduce_spans(window, device_events: dict, program_spans: list,
                 waits: list, device_modules: dict) -> Spans:
    """``window`` (start, end) and every time in ns; ``device_events`` and
    ``device_modules`` map a device to the [(name, start, end)] of its
    operations and of its programs; ``program_spans`` is [(name, start,
    end, stats)] and ``waits`` the gate's [(name, start, end)] waits."""
    lo, hi = window
    owner_spans = [(n, s, e) for n, s, e, _ in program_spans] + list(waits)
    owners: dict = {}
    gap_owners: list = []
    busy_total, n_dev = 0.0, 0
    for evs in device_events.values():
        busy = trace.merge((max(s, lo), min(e, hi)) for _, s, e in evs)
        if not busy:
            continue
        n_dev += 1
        busy_total += sum(e - s for s, e in busy) * 1e-9
        idle = trace.idle_gaps(busy, lo, hi)
        for k, ns in idle_owners(idle, owner_spans).items():
            owners[k] = owners.get(k, 0.0) + ns * 1e-9
        for g in sorted(idle, key=lambda g: g[0] - g[1])[:LONG_GAPS]:
            own = idle_owners([g], owner_spans)
            gap_owners.append((max(own, key=own.get), (g[1] - g[0]) * 1e-9))
    gap_owners.sort(key=lambda g: -g[1])
    modules: dict = {}
    for evs in device_modules.values():
        for n, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                n = module_name(n)
                modules[n] = modules.get(n, 0.0) + d * 1e-9
    return Spans(window_s=(hi - lo) * 1e-9,
                 busy_s=busy_total / max(n_dev, 1), n_devices=n_dev,
                 program_spans=list(program_spans),
                 idle_by_owner={k: v / max(n_dev, 1)
                                for k, v in owners.items()},
                 gap_owners=gap_owners[:LONG_GAPS], module_seconds=modules)


def read_spans(path: str) -> Spans:
    """Load a profiler trace and reduce its window."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window, device_events, device_modules = None, {}, {}
    program_spans, waits = [], []
    for plane in pd.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name in (trace.OPS_LINE, MODULES_LINE):
                    evs = device_events if line.name == trace.OPS_LINE \
                        else device_modules
                    evs[plane.name] = [(e.name, e.start_ns,
                                        e.start_ns + e.duration_ns)
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    if e.name == trace.WINDOW_SPAN:
                        window = (e.start_ns, end)
                    elif e.name == ARRIVAL_WAIT:
                        waits.append((e.name, e.start_ns, end))
                    elif e.name.startswith(PROGRAM_SPAN_PREFIXES):
                        program_spans.append((e.name, e.start_ns, end,
                                              dict(e.stats)))
    if window is None:
        raise RuntimeError(f"no {trace.WINDOW_SPAN!r} span in {path}")
    return reduce_spans(window, device_events, program_spans, waits,
                        device_modules)


def busy_share(s: Spans, prefixes) -> float | None:
    """% of busy time in programs whose names start with ``prefixes``, or
    None where the trace names no program of the engine."""
    mods = s.module_seconds
    if s.busy_s <= 0 or not any(n.startswith(ENGINE) for n in mods):
        return None
    secs = sum(v for n, v in mods.items() if n.startswith(prefixes))
    return 100.0 * secs / max(s.n_devices, 1) / s.busy_s


def report(s: Spans, steps: int) -> dict | None:
    """The readings of ``s`` over ``steps`` decode steps of the window, or
    None where the program put no span in the trace or no device ran."""
    if not s.program_spans or not s.n_devices or s.window_s <= 0:
        return None
    owned = dict(s.idle_by_owner)
    wait = owned.pop(ARRIVAL_WAIT, 0.0)
    loop = sum(owned.values())
    per_step = 1e3 / max(steps, 1)
    return {
        "loop_idle_ms_per_step": loop * per_step,
        "arrival_wait_share": 100.0 * wait / s.window_s,
        "loop_idle_share": 100.0 * loop / s.window_s,
        "uncovered_ms_per_step": owned.get(UNCOVERED, 0.0) * per_step,
        "by_span": {k: v * per_step for k, v in
                    sorted(owned.items(), key=lambda kv: -kv[1])},
        "spans_per_step": len(s.program_spans) / max(steps, 1),
        "long_gaps": [(k, 1e3 * v) for k, v in s.gap_owners],
        "maintain_busy_share": busy_share(s, MAINTAIN),
        "prefill_busy_share": busy_share(s, PREFILL),
        "named_share": busy_share(s, (ENGINE,)),
        "modules": sorted(s.module_seconds.items(),
                          key=lambda kv: -kv[1])[:10]}


def run_traced(cell: run.Cell, **kw) -> dict:
    """``run.run_cell(cell, trace=True, **kw)``, its result with
    ``spans``."""
    read_xplane, got = trace.read_xplane, []

    def read_both(path):
        got.append(read_spans(path))
        return read_xplane(path)

    trace.read_xplane = read_both
    try:
        result = run.run_cell(cell, trace=True, **kw)
    finally:
        trace.read_xplane = read_xplane
    result["spans"] = report(got[0], result["window"]["steps"])
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.load(args.workload, trace=True)
    chip = cell and run.open_chip(cell)
    if not chip:
        return 2
    result = run_traced(cell, seed=args.seed, seconds=args.seconds,
                        peak=chip[0], device=chip[1])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
