#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration, ``chipbench/configs/<config>.json``, and a traffic mix,
``chipbench/traffic/<traffic>.json``, which also holds the serving
settings; ``chipbench/cells/<cell>.json`` holds the limit of its
correctness check.  The run:

1. draws the weights on the device from the seed and builds the program's
   ``Engine`` with the mix's settings, its scheduler wrapped by the gate
   (``chipbench/gate.py``);
2. warms up every program the traffic can reach with a serial warm-up run,
   then starts the measured run: a closed backlog fills every lane, an open
   loop starts its arrivals ``lead_s`` before the window;
3. measures for ``--seconds``; with ``--trace 1`` the profiler records the
   window, and the cell's per-layer metrics are printed instead of its
   end-to-end ones;
4. reads peak device memory, frees the KV state, and checks a sample of the
   served tokens against the plain reference (``chipbench/correct.py``).

It exits non-zero and prints no result when JAX finds no TPU, fewer chips
than the cell asks for, or a device missing from ``chipbench/peaks.json``.
The last line of standard output is one JSON object; the last lines of
standard error are the numbers compared, each beside its limit.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse            # noqa: E402
import dataclasses         # noqa: E402
import gc                  # noqa: E402
import importlib           # noqa: E402
import json                # noqa: E402
import os                  # noqa: E402
import shutil              # noqa: E402
import sys                 # noqa: E402
import tempfile            # noqa: E402
from pathlib import Path   # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
CACHE_DIR = REPO / ".jax_cache"

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class CompileClock:
    """Programs compiled or read back from the persistent cache, and the
    seconds spent compiling, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == _COMPILE_EVENT:
            self.count += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.count += 1


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    mc: dict          # configuration file
    mix: dict         # traffic mix
    limits: dict      # cells/<name>.json
    metrics: list     # [(name, unit)] this run reports


def load_cell(bench: dict, name: str, trace: bool) -> Cell:
    from chipbench.model import load_json
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(REPO / conf["file"]) as f:
        mc = json.load(f)
    group = bench["per_layer"] if trace else bench["end_to_end"]
    mets = [(m["name"], m["unit"]) for m in group
            if name in m.get("workloads", [name])]
    return Cell(name, int(w["chips"]), mc,
                load_json("traffic", w["traffic"]), load_json("cells", name),
                mets)


# -- the run's requests ------------------------------------------------------

def warmup_lengths(mix: dict, serve: dict) -> list[int]:
    """Prompt lengths whose serial warm-up reaches every program the mix
    can: each padded prefill length of its prompts, and on the tiered
    backend each live-page attention bucket its decode positions span."""
    from repro.serve.engine import padded_len
    lo, hi = mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]
    max_len = serve["max_len"]
    chunked = serve["scheduler"] == "chunked"

    def P(n):          # the scheduler's padded prefill length
        return padded_len(n if chunked else n - 1, max_len)

    by_p: dict = {}
    for n in range(lo, hi + 1):
        by_p[P(n)] = n            # the longest prompt of each class
    out = set(by_p.values())
    if serve["backend"] == "tiered":
        pt = serve["page_tokens"]
        top = min(max_len - 2, hi + mix["output_len"]["hi"])
        b = 1
        while b * pt <= top:
            if b * pt - 1 >= lo:
                out.add(min(b * pt - 1, top))
            b *= 2
        out.add(top)
    return sorted(out)


def make_requests(planned):
    from repro.serve.engine import Request
    out = []
    for p in planned:
        r = Request(rid=p.idx, prompt=p.prompt, max_new=p.max_new)
        r.offset_s = p.offset_s
        out.append(r)
    return out


# -- what the metric readers see ---------------------------------------------

@dataclasses.dataclass
class View:
    mc: dict
    peak: dict | None
    chips: int
    seconds: float
    setup_s: float
    window: object
    requests: list
    due: list
    trace: object = None

    def window_tokens(self):
        """(request, token index, harvest time) for every output token
        harvested in the window."""
        t0, t1 = self.window.t0, self.window.t_end
        for r in self.requests:
            for j, t in enumerate(r.token_times):
                if t0 <= t <= t1:
                    yield r, j, t

    def counter_delta(self, keys) -> float | None:
        a, b = self.window.open_info, self.window.close_info
        if "counters" not in a or "counters" not in b:
            return None
        return float(sum(b["counters"][k] - a["counters"][k] for k in keys))

    def layer_steps(self) -> float:
        steps = self.window.steps1 - self.window.steps0
        return max(steps, 1) * self.mc["num_hidden_layers"]


def read_metrics(cell: Cell, view: View) -> dict:
    out = {}
    for name, unit in cell.metrics:
        got = importlib.import_module(f"chipbench.metrics.{name}").read(view)
        if got is None:
            continue
        got = got if isinstance(got, dict) else {"value": got}
        out[name] = {"value": float(got.pop("value")), "unit": unit, **got}
    return out


def ttft_halves(due, window) -> list:
    """Median time to first token (ms) of the requests due in the window's
    first and second halves: a backlog that grows shows as a rise."""
    import numpy as np
    mid = (window.t0 + window.t_end) / 2
    out = []
    for half in ([r for r in due if r.arrived < mid],
                 [r for r in due if r.arrived >= mid]):
        x = [r.first_token_at - r.arrived for r in half
             if r.first_token_at > 0]
        out.append(float(np.median(x) * 1e3) if x else None)
    return out


# -- one run -----------------------------------------------------------------

def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             peak: dict | None, device: dict,
             control: str | None = None) -> dict:
    """One run of ``cell``.  ``control`` (used by ``chipbench/limits.py``
    and the tests, never by a benchmark run) also reads the control's gaps
    on the same sample, under ``result["control"]``."""
    import jax
    import numpy as np
    from chipbench import correct, traffic
    from chipbench.gate import Gate
    from chipbench.model import arch_config, make_params
    from chipbench.trace import WINDOW_SPAN, find_xplane, read_xplane
    from repro.serve.engine import Engine, EngineConfig
    from repro.serve.sched import make_scheduler

    clock = CompileClock()
    mc, mix, serve = cell.mc, cell.mix, cell.mix["serve"]
    cfg = arch_config(mc)
    params = jax.block_until_ready(make_params(cfg, seed))
    log(f"weights drawn at {time.time() - T_START:.1f}s")
    ec = EngineConfig(
        batch=serve["batch"], max_len=serve["max_len"],
        backend=serve["backend"], page_tokens=serve["page_tokens"],
        fast_data_slots=serve["fast_data_slots"], policy=serve["policy"],
        maintain_every=serve["maintain_every"],
        scheduler=serve["scheduler"], prefill_chunk=serve["prefill_chunk"])
    gate = Gate(make_scheduler(ec))
    eng = Engine(cfg, params, ec, scheduler=gate)

    # warm-up: every program the window can reach, one request at a time
    rng = np.random.default_rng(seed)
    for i, n in enumerate(warmup_lengths(mix, serve)):
        prompt = rng.integers(0, cfg.vocab, size=n).astype(np.int32)
        eng.submit(make_requests([traffic.Planned(
            -1 - i, prompt, serve["maintain_every"] + 2, None)])[0])
    gate.arm("serial")
    eng.run()
    eng.final_state = None        # the measured run starts its own state
    log(f"warm-up done at {time.time() - T_START:.1f}s "
        f"({clock.count} compiles, {clock.seconds:.1f}s)")

    reqs = make_requests(traffic.plan(mix, cfg.vocab, seed, seconds))
    for r in reqs:
        eng.submit(r)
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    span = []
    tiered = ec.backend == "tiered"

    def books(state) -> dict:
        out = {}
        if tiered:
            c = eng.backend.counters(state)
            out["counters"] = {k: int(c[k]) for k in
                               ("lookups", "migrations", "demotions")}
        out["queued"] = gate.inner.pending
        out["compiles"] = clock.count     # after the counters' own compile
        return out

    def on_open(state):
        info = books(state)
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(tdir, profiler_options=opts)
            span.append(jax.profiler.TraceAnnotation(WINDOW_SPAN))
            span[0].__enter__()
        return info

    def on_close(state):
        if trace:
            span.pop().__exit__(None, None, None)
            jax.profiler.stop_trace()
        return books(state)

    arr = mix["arrivals"]
    mode = "closed" if arr["process"] == "closed" else "open"
    window = gate.arm(mode, seconds=seconds, lanes=ec.batch,
                      max_ingests=mix.get("max_ingests", 0),
                      lead_s=arr.get("lead_s", 0.0), on_open=on_open,
                      on_close=on_close)
    eng.run()
    if not window.closed:
        raise RuntimeError("the run ended before its window closed")
    setup_s = window.t0 - T_START
    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device, memory_peak_bytes=int(
        stats.get("peak_bytes_in_use", 0)))

    reduced = None
    if trace:
        reduced = read_xplane(find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
    due = gate.due_in_window
    view = View(mc, peak, cell.chips, float(seconds), setup_s, window, reqs,
                due, reduced)
    metrics = read_metrics(cell, view)
    compiles = window.close_info["compiles"] - window.open_info["compiles"]

    # the check: free the KV state, then the reference over a sample
    eng.final_state = None
    del eng
    gc.collect()
    chk = mix["check"]
    served = [r for r in reqs if r.token_times]
    sample = correct.sample(served, seed, chk["sample_requests"])
    t_chk = time.time()
    gaps = correct.served_gaps(mc, params, sample)
    gap = float(gaps.mean()) if gaps.size else None
    limit = float(cell.limits["mean_logit_gap"]["limit"])
    missing = [r for r in due if r.first_token_at <= 0]
    attempted = len(due) if mode == "open" else len(served)
    ok = gap is not None and gap <= limit and not missing
    log(f"check over {len(sample)} requests, {gaps.size} served tokens, "
        f"{time.time() - t_chk:.1f}s; compiles in window {compiles}")
    result = {
        "correct": ok, "attempted": attempted, "failed": len(missing),
        "metrics": metrics, "device": device,
        "window": {"compiles": compiles, "steps":
                   window.steps1 - window.steps0,
                   "requests_due": len(due),
                   "queued": [window.open_info["queued"],
                              window.close_info["queued"]],
                   "ttft_halves_ms": ttft_halves(due, window)},
    }
    if reduced is not None:
        result["breakdown"] = {
            "device_ops": sorted(reduced.op_seconds.items(),
                                 key=lambda kv: -kv[1])[:10],
            "idle_gaps": reduced.gaps[:10]}
    if control is not None:
        cgaps = correct.served_gaps(mc, params, sample, control=control)
        result["control"] = dict(correct.gap_stats(cgaps),
                                 program=correct.gap_stats(gaps))
    result["check"] = {
        "mean_logit_gap": {"value": gap, "limit": limit},
        "requests_without_first_token": {"value": len(missing), "limit": 0},
    }
    return result


def open_chip(cell: Cell):
    """Set the compile cache and look for the cell's chips: (peak, device)
    or None when they are not there (the reason is logged)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        log(f"needs {cell.chips} TPU chip(s); JAX has {len(devs)} "
            f"{devs[0].platform} device(s) ({devs[0].device_kind})")
        return None
    with open(HERE / "peaks.json") as f:
        peaks = json.load(f)["devices"]
    kind = devs[0].device_kind
    if kind not in peaks:
        log(f"no peaks for device kind {kind!r} in chipbench/peaks.json")
        return None
    return peaks[kind], {"platform": devs[0].platform, "kind": kind,
                         "count": len(devs)}


def load(workload: str, trace: bool) -> Cell | None:
    """The cell, with the program importable; None when the program is not
    in this checkout."""
    if not (REPO / "src" / "repro").is_dir():
        log(f"the program is not here: no {REPO / 'src' / 'repro'}")
        return None
    sys.path[:0] = [str(REPO / "src"), str(REPO)]
    with open(REPO / "BENCHMARK.json") as f:
        bench = json.load(f)
    return load_cell(bench, workload, trace)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load(args.workload, bool(args.trace))
    chip = cell and open_chip(cell)
    if not chip:
        return 2
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), peak=chip[0], device=chip[1])
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
