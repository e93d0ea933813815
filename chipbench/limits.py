#!/usr/bin/env python3
"""Readings for a cell's correctness limit, on the chip.

    python3 chipbench/limits.py --workload <cell> --seconds <s> \\
        --seeds <n>,<n>,...

Runs the cell once per seed in one process, as ``run.py`` does, and for
each prints one JSON line: the widest logit gap of the tokens the program
served (the lower reading) and, on the same sample, that of the tokens the
float8 control would put first (the upper reading), with other summaries
of the same per-token gaps.  ``PERF.md`` records
the readings and the limit set between them in
``chipbench/cells/<cell>.json``.  Benchmark runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = run.load(args.workload, trace=False)
    chip = cell and run.open_chip(cell)
    if not chip:
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, seed=seed, seconds=args.seconds,
                           trace=False, peak=chip[0], device=chip[1],
                           control="fp8")
        ctl = res["control"]
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "program": ctl.pop("program"), "control": ctl,
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "memory_peak_bytes": res["device"]["memory_peak_bytes"]}),
            flush=True)
        del res
        gc.collect()       # the run's engine and weights, before the next
    return 0


if __name__ == "__main__":
    sys.exit(main())
