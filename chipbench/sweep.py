#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop cell sustains, on the chip.

    python3 chipbench/sweep.py --workload <cell> --seconds <s> --seed <n> \
        --rates <r>,<r>,...

Runs the cell once per rate in one process, with the mix's ``rate_per_s``
replaced, and prints one JSON line per rate: the end-to-end metrics, the
requests queued for a lane at the window's open and close, and the median
time to first token of the requests due in the window's first and second
halves.  A rate is sustained when the queue at the close is no longer than
at the open and the second half waits no longer than the first.  The
cell's mix keeps a fixed rate below the highest sustained one; benchmark
runs never sweep.
"""

from __future__ import annotations

import argparse
import copy
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = run.load(args.workload, trace=False)
    chip = cell and run.open_chip(cell)
    if not chip:
        return 2
    for rate in (float(r) for r in args.rates.split(",")):
        c = copy.deepcopy(cell)
        c.mix["arrivals"]["rate_per_s"] = rate
        c.metrics = [("tokens_per_s", "tokens/s"), ("itl_p95_ms", "ms"),
                     ("ttft_p75_ms", "ms"), ("queue_wait_p75_ms", "ms")]
        res = run.run_cell(c, seed=args.seed, seconds=args.seconds,
                           trace=False, peak=chip[0], device=chip[1])
        print(json.dumps({
            "rate_per_s": rate, "correct": res["correct"],
            "due": res["attempted"], "failed": res["failed"],
            "queued_open_close": res["window"]["queued"],
            "ttft_median_halves_ms": res["window"]["ttft_halves_ms"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
            flush=True)
        del res
        gc.collect()       # the run's engine and weights, before the next
    return 0


if __name__ == "__main__":
    sys.exit(main())
