"""The one traffic generator: a mix file's parameters + a seed -> requests.

A mix (``chipbench/traffic/<name>.json``) fixes the distributions of prompt
and output lengths and the arrival process.  From them and the mix's own
``catalog_seed`` it draws a catalog of lengths and inter-arrival gaps that
is the same for every run; the run's seed only orders that catalog and
draws the token ids.  So every seed serves the same amount of work with the
same burst sizes, in another order, and a seed cannot make a run lighter.

Arrival processes:

- ``closed``: a backlog that keeps ``clients`` requests in the system; a
  request is due the moment a finished one leaves (offline batch serving).
- ``gamma``: an open loop of independent users; gaps between arrivals are
  gamma-distributed with mean ``1 / rate_per_s`` and coefficient of
  variation ``cv`` (``cv`` 1 is Poisson, larger is burstier).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as the traffic plans it."""
    idx: int
    prompt: np.ndarray        # [S] int32 token ids
    max_new: int
    offset_s: float | None    # open loop: due time after the first arrival


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if spec["dist"] == "uniform":
        x = rng.integers(lo, hi + 1, size=n)
    elif spec["dist"] == "lognormal":
        x = np.rint(rng.lognormal(math.log(spec["median"]), spec["sigma"],
                                  size=n))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def _gaps(arr: dict, n: int, rng) -> np.ndarray:
    if arr["process"] != "gamma":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    shape = 1.0 / arr["cv"] ** 2
    return rng.gamma(shape, 1.0 / (arr["rate_per_s"] * shape), size=n)


def catalog_size(mix: dict, seconds: float) -> int:
    """Requests a run can reach: an open loop's arrivals over its lead and
    window with a margin, or a closed backlog's fixed catalog."""
    arr = mix["arrivals"]
    if arr["process"] == "closed":
        return int(mix["catalog_requests"])
    span = float(arr.get("lead_s", 0.0)) + seconds
    return int(math.ceil(arr["rate_per_s"] * span * 1.5)) + 16


def plan(mix: dict, vocab: int, seed: int, seconds: float) -> list[Planned]:
    """The run's requests, in the order the traffic offers them."""
    n = catalog_size(mix, seconds)
    cat = np.random.default_rng(int(mix["catalog_seed"]))
    prompts = _lengths(mix["prompt_len"], n, cat)
    outputs = _lengths(mix["output_len"], n, cat)
    closed = mix["arrivals"]["process"] == "closed"
    gaps = None if closed else _gaps(mix["arrivals"], n, cat)
    run = np.random.default_rng(seed)
    order = run.permutation(n)
    prompts, outputs = prompts[order], outputs[order]
    if gaps is not None:
        # the first request arrives at 0; the catalog's gaps follow it
        offsets = np.concatenate([[0.0], np.cumsum(gaps[run.permutation(n)])
                                  [:-1]])
    out = []
    for i in range(n):
        ids = run.integers(0, vocab, size=int(prompts[i])).astype(np.int32)
        out.append(Planned(i, ids, int(outputs[i]),
                           None if closed else float(offsets[i])))
    return out
