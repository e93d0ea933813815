"""GreedyScheduler: PR 4's wave-refill engine behaviour, bit for bit.

One-shot prefill at admission, straggler bucketing anchored to the first
request of a batch wave (reset when the engine drains), single tenant,
FIFO with a length-class preference.  This is the default scheduler; the
engine parity tests (tests/test_engine.py) pin its token streams
unmodified.
"""

from __future__ import annotations

import time
from collections import deque

import numpy as np


class GreedyScheduler:
    kind = "greedy"

    def __init__(self, ec):
        self.ec = ec
        self.queue: deque = deque()
        self.active_bucket: int | None = None
        self.eng = None

    def bind(self, engine) -> None:
        self.eng = engine

    def submit(self, req) -> None:
        self.queue.append(req)

    @property
    def pending(self) -> int:
        return len(self.queue)

    def is_decoding(self, lane: int) -> bool:
        return True                      # one-shot prefill: a filled lane
                                         # decodes from its first step

    def _pick(self, bucket_len: int | None):
        """Prefer a request whose target length lands in the active bucket
        (straggler mitigation: uniform-ish finish times per batch)."""
        if not self.queue:
            return None
        if bucket_len is None:
            return self.queue.popleft()
        for i, r in enumerate(self.queue):
            if abs(r.max_new - bucket_len) <= self.ec.bucket:
                del self.queue[i]
                return r
        return self.queue.popleft()

    def refill(self, state, tokens, lanes, finished):
        """Recycle finished lanes (release their pages), fill empty lanes
        from the queue (real one-shot prefill), park still-empty lanes at
        pos = -1 so they neither write nor read nor heat anything."""
        eng, ec = self.eng, self.ec
        span = eng.tracer.span
        for i in range(ec.batch):
            r = lanes[i]
            if r is not None and r.done:
                with span("release", cat="sched", rid=r.rid, lane=i):
                    finished.append(r)
                    lanes[i] = None
                    state = eng.release_lane(state, i)
            if lanes[i] is None:
                req = self._pick(self.active_bucket)
                if req is None:
                    continue
                if self.active_bucket is None:
                    self.active_bucket = req.max_new
                lanes[i] = req
                with span("admit", cat="sched", rid=req.rid, lane=i):
                    req.admitted_at = time.time()
                    state, tok = eng.prefill_lane(state, i, req)
                    tokens = tokens.at[i].set(tok)
        with span("park", cat="sched"):
            idle = np.array([l is None for l in lanes])
            if idle.any():
                state = eng.park_idle(state, idle)
        if idle.all() and not self.queue:
            self.active_bucket = None       # the wave drained: re-anchor
        return state, tokens

    def maintain(self, state):
        return self.eng._maintain(state)
