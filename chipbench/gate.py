"""The gate: the benchmark's one seam into the engine.

``Gate`` implements the program's documented ``Scheduler`` protocol
(``repro/serve/sched/base.py``) and delegates every call to the engine's
own scheduler.  It only decides when a request reaches that scheduler:

- ``serial`` (warm-up): one request at a time; the run ends when all are
  served.
- ``closed``: a backlog that keeps every lane busy.  A request is released
  as soon as a lane frees up, with at most ``max_ingests`` prompts being
  ingested at once.  Set-up ends and the window starts once every lane is
  decoding; the run ends at the window's close.
- ``open``: requests are released at their due times and ``arrived`` is
  stamped with the due time, so a wait for a lane counts.  The arrival clock
  starts at the first refill; the window opens ``lead_s`` later.  After the
  close the run goes on until every request due in the window has its first
  token, or a minute has passed.

When no lane is busy and nothing is due it sleeps until the next arrival.
Around each delegated call it records a host span in the profiler's trace
(``gate.refill``, ``gate.maintain``, ``gate.wait``), so that an idle gap on
the device can be laid against what the host was doing.
"""

from __future__ import annotations

import dataclasses
import time

import jax

LATE_S = 60.0   # how long past the close a due request may take


@dataclasses.dataclass
class Window:
    """What the gate saw at the window's open and close."""
    t0: float = 0.0
    t_end: float = 0.0
    steps0: int = 0
    steps1: int = 0
    open_info: dict = dataclasses.field(default_factory=dict)
    close_info: dict = dataclasses.field(default_factory=dict)
    opened: bool = False
    closed: bool = False


class Gate:
    def __init__(self, inner, *, clock=None, sleep=None):
        self.inner = inner
        self.clock = clock or time.time
        self.sleep = sleep or time.sleep
        self.eng = None
        self._held: list = []

    # -- Scheduler protocol ------------------------------------------------

    def bind(self, engine) -> None:
        self.eng = engine
        self.inner.bind(engine)

    def submit(self, req) -> None:
        self._held.append(req)

    @property
    def pending(self) -> int:
        return len(self._held) + self.inner.pending

    @property
    def queue(self):
        return self.inner.queue

    def is_decoding(self, lane: int) -> bool:
        return self.inner.is_decoding(lane)

    def maintain(self, state):
        with jax.profiler.TraceAnnotation("gate.maintain"):
            return self.inner.maintain(state)

    # -- run set-up ----------------------------------------------------------

    def arm(self, mode: str, *, seconds: float = 0.0, lanes: int = 0,
            max_ingests: int = 0, lead_s: float = 0.0, on_open=None,
            on_close=None) -> Window:
        """Prepare the next ``Engine.run``: submit its requests first, then
        arm.  ``on_open(state)`` / ``on_close(state)`` run at the window's
        open and close and return a dict kept in the ``Window``."""
        if mode not in ("serial", "closed", "open"):
            raise ValueError(f"unknown gate mode {mode!r}")
        self.mode, self.seconds, self.lanes = mode, float(seconds), lanes
        self.max_ingests = max_ingests or lanes or 1
        self.lead_s = float(lead_s)
        self.on_open, self.on_close = on_open, on_close
        self.t_arr0 = None
        self.due_in_window: list = []
        self.window = Window()
        self._ended = False
        # open loop: due order is the planned offsets' order
        if mode == "open":
            self._held.sort(key=lambda r: r.offset_s)
        return self.window

    # -- the per-step pass ---------------------------------------------------

    def refill(self, state, tokens, lanes, finished):
        with jax.profiler.TraceAnnotation("gate.refill"):
            now = self.clock()
            if not self._ended:
                if self.mode == "serial":
                    self._release_serial(lanes)
                elif self.mode == "closed":
                    state = self._step_closed(state, lanes, now)
                else:
                    state = self._step_open(state, lanes, now)
            if self._ended:
                for i in range(len(lanes)):
                    lanes[i] = None
                return state, tokens
            return self.inner.refill(state, tokens, lanes, finished)

    # -- helpers -------------------------------------------------------------

    def _busy(self, lanes) -> int:
        return sum(1 for r in lanes if r is not None and not r.done)

    def _ingesting(self, lanes) -> int:
        return self.inner.pending + sum(
            1 for i, r in enumerate(lanes)
            if r is not None and not r.done and not self.inner.is_decoding(i))

    def _release(self, req, due: float) -> None:
        req.arrived = due
        self.inner.submit(req)

    def _release_serial(self, lanes) -> None:
        # the run ends by itself: once nothing is held, the delegated
        # refill recycles the last lane and leaves every lane empty
        if self._held and not self._busy(lanes) and not self.inner.pending:
            self._release(self._held.pop(0), self.clock())

    def _open(self, state) -> None:
        w = self.window
        w.steps0 = self.eng.steps
        if self.on_open is not None:
            w.open_info = self.on_open(state)
        w.t0 = self.clock()
        w.t_end = w.t0 + self.seconds
        w.opened = True

    def _close(self, state) -> None:
        w = self.window
        w.steps1 = self.eng.steps
        if self.on_close is not None:
            w.close_info = self.on_close(state)
        w.closed = True

    def _step_closed(self, state, lanes, now: float):
        w = self.window
        if w.opened and now >= w.t_end:
            self._close(state)
            self._ended = True
            return state
        while (self._held and self._busy(lanes) + self.inner.pending
               < self.lanes and self._ingesting(lanes) < self.max_ingests):
            self._release(self._held.pop(0), now)
        if (not w.opened and self.inner.pending == 0
                and self._busy(lanes) == self.lanes
                and self._ingesting(lanes) == 0):
            self._open(state)
        return state

    def _step_open(self, state, lanes, now: float):
        w = self.window
        if self.t_arr0 is None:
            self.t_arr0 = now
        while True:
            now = self.clock()
            if not w.opened and now >= self.t_arr0 + self.lead_s:
                self._open(state)
                now = self.clock()
            if w.opened and not w.closed and now >= w.t_end:
                self._release_due(w.t_end)
                self._close(state)
            if w.closed:
                late = now >= w.t_end + LATE_S
                if late or all(r.first_token_at > 0
                               for r in self.due_in_window):
                    self._ended = True
                    return state
            else:
                self._release_due(now)
            if self._busy(lanes) or self.inner.pending:
                return state
            # idle: sleep to the next arrival, the window's open or close
            wake = [w.t_end if w.opened else self.t_arr0 + self.lead_s]
            if self._held and not w.closed:
                wake.append(self.t_arr0 + self._held[0].offset_s)
            if w.closed:
                return state      # due requests are all queued or served
            with jax.profiler.TraceAnnotation("gate.wait"):
                self.sleep(max(0.0, min(wake) - self.clock()))

    def _release_due(self, until: float) -> None:
        w = self.window
        while self._held and self.t_arr0 + self._held[0].offset_s <= until:
            req = self._held.pop(0)
            due = self.t_arr0 + req.offset_s
            self._release(req, due)
            if w.opened and w.t0 <= due <= w.t_end:
                self.due_in_window.append(req)
