"""The gate on a toy engine: due-time stamping, the window's open and
close, and the wait for the next arrival, on a fake clock."""

import dataclasses

from chipbench.gate import Gate


@dataclasses.dataclass
class Req:
    rid: int
    offset_s: float | None = None
    arrived: float = 0.0
    admitted_at: float = 0.0
    first_token_at: float = 0.0
    done: bool = False
    left: int = 3


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class Inner:
    """A scheduler that admits to free lanes and decodes a token per step."""

    def __init__(self, clock):
        self.q, self.clock = [], clock

    def bind(self, eng):
        pass

    def submit(self, r):
        self.q.append(r)

    @property
    def pending(self):
        return len(self.q)

    queue = property(lambda self: tuple(self.q))

    def is_decoding(self, lane):
        return True

    def maintain(self, state):
        return state

    def refill(self, state, tokens, lanes, finished):
        for i, r in enumerate(lanes):
            if r is not None and r.done:
                finished.append(r)
                lanes[i] = None
            if lanes[i] is None and self.q:
                lanes[i] = self.q.pop(0)
                lanes[i].admitted_at = self.clock()
        return state, tokens


class Eng:
    steps = 0


def drive(gate, clock, lanes, step_s=0.1, max_steps=10_000):
    """The engine loop: refill, then a step for every busy lane."""
    finished = []
    gate.refill(None, None, lanes, finished)
    while any(r is not None for r in lanes) and max_steps:
        clock.t += step_s
        gate.eng.steps += 1
        for r in lanes:
            if r is not None and not r.done:
                if not r.first_token_at:
                    r.first_token_at = clock()
                r.left -= 1
                r.done = r.left <= 0
        gate.refill(None, None, lanes, finished)
        max_steps -= 1
    return finished


def test_open_loop_stamps_due_times_and_drains_the_window():
    clock = Clock()
    gate = Gate(Inner(clock), clock=clock, sleep=clock.sleep)
    gate.bind(Eng())
    offsets = [0.0, 0.05, 3.0, 3.01, 3.02, 6.5, 9.0, 30.0]
    reqs = [Req(i, o) for i, o in enumerate(offsets)]
    for r in reqs:
        gate.submit(r)
    w = gate.arm("open", seconds=5.0, lead_s=2.0)
    drive(gate, clock, [None, None])
    t0 = 100.0
    for r in reqs[:6]:
        assert r.arrived == t0 + r.offset_s      # stamped with its due time
        assert r.admitted_at >= r.arrived
    assert w.opened and w.closed
    assert w.t0 >= t0 + 2.0 and w.t_end == w.t0 + 5.0
    assert [r.rid for r in gate.due_in_window] == [2, 3, 4, 5]
    assert all(r.first_token_at > 0 for r in gate.due_in_window)
    assert reqs[6].arrived == reqs[7].arrived == 0.0   # due after the close
    # the idle engine slept to the 3 s arrival rather than spinning
    assert reqs[2].admitted_at == reqs[2].arrived


def test_closed_backlog_opens_when_every_lane_decodes():
    clock = Clock()
    gate = Gate(Inner(clock), clock=clock, sleep=clock.sleep)
    gate.bind(Eng())
    reqs = [Req(i) for i in range(200)]
    for r in reqs:
        gate.submit(r)
    w = gate.arm("closed", seconds=2.0, lanes=3, max_ingests=3)
    lanes = [None] * 3
    drive(gate, clock, lanes)
    assert w.opened and w.closed
    assert w.t_end - w.t0 == 2.0
    assert all(r is None for r in lanes)          # the run was ended
    released = [r for r in reqs if r.arrived]
    assert all(r.admitted_at == r.arrived for r in released)
    # ~20 steps of 3 lanes, each request 3 tokens: a few dozen released
    assert 15 <= len(released) <= 40


def test_serial_mode_releases_one_at_a_time():
    clock = Clock()
    inner = Inner(clock)
    gate = Gate(inner, clock=clock, sleep=clock.sleep)
    gate.bind(Eng())
    reqs = [Req(i) for i in range(4)]
    for r in reqs:
        gate.submit(r)
    gate.arm("serial")
    done = drive(gate, clock, [None, None])
    assert [r.rid for r in done] == [0, 1, 2, 3]
    for a, b in zip(reqs, reqs[1:]):
        assert b.admitted_at > a.first_token_at
