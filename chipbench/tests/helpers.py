"""Shared set-up for the benchmark's tests: tiny configurations run on the
CPU through the same ``run_cell`` a chip run uses."""

import json
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"


def tiny_cell(config: str, mix: str, metrics=("tokens_per_s",)):
    from chipbench import run
    mc = json.loads((DATA / f"{config}.json").read_text())
    limits = json.loads((DATA / "tiny-cells.json").read_text())[config]
    return run.Cell(f"{config}.{mix}", 1, mc,
                    json.loads((DATA / f"{mix}.json").read_text()), limits,
                    [(m, "x") for m in metrics])


def run_tiny(config: str, mix: str, *, seed: int, seconds: float = 1.0,
             control=None, metrics=("tokens_per_s",)):
    from chipbench import run
    return run.run_cell(tiny_cell(config, mix, metrics), seed=seed,
                        seconds=seconds, trace=False, peak=None, device={},
                        control=control)


class StepClock:
    """A clock that advances a millisecond at every read, so that a run's
    window holds the same steps on every machine; ``install`` puts it in
    place of ``time.time`` and ``time.sleep`` for the engine and the gate
    alike."""

    def __init__(self):
        import time
        self.t = time.time()

    def time(self):
        self.t += 1e-3
        return self.t

    def sleep(self, s):
        self.t += max(s, 0.0)

    def install(self, monkeypatch):
        import time
        monkeypatch.setattr(time, "time", self.time)
        monkeypatch.setattr(time, "sleep", self.sleep)
