"""The control: the reference at float8 (e4m3) weights, put in the
program's place, must fail the cell's limit while the program passes it
(at a tiny size on the CPU; the chip readings that set the real cells'
limits are in PERF.md)."""

import pytest

from chipbench.tests.helpers import StepClock, run_tiny


@pytest.mark.parametrize("config,mix,seed", [
    ("tiny-dense", "tiny-closed", 2), ("tiny-dense", "tiny-open", 1)])
def test_the_program_passes_and_the_control_fails(config, mix, seed,
                                                  monkeypatch):
    StepClock().install(monkeypatch)
    res = run_tiny(config, mix, seed=seed, control="fp8")
    limit = res["check"]["mean_logit_gap"]["limit"]
    assert res["correct"]
    assert res["check"]["mean_logit_gap"]["value"] <= limit
    assert res["control"]["mean_logit_gap"] > limit
    assert res["control"]["tokens"] >= 30
