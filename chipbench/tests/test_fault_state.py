"""A run whose decode step leaves its KV state unchanged (the new K/V rows
are never persisted) comes out not correct."""

import pytest

from chipbench.tests.helpers import StepClock, run_tiny


@pytest.mark.parametrize("backend", ["tiered", "dense"])
def test_an_unchanged_state_fails_the_check(monkeypatch, backend):
    from repro.models import kv_backend
    StepClock().install(monkeypatch)
    if backend == "tiered":
        monkeypatch.setattr(kv_backend.TieredBackend, "end_step",
                            lambda self, caches, knv, pos, aux: caches)
        res = run_tiny("tiny-dense", "tiny-closed", seed=3)
    else:
        monkeypatch.setattr(kv_backend.DenseBackend, "append",
                            lambda self, cache, k, v, pos, ring=False: cache)
        res = run_tiny("tiny-dense", "tiny-dense-closed", seed=2)
    assert not res["correct"]
