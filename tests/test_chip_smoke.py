"""Rehearsal of ``chip_smoke.py`` on the CPU: the same phases on a toy
granite-family model in bf16, with the engine's fused decode running the
Pallas kernel in interpret mode instead of its jnp oracle."""

import dataclasses
import importlib.util
from pathlib import Path

import pytest

from repro.configs import get_config, reduce_for_smoke

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_cpu(chip_smoke, monkeypatch, capsys):
    monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
    assert chip_smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_phases_on_toy_model(chip_smoke, monkeypatch):
    from repro.kernels.paged_attention import ops
    kernel = ops.paged_attention_fused_op
    monkeypatch.setattr(ops, "paged_attention_fused_op",
                        lambda *a, impl: kernel(*a, impl="kernel"))
    cfg = dataclasses.replace(reduce_for_smoke(get_config(chip_smoke.ARCH)),
                              dtype="bfloat16")
    n_req, max_new = 4, 8
    facts = chip_smoke.smoke(cfg, seed=0, batch=4, max_len=128,
                             page_tokens=8, fast_slots=4,
                             n_requests=n_req, max_new=max_new,
                             prompt_len=(34, 60), log=lambda s: None)
    # on the CPU the step holds no Pallas custom call: everything else
    # must hold as it does on the chip
    assert chip_smoke.failures(facts, max_new, n_req) == [
        "tiered decode step holds no Pallas custom call"]
    tiered = facts["runs"]["tiered"]
    assert tiered["counters"]["lookups"] > 0
    assert tiered["warm_compile_s"] == 0.0
    assert facts["runs"]["dense"]["served"] == n_req * max_new
