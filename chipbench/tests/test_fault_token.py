"""A run whose timed path alters a token where it is produced comes out
not correct: the harness is driven end to end on the CPU, with the chip
check skipped, and the decode step's logits are bent towards one token."""

from chipbench.tests.helpers import StepClock, run_tiny


def test_an_altered_token_fails_the_check(monkeypatch):
    from repro.serve.engine import Engine
    make = Engine._step_fn

    def bent(self, n_pages):
        step = make(self, n_pages)

        def fn(params, state, tokens):
            logits, state = step(params, state, tokens)
            return logits.at[:, 7].add(1e3), state
        return fn

    monkeypatch.setattr(Engine, "_step_fn", bent)
    StepClock().install(monkeypatch)
    res = run_tiny("tiny-dense", "tiny-closed", seed=3)
    assert not res["correct"]
    assert res["check"]["mean_logit_gap"]["value"] > \
        res["check"]["mean_logit_gap"]["limit"]
