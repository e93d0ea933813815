"""The gate drives the program's real engine on the CPU: a tiny open-loop
cell runs end to end, every request due in the window gets its first
token, and nothing compiles inside the window."""

import numpy as np

from chipbench.tests.helpers import run_tiny


def test_tiny_open_loop_cell_runs_end_to_end():
    res = run_tiny("tiny-moe", "tiny-open", seed=11, seconds=1.5,
                   metrics=("tokens_per_s", "itl_p95_ms", "ttft_p75_ms",
                            "setup_s"))
    assert res["correct"], res["check"]
    assert res["failed"] == 0
    assert res["window"]["compiles"] == 0
    assert res["window"]["requests_due"] == res["attempted"] > 0
    m = res["metrics"]
    assert set(m) == {"tokens_per_s", "itl_p95_ms", "ttft_p75_ms",
                      "setup_s"}
    assert all(np.isfinite(v["value"]) and v["value"] > 0
               for v in m.values())
