"""Operations, bytes, roofline and MFU from a configuration's shapes."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def granite():
    return json.loads((CONFIGS / "granite-moe-3b.json").read_text())


def qwen():
    return json.loads((CONFIGS / "qwen2-7b-14l.json").read_text())


def test_paged_attention_needs_each_live_row_once():
    mc = granite()
    f, b = flops.paged_attn_need(mc, [100, 300])
    H, KV, hd = 24, 8, 64
    assert f == 4 * 400 * H * hd
    rows = 400 * KV * hd * 2 * 2                 # K and V, bf16
    per_lane = (H * hd * 2 + KV * hd * 2) * 2    # q, out, new K, new V
    assert b == rows + 2 * per_lane


def test_active_parameters_count_routed_experts_only():
    mc = granite()
    d, ff, L = 1536, 512, 32
    attn = d * 24 * 64 * 2 + d * 8 * 64 * 2
    per_layer = attn + 3 * d * ff * 8 + d * 40
    assert flops.active_params_per_token(mc) == L * per_layer + 49155 * d
    q = qwen()
    attn = 3584 * 28 * 128 * 2 + 3584 * 4 * 128 * 2
    assert flops.active_params_per_token(q) == 14 * (
        attn + 3 * 3584 * 18944) + 152064 * 3584


def test_decode_flops_grow_with_context_by_the_attention_term():
    mc = qwen()
    a, b = flops.decode_token_flops(mc, [0, 1000])
    assert b - a == pytest.approx(4 * 1000 * 28 * 128 * 14)
    assert a == 2 * flops.active_params_per_token(mc)


@pytest.mark.parametrize("ctx", [[16], [4096] * 4, [1, 2000, 3000]])
def test_the_algorithms_own_work_at_peak_is_exactly_the_roofline(ctx):
    mc = granite()
    f, b = flops.paged_attn_need(mc, ctx)
    least = max(f / PEAK["bf16_flops_per_s"], b / PEAK["hbm_bytes_per_s"])
    share, bound = flops.roofline_share(f, b, least, PEAK)
    assert share == pytest.approx(1.0)
    assert bound == "memory"                 # decode attention: ~1 FLOP/B
    for slower in (1.01, 2.0, 100.0):
        s, _ = flops.roofline_share(f, b, least * slower, PEAK)
        assert s < 1.0


def test_compute_bound_when_flops_dominate():
    share, bound = flops.roofline_share(197e12, 1.0, 2.0, PEAK)
    assert bound == "compute" and share == pytest.approx(0.5)


def test_mfu_reader_is_flops_over_window_times_peak():
    import dataclasses
    from chipbench.metrics import decode_mfu

    @dataclasses.dataclass
    class R:
        prompt: np.ndarray
        token_times: list

    class V:
        mc, peak, seconds, chips = qwen(), PEAK, 2.0, 1

        def window_tokens(self):
            r = R(np.zeros(10, np.int32), [0.0, 1.0, 1.5])
            for j, t in enumerate(r.token_times):
                yield r, j, t

    # tokens 1 and 2 are decode tokens at contexts 11 and 12
    want = flops.decode_token_flops(qwen(), [11, 12]).sum() / (2.0 * 197e12)
    assert decode_mfu.read(V()) == pytest.approx(100 * want)
