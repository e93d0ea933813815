"""The traffic generator: one catalog per mix, ordered by the seed."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["longctx-tiered", "chat-open"])
def test_same_seed_same_requests(name):
    a = traffic.plan(mix(name), 1000, seed=2 ** 31 + 7, seconds=20)
    b = traffic.plan(mix(name), 1000, seed=2 ** 31 + 7, seconds=20)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.offset_s) == (y.max_new, y.offset_s)


@pytest.mark.parametrize("name", ["longctx-tiered", "chat-open"])
def test_seeds_reorder_one_catalog(name):
    m = mix(name)
    a = traffic.plan(m, 1000, seed=1, seconds=20)
    b = traffic.plan(m, 1000, seed=2, seconds=20)
    key = lambda p: sorted((len(r.prompt), r.max_new) for r in p)  # noqa
    assert key(a) == key(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    if m["arrivals"]["process"] != "closed":
        gaps = lambda p: sorted(np.round(np.diff([r.offset_s for r in p]),
                                         9))  # noqa: E731
        # the same gaps, less the one each order drops at its end
        assert len(set(gaps(a)) ^ set(gaps(b))) <= 2


def test_lengths_follow_the_mix():
    m = mix("longctx-tiered")
    p = traffic.plan(m, 49155, seed=5, seconds=20)
    lens = np.array([len(r.prompt) for r in p])
    outs = np.array([r.max_new for r in p])
    assert lens.min() >= m["prompt_len"]["lo"]
    assert lens.max() <= m["prompt_len"]["hi"]
    assert outs.min() >= m["output_len"]["lo"]
    assert outs.max() <= m["output_len"]["hi"]
    assert all(r.offset_s is None for r in p)
    assert all(r.prompt.max() < 49155 for r in p)


def test_open_loop_rate_and_burstiness():
    m = mix("chat-open")
    m = dict(m, arrivals=dict(m["arrivals"], rate_per_s=5.0))
    p = traffic.plan(m, 100, seed=3, seconds=2000)
    gaps = np.diff([r.offset_s for r in p])
    assert np.mean(gaps) == pytest.approx(0.2, rel=0.1)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(2.0, rel=0.15)
    lens = np.array([len(r.prompt) for r in p])
    assert np.median(lens) == pytest.approx(512, rel=0.1)
    assert lens.min() >= 32 and lens.max() <= m["prompt_len"]["hi"]
