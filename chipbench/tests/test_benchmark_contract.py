"""BENCHMARK.json and the files it names: every cell, metric reader,
traffic mix, configuration and limit is in place, and each configuration
builds the program's model config the repo registers, except for what the
file lists as changed."""

import dataclasses
import importlib
import json
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    assert 1 <= BENCH["run_seconds"] <= 51


def test_every_cell_has_its_files():
    confs = {c["name"]: c for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4)
        assert (REPO / confs[w["config"]]["file"]).is_file()
        assert (REPO / "chipbench/traffic" / f"{w['traffic']}.json").is_file()
        limits = json.loads(
            (REPO / "chipbench/cells" / f"{w['name']}.json").read_text())
        assert limits["mean_logit_gap"]["limit"] > 0
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == set(confs)


def test_every_metric_has_a_reader_and_every_cell_reports_enough():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        mod = importlib.import_module(f"chipbench.metrics.{m['name']}")
        assert callable(mod.read)
        assert m["better"] in ("lower", "higher")
    cells = [w["name"] for w in BENCH["workloads"]]

    def reports(m, cell):
        return cell in m.get("workloads", cells)

    for cell in cells:
        got = [m["name"] for m in BENCH["end_to_end"] if reports(m, cell)]
        assert "setup_s" in got and len(got) >= 2
        assert any(reports(m, cell) for m in BENCH["per_layer"])
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert reports(moved, cell), (m["name"], cell)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


# keys in ``reduced`` that map onto an ArchConfig field
_FIELD = {"num_hidden_layers": "n_layers", "rms_norm_eps": "rms_eps",
          "tie_word_embeddings": "tie_embeddings"}
# the repo's registered model of each configuration
_REGISTERED = {"granite-moe-3b": "granite-moe-3b-a800m",
               "qwen2-7b-14l": "qwen2-7b"}
# where the benchmark runs the published value and the repo's
# registration does not (the registration predates the benchmark)
_FROM_SOURCE = {"granite-moe-3b": {"rms_eps", "tie_embeddings"},
                "qwen2-7b-14l": {"rms_eps"}}


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_builds_the_registered_model(conf):
    from chipbench.model import arch_config
    from repro.configs import get_config
    mc = json.loads((REPO / conf["file"]).read_text())
    assert mc["reduced"] == conf["reduced"]
    assert set(mc["reduced"]) <= set(mc["published"])
    ours = dataclasses.asdict(arch_config(mc))
    theirs = dataclasses.asdict(get_config(_REGISTERED[conf["name"]]))
    skip = {"name"} | _FROM_SOURCE[conf["name"]] | {
        _FIELD[k] for k in mc["reduced"] if k in _FIELD}
    diff = {k for k in ours if k not in skip and ours[k] != theirs[k]}
    assert not diff, {k: (ours[k], theirs[k]) for k in diff}
