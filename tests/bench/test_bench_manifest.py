"""BENCHMARK.json and the files it names keep to the benchmark's rules."""
import json
import math
import os
import re

import pytest

from conftest import ROOT

M = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|proj|"
                   r"head|expansion|d_model|d_ff|experts_per_tok")


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_and_limits():
    assert set(M) == KEYS["top"]
    assert len(json.dumps(M)) <= 64 * 1024
    assert M["command"][:2] == ["python3", "bench/run.py"]
    assert 1 <= M["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in M[group]:
            extra = {"workloads"} if group in ("end_to_end",
                                              "per_layer") else set()
            assert KEYS[group] <= set(e) <= KEYS[group] | extra, e


def test_paths_hold_the_benchmark():
    assert 1 <= len(M["paths"]) <= 16
    for p in M["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    for word in M["command"]:
        assert _line(word)
        if word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in M["paths"])


def test_names_units_and_lines():
    names = [e["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in M[g]]
    for group in ("configs", "workloads"):
        got = [e["name"] for e in M[group]]
        assert len(got) == len(set(got))
    metrics = [e["name"] for g in ("end_to_end", "per_layer")
               for e in M[g]]
    assert len(metrics) == len(set(metrics))
    for n in names:
        assert NAME.match(n), n
    for w in M["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert _line(w["why"])
    for c in M["configs"]:
        assert _line(c["why"]) and _line(c["source"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
    for e in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
    for e in M["per_layer"]:
        assert _line(e["layer"])


def test_cells_configs_and_files():
    cfgs = {c["name"]: c for c in M["configs"]}
    pairs = {(w["config"], w["traffic"]) for w in M["workloads"]}
    assert len(pairs) == len(M["workloads"])
    assert {w["config"] for w in M["workloads"]} == set(cfgs)
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))
    for c in M["configs"]:
        assert any(c["file"].startswith(p + "/") for p in M["paths"])
        doc = json.load(open(os.path.join(ROOT, c["file"])))
        assert doc["name"] == c["name"]
        assert sorted(doc["reduced"]) == sorted(c["reduced"])
        assert not any(WIDTH.search(k) for k in c["reduced"])
    for w in M["workloads"]:
        assert w["chips"] in (1, 4)
        cell = json.load(open(os.path.join(ROOT, "bench", "workloads",
                                           w["name"] + ".json")))
        assert {k: cell[k] for k in ("config", "traffic", "chips")} == \
            {k: w[k] for k in ("config", "traffic", "chips")}
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
        assert set(cell["limits"]) == {"loss_gap", "grad_gap", "change_gap"}


def test_four_chip_cells_within_the_limit():
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, math.floor(len(M["workloads"]) / 2))


def test_metrics_reported_where_they_move():
    cells = {w["name"] for w in M["workloads"]}
    e2e = {e["name"]: e for e in M["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for e in M["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for e in M["per_layer"]:
        assert e["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert e["moves"] in e2e
        moved = set(e2e[e["moves"]].get("workloads", cells))
        assert set(e.get("workloads", cells)) <= moved & cells
    for c in cells:
        assert any(e["name"] != "setup_s" for e in M["end_to_end"]
                   if c in e.get("workloads", cells))
        assert any(c in e.get("workloads", cells) for e in M["per_layer"])
    layers = {}
    for e in M["per_layer"]:
        layers.setdefault(e["layer"].lower(), set()).add(e["layer"])
    assert all(len(v) == 1 for v in layers.values())


@pytest.mark.parametrize("group", ["end_to_end", "per_layer"])
def test_every_metric_has_a_reader(group):
    for e in M[group]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           e["name"] + ".py"))


def test_check_fits_the_day():
    cells = 24
    runs = 2 + 14 * cells
    total = runs * (M["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert total <= 43200
