"""The manifests resolve to files by name, use only the allowed characters,
and every per-layer metric's ``moves`` is reported wherever it is."""

import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MANIFESTS = [os.path.join(ROOT, "BENCHMARK.json"),
             os.path.join(HERE, "rehearsal.json")]


@pytest.fixture(params=MANIFESTS, ids=["BENCHMARK", "rehearsal"])
def manifest(request):
    with open(request.param) as f:
        return json.load(f)


def cells_of(metric, manifest):
    return set(metric.get("workloads")
               or [w["name"] for w in manifest["workloads"]])


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["chipbench"]
    assert manifest["command"] == ["python3", "chipbench/run.py"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_every_cell_resolves_to_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cfg = configs[w["config"]]
        used.add(w["config"])
        assert cfg["file"].startswith("chipbench/")
        with open(os.path.join(ROOT, cfg["file"])) as f:
            doc = json.load(f)
        assert doc["chips"] == w["chips"]
        assert {"arch", "worker_flags", "expect"} <= set(doc)
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        with open(os.path.join(BENCH, "cells", w["name"] + ".json")) as f:
            params = json.load(f)["params"]
        assert mix["loop"] in ("open", "closed")
        assert mix["scale_param"] in params
    assert used == set(configs)           # each configuration has a cell
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_names_units_and_sources(manifest):
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        names += [w["name"], w["traffic"]]
    for section, keys in (("end_to_end", {"name", "unit", "better", "bound",
                                          "source"}),
                          ("per_layer", {"name", "unit", "better", "source",
                                         "layer", "moves"})):
        for m in manifest[section]:
            assert set(m) - {"workloads"} == keys, m
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
            names.append(m["name"])
    assert all(NAME.match(n) for n in names), names
    metric_names = [m["name"] for s in ("end_to_end", "per_layer")
                    for m in manifest[s]]
    assert len(metric_names) == len(set(metric_names))
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]


def test_moves_is_reported_wherever_the_layer_metric_is(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    everywhere = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        assert cells_of(m, manifest) <= cells_of(e2e[m["moves"]], manifest), m
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py")), m
    for cell in everywhere:
        mine = [m["name"] for m in manifest["end_to_end"]
                if cell in cells_of(m, manifest)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(cell in cells_of(m, manifest)
                   for m in manifest["per_layer"])


def test_layer_names_are_the_ones_perf_md_lists(manifest):
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in manifest["per_layer"]:
        assert f"| {m['layer']} |" in perf, m["layer"]


def test_files_under_paths_use_only_name_characters():
    bad = []
    for d, _, files in os.walk(BENCH):
        if "__pycache__" in d:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), ROOT)
            if not re.match(r"^[A-Za-z0-9_.\-/]+$", rel):
                bad.append(rel)
    assert not bad
