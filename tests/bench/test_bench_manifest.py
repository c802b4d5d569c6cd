"""BENCHMARK.json against the files it names, and against the contract's
own rules for names: what a later PR's added entry is held to as well."""
import json
import os
import re

import pytest

from benchmarks import run as bench_run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _cells_of(metric, manifest):
    return metric.get("workloads", [c["name"] for c in manifest["workloads"]])


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word
    assert manifest["command"][1].startswith(tuple(manifest["paths"]))
    four = sum(c["chips"] == 4 for c in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 2)
    setup = [m for m in manifest["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] <= 0.25


def test_every_name_and_unit_uses_the_allowed_characters(manifest):
    names = []
    for c in manifest["configs"]:
        names.append(c["name"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert "\n" not in w["why"] and "\t" not in w["why"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200
    assert all(NAME.match(n) for n in names), names
    for group in ("configs", "workloads"):
        ns = [x["name"] for x in manifest[group]]
        assert len(ns) == len(set(ns))
    ms = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(ms) == len(set(ms))
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_name_resolves_to_its_file(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    for w in manifest["workloads"]:
        cfg = configs[w["config"]]
        used.add(cfg["name"])
        with open(os.path.join(REPO, cfg["file"])) as f:
            body = json.load(f)
        assert cfg["file"] == f"benchmarks/configs/{cfg['name']}.json"
        assert body["source"] == cfg["source"]
        assert body["reduced"] == cfg["reduced"]
        assert body["chips"] == w["chips"]
        assert body["guarantees"] and body["assumed"] and body["rehearse"]
        assert os.path.isfile(os.path.join(
            BENCH, "deployments", body["deployment"] + ".py"))
        with open(os.path.join(BENCH, "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert os.path.isfile(os.path.join(BENCH, "loops",
                                           traffic["loop"] + ".py"))
    assert used == set(configs)         # each configuration has a cell
    # a variant without a file of its own reads with its quantity's
    for section, directory in (("end_to_end", "end_to_end"),
                               ("per_layer", "layer_metrics")):
        for m in manifest[section]:
            assert callable(bench_run.load_reader(directory, m["name"]))
    assert bench_run.reader_path("layer_metrics", "step_ms.lat") \
        == os.path.join(BENCH, "layer_metrics", "step_ms.py")
    assert bench_run.reader_path("layer_metrics", "hbm_peak_gb.x4") \
        == os.path.join(BENCH, "layer_metrics", "hbm_peak_gb.x4.py")


@pytest.mark.parametrize("kind", sorted(
    f[:-3] for f in os.listdir(os.path.join(BENCH, "deployments"))
    if f.endswith(".py") and f != "__init__.py"))
def test_every_deployment_module_brings_the_whole_contract(kind):
    """benchmarks/deployments/__init__.py: what run.py and tests/bench
    take from the module, before any object is built."""
    import importlib
    dep = importlib.import_module("benchmarks.deployments." + kind)
    assert callable(dep.build) and callable(dep.compare_small)
    for names in (dep.GUARANTEE_CHECKS, dep.COMPARE_CHECKS):
        assert names and all(isinstance(n, str) and n for n in names)
    assert all(n.startswith("compare.") for n in dep.COMPARE_CHECKS)
    assert not any("." in n for n in dep.GUARANTEE_CHECKS)  # no phase tag


def test_every_cell_reports_enough_and_moves_point_at_what_it_reports(
        manifest):
    cells = [c["name"] for c in manifest["workloads"]]
    e2e = {m["name"]: set(_cells_of(m, manifest))
           for m in manifest["end_to_end"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(_cells_of(m, manifest)) <= set(cells), m["name"]
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s", m
        assert set(_cells_of(m, manifest)) <= e2e[m["moves"]], m["name"]
    for c in cells:
        assert c in e2e["setup_s"]
        assert any(c in v for k, v in e2e.items() if k != "setup_s")
        assert any(c in _cells_of(m, manifest)
                   for m in manifest["per_layer"])


def test_one_layer_one_spelling(manifest):
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in manifest["per_layer"]}:
        assert f"**{layer}**" in perf, layer
