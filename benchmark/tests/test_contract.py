"""``BENCHMARK.json`` against the contract's limits and against the files
it names: what the driver would refuse before a single run."""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
WIDTH = re.compile(r"(_dim|_rank)$|(hidden|intermediate|latent|state|"
                   r"proj\w*|head)_size|expansion|experts_per_tok")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert len(bench["command"]) <= 32 and all(map(line, bench["command"]))
    assert bench["paths"] == ["benchmark"]
    assert isinstance(bench["run_seconds"], int)
    # 2 + 14 x 24 runs of run_seconds + 60, 24 x 180 to compile, 1200 spare
    assert ((2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200
            <= 43200)


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names) <= 24
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmark/")
        assert len(c["reduced"]) <= 16
        assert not any(WIDTH.search(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["reduced"] == c["reduced"]
        importlib.import_module(f"benchmark.families.{held['family']}")


def test_workloads(bench):
    cells = bench["workloads"]
    names = [w["name"] for w in cells]
    assert 2 <= len(cells) <= 24 and len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in bench["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert traffic["mesh"] == {"data": w["chips"]}
        importlib.import_module(f"benchmark.jobs.{traffic['job']}")
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e, layers = bench["end_to_end"], bench["per_layer"]
    names = [m["name"] for m in e2e + layers]
    assert len(set(names)) == len(names)
    assert 1 <= len(e2e) <= 16 and 1 <= len(layers) <= 128

    def where(metric):
        return set(metric.get("workloads", cells))

    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    setup = next(m for m in e2e if m["name"] == "setup_s")
    assert where(setup) == cells
    for m in layers:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert line(m["layer"])
        moved = next(e for e in e2e if e["name"] == m["moves"])
        # a per-layer metric is reported only where the metric it moves is
        assert where(m) <= where(moved)
        reader = importlib.import_module(
            f"benchmark.layer_metrics.{m['name']}")
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            m["layer"], m["unit"], m["moves"])
    for m in e2e + layers:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert where(m) <= cells
    for cell in cells:
        assert sum(cell in where(m) for m in e2e) >= 2
        assert any(cell in where(m) for m in layers)


def test_files_are_named_from_the_characters_of_a_name():
    for folder, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        if "__pycache__" in folder:
            continue
        for name in files:
            rel = os.path.relpath(os.path.join(folder, name), ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
