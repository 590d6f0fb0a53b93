"""BENCHMARK.json is consistent with the files under benchmark/, and a
later PR can add a cell by adding files and entries only."""

import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import manifest  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_./-]+$")
# a layer's name may also start with "_"; the driver refuses a path or a phrase
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def m():
    return manifest.load_manifest()


def test_manifest_has_exactly_the_contracts_keys(m):
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["command"] == ["python3", "benchmark/run.py"]
    assert m["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert os.path.getsize(manifest.MANIFEST_PATH) <= 64 * 1024


def test_every_name_is_plain_and_used_once(m):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in m[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        ns = [x["name"] for x in m[k]]
        assert len(ns) == len(set(ns))
    metrics = [x["name"] for k in ("end_to_end", "per_layer") for x in m[k]]
    assert len(metrics) == len(set(metrics))
    for x in m["configs"] + m["workloads"]:
        assert len(x["why"]) <= 200, x["name"]


def test_files_under_paths_have_plain_names(m):
    for top in m["paths"]:
        for d, _, files in os.walk(os.path.join(ROOT, top)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert PLAIN_PATH.match(rel), rel


def test_cells_are_within_the_contracts_limits(m):
    cells = m["workloads"]
    assert 2 <= len(cells) <= 24
    assert all(c["chips"] in (1, 4) for c in cells)
    four = sum(1 for c in cells if c["chips"] == 4)
    assert four <= max(1, len(cells) // 4)
    pairs = [(c["config"], c["traffic"]) for c in cells]
    assert len(pairs) == len(set(pairs))
    used = {c["config"] for c in cells}
    assert used == {c["name"] for c in m["configs"]}


def test_every_cell_resolves_to_files_by_name(m):
    for w in m["workloads"]:
        cell = manifest.resolve_cell(m, w["name"])
        assert cell.config["name"] == w["config"]
        for kind, name in (
            ("builder", cell.config["builder"]),
            ("reference", cell.config["reference"]),
            ("driver", cell.traffic["driver"]),
        ):
            assert os.path.isfile(manifest.module_path(kind, name)), (kind, name)
        if cell.traffic["generator"]:
            assert os.path.isfile(
                manifest.module_path("generator", cell.traffic["generator"]))
        driver = manifest.load_module("driver", cell.traffic["driver"])
        assert callable(driver.run) and callable(driver.rehearse)
        for kind in ("end_to_end", "per_layer"):
            for metric in getattr(cell, kind):
                reader = manifest.load_module(kind, metric["name"])
                assert callable(reader.compute), metric["name"]
                assert reader.__doc__ and len(reader.__doc__) > 40


def test_every_cell_file_belongs_to_a_cell_and_drains_state_their_rate(m):
    cells = {w["name"] for w in m["workloads"]}
    files = {f[:-5] for f in os.listdir(os.path.join(ROOT, "benchmark", "cells"))}
    assert files <= cells
    serve_drain = manifest.load_module("driver", "serve_drain")
    for name in cells:
        cell = manifest.resolve_cell(m, name)
        if cell.traffic["driver"] == "serve_drain":
            assert serve_drain.stated_rate(cell) > 0
            # at run_seconds the drain finishes the tail's hundred
            assert serve_drain.drain_size(
                serve_drain.stated_rate(cell), m["run_seconds"]) >= 100


def test_a_drain_without_a_stated_rate_is_an_error_that_names_the_file(m):
    import dataclasses

    serve_drain = manifest.load_module("driver", "serve_drain")
    cell = dataclasses.replace(
        manifest.resolve_cell(m, "gpt2s_serve_chat"), params={})
    with pytest.raises(KeyError, match="cells/gpt2s_serve_chat.json"):
        serve_drain.stated_rate(cell)


def test_every_reader_file_is_listed_and_every_listed_metric_has_one(m):
    for kind, directory in (("end_to_end", "e2e_metrics"),
                            ("per_layer", "layer_metrics")):
        files = {f[:-3] for f in os.listdir(os.path.join(ROOT, "benchmark", directory))
                 if f.endswith(".py")}
        assert files == {x["name"] for x in m[kind]}


def test_configs_name_a_public_source_and_cut_no_width(m):
    for entry in m["configs"]:
        assert entry["source"].startswith("https://huggingface.co/")
        assert entry["file"].startswith("benchmark/configs/")
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        assert config["source"] == entry["source"]
        assert config["reduced"] == entry["reduced"] == []
        assert config["n_embd"] == config["n_head"] * 64
    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files))


def test_metric_entries_follow_the_contract(m):
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and 1 <= len(m["per_layer"]) <= 128
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] == 0.1
    for x in m["end_to_end"]:
        assert x["source"] in ("host_clock", "device_trace")
        assert x["better"] in ("higher", "lower")
        assert 0.01 <= x["bound"] <= 0.1
    cells = {w["name"] for w in m["workloads"]}
    for x in m["per_layer"]:
        assert x["source"] in SOURCES and x["moves"] in e2e
        assert "bound" not in x and LAYER.match(x["layer"]), x
        # reported only where the metric it moves is
        here = set(x.get("workloads", cells))
        there = set(e2e[x["moves"]].get("workloads", cells))
        assert here <= there, x["name"]
    for name in cells:
        cell = manifest.resolve_cell(m, name)
        others = [x for x in cell.end_to_end if x["name"] != "setup_s"]
        assert others and cell.per_layer, name


def test_every_layer_is_named_in_perf_md(m):
    with open(os.path.join(ROOT, "PERF.md")) as f:
        layers_section = f.read().split("## 3. Layers")[1].split("\n## ")[0]
    for x in m["per_layer"]:
        assert "| `%s` " % x["layer"] in layers_section, x["layer"]


def test_adding_a_cell_needs_files_and_entries_only(tmp_path):
    """A dummy configuration, mix, metric and cell in a copy of the
    benchmark: no file that was there is edited, and everything
    resolves by name."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()

    m = manifest.load_manifest()
    with open(os.path.join(root, "benchmark/configs/dummy.v1.json"), "w") as f:
        json.dump({"name": "dummy.v1", "builder": "gpt",
                   "reference": "gpt2_ref", "n_embd": 128}, f)
    with open(os.path.join(root, "benchmark/traffic/dummy-mix.json"), "w") as f:
        json.dump({"driver": "serve_drain", "generator": "sessions",
                   "requests": {}}, f)
    with open(os.path.join(root, "benchmark/cells/dummy_cell.json"), "w") as f:
        json.dump({"drain_requests_per_s": 2.5}, f)
    with open(os.path.join(root, "benchmark/layer_metrics/dummy.count.py"), "w") as f:
        f.write('"""A dummy reader."""\n\n\ndef compute(record):\n'
                '    return record.get("dummy")\n')
    m["configs"].append({"name": "dummy.v1", "source": "https://example.org",
                         "file": "benchmark/configs/dummy.v1.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "dummy_cell", "config": "dummy.v1",
                           "traffic": "dummy-mix", "chips": 1, "why": "test"})
    m["per_layer"].append({"name": "dummy.count", "unit": "1", "better": "higher",
                           "source": "program_counter", "layer": "none",
                           "moves": "setup_s", "workloads": ["dummy_cell"]})

    cell = manifest.resolve_cell(m, "dummy_cell", root=root)
    assert cell.config["n_embd"] == 128
    assert cell.traffic["driver"] == "serve_drain"
    assert cell.params == {"drain_requests_per_s": 2.5}
    assert [x["name"] for x in cell.per_layer] == ["dummy.count"]
    assert [x["name"] for x in cell.end_to_end] == ["setup_s"]
    reader = manifest.load_module("per_layer", "dummy.count", root=root)
    assert reader.compute({"dummy": 3}) == 3
    assert reader.compute({}) is None
    # the old cells do not see the new metric
    old = manifest.resolve_cell(m, "gpt2s_train", root=root)
    assert "dummy.count" not in [x["name"] for x in old.per_layer]
    assert old.params == {}  # a cell with nothing of its own has no file
    for p, content in before.items():
        assert open(p, "rb").read() == content, p


@pytest.mark.parametrize("bad", ["../x", "a/b", "", "x" * 65, ".hidden"])
def test_names_that_are_not_plain_are_refused(bad):
    with pytest.raises(ValueError):
        manifest.module_path("driver", bad)


def test_an_unknown_workload_is_an_error_that_lists_the_cells(m):
    with pytest.raises(KeyError, match="gpt2s_train"):
        manifest.resolve_cell(m, "nope")
