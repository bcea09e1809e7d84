"""BENCHMARK.json against the benchmark's contract: names and units, what
each metric moves and where it is reported, the files each entry names,
and traffic that depends on the seed alone."""

from __future__ import annotations

import os
import re

import numpy as np
import pytest

from bench import core, gen

M = core.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
E2E = {m["name"]: m for m in M["end_to_end"]}
CELLS = {c["name"]: c for c in M["workloads"]}
METRIC_KEYS = {"name", "unit", "better", "source"}


def test_top_level_keys_and_command():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(M["command"]) <= 32
    for word in M["command"]:
        assert 1 <= len(word) <= 200 and "\n" not in word and "\t" not in word
        assert not word.startswith("/") and ".." not in word
    files = [w for w in M["command"] if w.endswith(".py")]
    assert files and all(any(f.startswith(p + "/") for p in M["paths"])
                         for f in files)
    assert 1 <= len(M["paths"]) <= 16
    for p in M["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(core.ROOT, p))


def test_run_seconds_fits_the_check_budget():
    rs = M["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", M["configs"] + M["workloads"]
                         + M["end_to_end"] + M["per_layer"],
                         ids=lambda e: e["name"])
def test_names_units_and_text(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    for key in ("why", "layer", "source"):
        if key in entry:
            text = entry[key]
            assert 1 <= len(text) <= 200 and "\n" not in text
            assert "\t" not in text
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")


def test_unique_names():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in M[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(c["config"], c["traffic"]) for c in M["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cfg", M["configs"], ids=lambda c: c["name"])
def test_config_file_resolves(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert any(cfg["file"].startswith(p + "/") for p in M["paths"])
    data = core.load_config(M, cfg["name"])
    assert data["name"] == cfg["name"]
    assert data["source"] == cfg["source"]
    assert len(cfg["reduced"]) <= 16
    for key in cfg["reduced"]:
        assert NAME.match(key)
        assert not re.search(
            r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|head|"
            r"expand|expansion|experts_per_tok)", key), f"{key} is a width"
    assert os.path.exists(os.path.join(core.BENCH, "drivers",
                                       f"{data['driver']}.py"))
    assert os.path.exists(os.path.join(core.BENCH, "reference",
                                       f"{data['reference']}.py"))
    assert any(c["config"] == cfg["name"] for c in M["workloads"])
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda c: c["name"])
def test_cell_resolves_and_reports(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    core.config_entry(M, cell["config"])
    traffic = core.load_traffic(cell["traffic"])
    assert traffic["kind"] in gen.KINDS
    e2e = [m["name"] for m in core.cell_metrics(M, cell["name"],
                                                "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert core.cell_metrics(M, cell["name"], "per_layer")


def test_four_chip_cells_within_the_cap():
    four = sum(c["chips"] == 4 for c in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 2)


@pytest.mark.parametrize("metric", M["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads", "bound"} == METRIC_KEYS
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.25
    for w in metric.get("workloads", []):
        assert w in CELLS
    assert "setup_s" in E2E


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_moves_what_its_cells_report(metric):
    assert set(metric) - {"workloads"} == METRIC_KEYS | {"layer", "moves"}
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    moved = E2E[metric["moves"]]
    cells = metric.get("workloads", list(CELLS))
    for w in cells:
        assert w in CELLS
        assert "workloads" not in moved or w in moved["workloads"], (
            f"{w} reports {metric['name']} but not {metric['moves']}")
    path = os.path.join(core.BENCH, "metrics", f"{metric['name']}.py")
    assert os.path.exists(path)
    assert callable(core.load_module("metrics", metric["name"]).read)
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_layers_named_alike():
    layers = {m["layer"] for m in M["per_layer"]}
    for name in layers:
        assert name == name.strip() and "\n" not in name


@pytest.mark.parametrize("name", ["batch64", "stream1"])
def test_frame_traffic_is_the_seeds_alone(name):
    traffic = dict(core.load_traffic(name), bank_calls=2)
    a = np.asarray(gen.frame_bank(traffic, 2**31 + 3, (4, 4, 3)))
    b = np.asarray(gen.frame_bank(traffic, 2**31 + 3, (4, 4, 3)))
    c = np.asarray(gen.frame_bank(traffic, 3, (4, 4, 3)))
    assert a.shape == (2, traffic["frames_per_call"], 4, 4, 3)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_manifest_small_enough():
    assert os.path.getsize(os.path.join(core.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024

