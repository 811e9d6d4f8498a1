"""BENCHMARK.json against the contract's limits, and every name in it
against the files it stands for: a cell, a configuration, a traffic mix or
a metric is added with new files and new entries, nothing else."""

import json
import os
import re

import pytest

from benchmarks import harness

REPO = harness.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10
    assert manifest["paths"] == ["benchmarks"]
    assert manifest["command"][:2] == ["python3", "benchmarks/run.py"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    n = len(manifest["workloads"])
    # a full check has to fit the driver's allowance with 24 cells
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 2 * 90 + 1200 \
        <= 43200
    assert 1 <= n <= 24
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) \
        <= max(1, n // 2)


def test_names_units_and_entry_keys(manifest):
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("benchmarks/")
        assert os.path.exists(os.path.join(REPO, c["file"]))
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line(w["why"])
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and line(m["layer"])
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in (metrics, manifest["workloads"], manifest["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    assert len({(w["config"], w["traffic"])
                for w in manifest["workloads"]}) == len(manifest["workloads"])
    assert {c["name"] for c in manifest["configs"]} \
        == {w["config"] for w in manifest["workloads"]}
    assert len({c["file"] for c in manifest["configs"]}) \
        == len(manifest["configs"])


def test_every_cell_reports_what_the_contract_asks(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    cells = [w["name"] for w in manifest["workloads"]]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(m.get("workloads", cells)) <= set(cells), m["name"]
    for name in cells:
        cell = harness.resolve_cell(manifest, name)
        assert "setup_s" in cell.e2e and len(cell.e2e) >= 2
        assert cell.layers, f"{name} reads no per-layer metric"
    for m in manifest["per_layer"]:
        # `moves` names an end-to-end metric that each of its cells reports
        assert m["moves"] in e2e, m["name"]
        for name in m.get("workloads", cells):
            assert m["moves"] in harness.resolve_cell(manifest, name).e2e, \
                (m["name"], name)


def test_every_name_has_its_file(manifest):
    for m in manifest["end_to_end"]:
        assert callable(harness.load_reader("e2e", m["name"]).read)
    for m in manifest["per_layer"]:
        assert callable(harness.load_reader("layers", m["name"]).read)
    for w in manifest["workloads"]:
        cell = harness.resolve_cell(manifest, w["name"])
        t = cell.traffic
        assert t["loop"] in ("saturate", "open")
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "laws", t["law"] + ".py"))
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "worlds",
            cell.config["world"]["builder"] + ".py"))
        assert cell.config["chips"] == w["chips"]
        if t["loop"] == "open":
            assert cell.knee and cell.knee["knee_frames_per_s"] > 0
            assert harness.resolve_rate(cell) == pytest.approx(
                t["knee_share"] * cell.knee["knee_frames_per_s"])
    for c in manifest["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        assert cfg["guarantees"] and cfg["source"]


def test_the_harness_names_no_cell(manifest):
    """run.py, harness.py and sweep.py hold no cell's, configuration's,
    traffic mix's or metric's name: they find them all in the manifest."""
    names = {x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in manifest[k]}
    names |= {w["traffic"] for w in manifest["workloads"]}
    names -= {"setup_s", "saturate"}        # the contract's own words
    for fn in ("run.py", "harness.py"):
        with open(os.path.join(harness.BENCH_DIR, fn)) as f:
            text = f.read()
        for n in names:
            assert n not in text, f"{fn} names {n!r}"


def test_file_names_hold_only_permitted_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for root, dirs, files in os.walk(harness.BENCH_DIR):
        dirs[:] = [d for d in dirs if d not in (".build", "__pycache__",
                                                ".pytest_cache")]
        for fn in files:
            rel = os.path.relpath(os.path.join(root, fn), REPO)
            assert ok.match(rel) and len(rel) <= 200, rel


def test_peaks_table_names_its_source():
    with open(os.path.join(harness.BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert "source" in v5e
