"""BENCHMARK.json against the files it names, and the proof that a
later PR adds a cell with new files only."""

import json
import os
import shutil

import pytest

from benchmarks.lib import manifest

ROOT = manifest.checkout_root()
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_every_cell_has_its_files_and_they_agree_with_benchmark_json():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        cell = manifest.load_cell(w["name"])
        assert cell["config"] == w["config"] and w["config"] in configs
        assert cell["traffic"] == w["traffic"]
        assert cell["chips"] == w["chips"] and cell["why"] == w["why"]
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert hasattr(manifest.load_driver(cell["driver"]), "run")
        cfg = configs[w["config"]]
        assert cfg["file"] == f"benchmarks/configs/{w['config']}.json"
        assert cfg["source"] == cell["config_file"]["source"]
        assert cfg["reduced"] == cell["config_file"]["reduced"]
    assert {c["name"] for c in BENCH["configs"]} == {
        w["config"] for w in BENCH["workloads"]}


def test_names_and_units_use_only_the_allowed_characters():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [c["name"] for c in BENCH["configs"]]
    for group in ("end_to_end", "per_layer", "workloads", "configs"):
        own = [x["name"] for x in BENCH[group]]
        assert len(set(own)) == len(own), group
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for n in names:
        assert manifest.NAME_RE.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert manifest.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1


def test_cells_report_what_benchmark_json_says_and_moves_is_reported():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    layer = {m["name"]: m for m in BENCH["per_layer"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for w in BENCH["workloads"]:
        cell = manifest.load_cell(w["name"])
        assert "setup_s" in cell["end_to_end"]
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
        for name, unit in cell["end_to_end"].items():
            assert e2e[name]["unit"] == unit
            assert w["name"] in e2e[name].get("workloads", cells)
        for name in cell["per_layer"]:
            spec, entry = cell["layer_metric_files"][name], layer[name]
            for key in ("layer", "unit", "better", "source", "moves"):
                assert spec[key] == entry[key], (name, key)
            assert w["name"] in entry.get("workloads", cells)
            assert spec["moves"] in cell["end_to_end"], (name, w["name"])
            assert hasattr(manifest.load_reader(spec["reader"]), "read")
    for m in list(e2e.values()) + list(layer.values()):
        for cell_name in m.get("workloads", []):
            cell = manifest.load_cell(cell_name)
            assert m["name"] in cell["end_to_end"] or \
                m["name"] in cell["per_layer"]


def test_a_later_pr_adds_a_cell_with_new_files_only(tmp_path):
    copy = tmp_path / "benchmarks"
    shutil.copytree(manifest.BENCH_DIR, copy,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: os.path.getmtime(os.path.join(d, p))
              for d, _, fs in os.walk(copy) for p in fs}
    new_cell = dict(manifest.load_json(
        "workloads", "gpt2-large.serve-long", str(copy)))
    new_cell["traffic"] = "serve-short"
    new_cell["per_layer"] = new_cell["per_layer"] + ["queue_wait_ms.serve"]
    (copy / "workloads" / "gpt2-large.serve-short.json").write_text(
        json.dumps(new_cell))
    mix = manifest.load_json("traffic", "serve-long", str(copy))
    mix["prompt_len"] = {"dist": "log_uniform", "low": 16, "high": 128}
    (copy / "traffic" / "serve-short.json").write_text(json.dumps(mix))
    (copy / "layer_metrics" / "queue_wait_ms.serve.json").write_text(
        json.dumps({"layer": "serving", "unit": "ms", "better": "lower",
                    "source": "program_counter", "moves": "ttft_p95_ms",
                    "reader": "queue_wait"}))
    (copy / "readers" / "queue_wait.py").write_text(
        "def read(obs):\n    return obs['counters'].get('queue_wait_ms')\n")
    assert "gpt2-large.serve-short" in manifest.list_names(
        "workloads", str(copy))
    cell = manifest.load_cell("gpt2-large.serve-short", str(copy))
    got = manifest.read_layer_metrics(
        cell, {"counters": {"queue_wait_ms": 3.5, "compile_s_setup": 1.0}},
        str(copy))
    assert got["queue_wait_ms.serve"] == {"value": 3.5, "unit": "ms"}
    assert "decode_tick_ms.serve" not in got      # nothing to read: left out
    after = {p: os.path.getmtime(os.path.join(d, p))
             for d, _, fs in os.walk(copy) for p in fs if p in before}
    assert after == before                         # no existing file edited


def test_unknown_names_fail():
    with pytest.raises(FileNotFoundError):
        manifest.load_cell("no-such.cell")
    with pytest.raises(ValueError):
        manifest.load_cell("../etc/passwd")
