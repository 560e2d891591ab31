"""BENCHMARK.json against its contract, and the harness against its promise
that a later PR adds files and entries and edits nothing. CPU, seconds."""

import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return load("BENCHMARK.json")


def cells_of(manifest, metric):
    return metric.get("workloads", [w["name"] for w in manifest["workloads"]])


def line_ok(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= len(manifest["command"]) <= 32
    for word in manifest["command"]:
        assert line_ok(word) and not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in manifest["paths"])


def test_files_under_paths_are_named_from_name_characters(manifest):
    files = [
        os.path.relpath(os.path.join(where, name), ROOT)
        for p in manifest["paths"]
        for where, dirs, names in os.walk(os.path.join(ROOT, p))
        if "__pycache__" not in where  # git-ignored
        for name in names
    ]
    assert files
    for f in files:
        assert PATH.match(f), f


def test_configs(manifest):
    assert 1 <= len(manifest["configs"]) <= 24
    names = [c["name"] for c in manifest["configs"]]
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line_ok(c["source"]) and line_ok(c["why"])
        assert c["name"] in used, "a configuration no cell uses"
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        body = load(c["file"])
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
        assert "train_config" in body


def test_gpt2_small_runs_at_its_published_sizes():
    body = load("benchmark", "configs", "gpt2-small.json")
    src, run = body["source_config"], body["train_config"]
    assert (run["layers"], run["d_model"], run["heads"], run["seq_len"]) == (
        src["n_layer"], src["n_embd"], src["n_head"], src["n_positions"])
    assert body["vocab_size"] == src["vocab_size"] == 50257
    assert run["d_ff"] == 0 and src["n_inner"] is None  # both mean 4 x d_model


def test_workloads(manifest):
    cells = manifest["workloads"]
    assert 1 <= len(cells) <= 24
    names = [w["name"] for w in cells]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in manifest["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line_ok(w["why"])
        job = load("benchmark", "workloads", f"{w['name']}.json")
        for key in ("config", "traffic", "chips", "why"):
            assert job[key] == w[key], (w["name"], key)
        assert "rehearsal" in job and "per_chip_batch" in job
        module, _, function = job.get("driver", "train:run").partition(":")
        assert callable(getattr(
            importlib.import_module(f"benchmark.drivers.{module}"), function))
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)


def test_end_to_end_metrics(manifest):
    metrics = manifest["end_to_end"]
    assert [m["name"] for m in metrics] == [
        "samples_per_s_chip", "unit_ms_p90", "setup_s"]
    for m in metrics:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for w in manifest["workloads"]:
        reported = [m["name"] for m in metrics if w["name"] in cells_of(manifest, m)]
        assert "setup_s" in reported and len(reported) >= 2


def test_per_layer_metrics_resolve_and_move_what_their_cells_report(manifest):
    metrics = manifest["per_layer"]
    assert 1 <= len(metrics) <= 128
    names = [m["name"] for m in metrics + manifest["end_to_end"]]
    assert len(set(names)) == len(names)
    all_cells = {w["name"] for w in manifest["workloads"]}
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    for m in metrics:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert line_ok(m["layer"])
        assert set(cells_of(manifest, m)) <= all_cells and cells_of(manifest, m)
        moved = end_to_end[m["moves"]]
        assert set(cells_of(manifest, m)) <= set(cells_of(manifest, moved))
        own = load("benchmark", "layer_metrics", f"{m['name']}.json")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert own[key] == m[key], (m["name"], key)
        assert own["cells"] == m.get("workloads", "all")
        module, _, function = own["reader"].partition(":")
        assert callable(getattr(
            importlib.import_module(f"benchmark.readers.{module}"), function))
    for w in manifest["workloads"]:
        assert any(w["name"] in cells_of(manifest, m) for m in metrics)


THROWAWAY_DRIVER = '''
def run(ctx):
    return {"correct": True, "attempted": ctx["config"]["marker"], "failed": 0,
            "memory_peak_bytes": 1, "setup_s": 1.0,
            "end_to_end": {"samples_per_s_chip": 2.0, "unit_ms_p90": 3.0},
            "run": {"answer": ctx["workload"]["answer"], "trace": None}}
'''
THROWAWAY_READER = '''
def read(run):
    return run["answer"]
'''


def test_a_later_pr_adds_files_and_entries_and_edits_nothing(tmp_path, manifest):
    """A throw-away configuration, cell, layer metric and driver in a copy of
    ``benchmark/``: new files plus manifest entries, no edit to a file that is
    there, and the one command runs the new cell and reads the new metric."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*")
              if p.is_file()}
    bench = tmp_path / "benchmark"
    (bench / "configs" / "throwaway.json").write_text(json.dumps(
        {"source": "https://example.org/throwaway", "reduced": [],
         "train_config": {}, "marker": 41}))
    (bench / "workloads" / "throwaway_cell.json").write_text(json.dumps(
        {"config": "throwaway", "traffic": "none", "chips": 1, "why": "test",
         "driver": "throwaway:run", "answer": 42.5, "per_chip_batch": 1,
         "rehearsal": {}}))
    (bench / "layer_metrics" / "throwaway_metric.json").write_text(json.dumps(
        {"unit": "x", "better": "lower", "source": "program_counter",
         "layer": "Entry", "moves": "setup_s", "cells": ["throwaway_cell"],
         "reader": "throwaway_metric:read"}))
    (bench / "drivers" / "throwaway.py").write_text(THROWAWAY_DRIVER)
    (bench / "readers" / "throwaway_metric.py").write_text(THROWAWAY_READER)
    grown = json.loads(json.dumps(manifest))
    grown["configs"].append(
        {"name": "throwaway", "source": "https://example.org/throwaway",
         "file": "benchmark/configs/throwaway.json", "reduced": [], "why": "t"})
    grown["workloads"].append(
        {"name": "throwaway_cell", "config": "throwaway", "traffic": "none",
         "chips": 1, "why": "test"})
    grown["per_layer"].append(
        {"name": "throwaway_metric", "unit": "x", "better": "lower",
         "source": "program_counter", "layer": "Entry", "moves": "setup_s",
         "workloads": ["throwaway_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(grown))

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run(
        [sys.executable, "benchmark/run_cell.py", "--workload",
         "throwaway_cell", "--seed", "1", "--seconds", "1", "--trace", "1",
         "--rehearsal"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["attempted"] == 41  # the new configuration's file was read
    assert line["metrics"]["throwaway_metric"] == {"value": 42.5, "unit": "x"}
    # cells' own metrics only: none of the other layer metrics lists this cell
    assert set(line["metrics"]) <= {"throwaway_metric"} | {
        m["name"] for m in manifest["per_layer"] if "workloads" not in m}
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"
