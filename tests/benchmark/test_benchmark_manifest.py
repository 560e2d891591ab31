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


def test_a_described_models_comparison_block_resolves(manifest):
    """Every configuration with a ``comparison`` block names what the one
    driver of described models reads: a reference under ``benchmark/lib/``,
    the function that gives its kernels' shapes, leaf groups and counters,
    and where it has experts, the key of their count in its architecture."""
    from benchmark.drivers import train_lm

    described = 0
    for c in manifest["configs"]:
        body = load(c["file"])
        if "comparison" not in body:
            continue
        described += 1
        compare = body["comparison"]
        reference = importlib.import_module(
            f"benchmark.lib.{compare['reference']}")
        assert callable(reference.loss_and_grad_by_layer), c["name"]
        module, _, function = compare["kernel_shapes"].partition(":")
        kernels = importlib.import_module(f"benchmark.lib.{module}")
        assert callable(getattr(kernels, function)), c["name"]
        assert compare["leaf_groups"] and isinstance(compare["counters"], list)
        if "experts_held_key" in compare:
            assert compare["experts_held_key"] in train_lm.arch_of(body)
    assert described >= 2


#: what a later PR adds for a described model: its kernels' module, with its
#: family of scopes for lib/scope_spans.py (this one reads the gated delta
#: rule's under new names)
THROWAWAY_KERNELS = '''
import json

from benchmark.lib import delta_kernels

SCOPES = ("linattn", "delta_rule")
SPANS = "throwaway_spans"
least_seconds_unit = delta_kernels.least_seconds_unit


def kernel_shapes(arch, per_chip, seq_len):
    out = {"throwaway": delta_kernels.kernel_shapes(
        arch, per_chip, seq_len)["delta"]}
    print(json.dumps({"detail": "throwaway_kernels", "value": out}))
    return out
'''
THROWAWAY_READERS = {
    # the one scope reduction: left out on the CPU, which traces no device
    "throwaway_scope_ms_unit": ("device_trace", "ms", '''
from benchmark.lib import scope_spans


def read(run):
    return scope_spans.under_ms_unit(run, "throwaway_kernels", "linattn")
'''),
    # the step's own counter: read on the CPU too
    "throwaway_decay_min": ("program_counter", "nat", '''
def read(run):
    seen = (run.get("counters") or {}).get("delta_chunk_log_decay_min")
    return min(seen) if seen else None
'''),
}
CELL = "throwaway_sync_cell"


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """A copy of the checkout with a described remat cell added as new files
    and manifest entries: a configuration whose comparison block names the
    one driver's reference and a kernels' module of its own, a workload on
    ``train_lm_ref:run``, and two per-layer metrics. Returns the copy and the
    bytes of every file that was there before."""
    root = tmp_path_factory.mktemp("grown")
    for part in ("benchmark", os.path.join("tests", "benchmark")):
        shutil.copytree(os.path.join(ROOT, part), root / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    # the program, as a checkout has it
    os.symlink(os.path.join(ROOT, "mpit_tpu"), root / "mpit_tpu")
    # every file but the manifest, which takes entries
    before = {p: p.read_bytes() for p in root.rglob("*")
              if p.is_file() and "mpit_tpu" not in p.parts
              and p.name != "BENCHMARK.json"}
    bench = root / "benchmark"
    config = load("benchmark", "configs", "olmo-hybrid-7b.json")
    config["source"] = "https://example.org/throwaway-hybrid"
    config["comparison"]["kernel_shapes"] = "throwaway_kernels:kernel_shapes"
    (bench / "configs" / "throwaway-hybrid.json").write_text(json.dumps(config))
    (bench / "lib" / "throwaway_kernels.py").write_text(THROWAWAY_KERNELS)
    job = load("benchmark", "workloads", "olmo_hybrid_sync_1chip_8k.json")
    job.update(config="throwaway-hybrid", why="a described remat cell",
               driver="train_lm_ref:run")
    (bench / "workloads" / f"{CELL}.json").write_text(json.dumps(job))
    grown = load("BENCHMARK.json")
    grown["configs"].append(
        {"name": "throwaway-hybrid", "source": config["source"],
         "file": "benchmark/configs/throwaway-hybrid.json",
         "reduced": config["reduced"], "why": "t"})
    grown["workloads"].append({"name": CELL, "config": "throwaway-hybrid",
                               "traffic": job["traffic"], "chips": 1,
                               "why": job["why"]})
    for name, (source, unit, reader) in THROWAWAY_READERS.items():
        (bench / "readers" / f"{name}.py").write_text(reader)
        entry = {"name": name, "unit": unit, "better": "lower",
                 "source": source, "layer": "Collective trainers",
                 "moves": "samples_per_s_chip"}
        (bench / "layer_metrics" / f"{name}.json").write_text(json.dumps(
            {**{k: v for k, v in entry.items() if k != "name"},
             "cells": [CELL], "reader": f"{name}:read"}))
        grown["per_layer"].append({**entry, "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(grown, indent=2))
    return root, before


def test_a_later_pr_adds_files_and_entries_and_edits_nothing(grown):
    """The one command rehearses the added cell through the one driver and
    reads its metrics, and no file that was there is edited."""
    root, before = grown
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(root),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run(
        [sys.executable, "benchmark/run_cell.py", "--workload", CELL,
         "--seed", "2147483659", "--seconds", "2", "--trace", "1",
         "--rehearsal"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    line = lines[-1]
    assert line["correct"] is True and line["failed"] == 0
    # the configuration's own kernels' module gave the shapes
    shapes = next(l["value"] for l in lines
                  if l.get("detail") == "throwaway_kernels")
    assert shapes["throwaway"]["layers"] == 3
    assert line["metrics"]["throwaway_decay_min"]["value"] < 0
    assert "throwaway_scope_ms_unit" not in line["metrics"]
    # cells' own metrics only: no accepted list names the new cell
    manifest = load("BENCHMARK.json")
    assert set(line["metrics"]) <= set(THROWAWAY_READERS) | {
        m["name"] for m in manifest["per_layer"] if "workloads" not in m}
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"


def test_the_contract_tests_hold_on_the_grown_manifest(grown):
    """Every test of ``tests/benchmark/`` that checks files and the manifest,
    run in the grown copy against its own ``BENCHMARK.json``: none pins the
    manifest as it stands. The rehearsals and the program-against-copy
    comparisons, which the addition does not touch, are left out."""
    root, _ = grown
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(root))
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/benchmark", "-q",
         "-p", "no:cacheprovider", "-p", "no:randomly", "-p", "no:xdist",
         "-k", "not rehearsal and not reference_is_the_programs "
               "and not a_later_pr and not contract_tests_hold"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stdout[-3000:]
    assert " passed" in out.stdout.strip().splitlines()[-1]
