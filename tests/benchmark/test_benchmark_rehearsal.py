"""``benchmark/run_cell.py`` end to end on the CPU: it refuses to measure
there, and each cell's tiny rehearsal runs through the real driver: the cells
the manifest holds from the repo itself, the cells parked in
``benchmark/workloads/`` from a copy whose manifest lists them too. Each run
is a child process with a timeout of its own."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


MANIFEST = load("BENCHMARK.json")
# every cell that has a file: those the manifest holds, and those parked in
# benchmark/workloads/ until a later PR proves them on the chip
CELLS = {
    name[:-len(".json")]: load("benchmark", "workloads", name)
    for name in sorted(os.listdir(os.path.join(ROOT, "benchmark", "workloads")))
    if name.endswith(".json")
}
HELD = {w["name"] for w in MANIFEST["workloads"]}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark whose manifest also lists the parked cells
    (and their configurations), as the PR that proves them will: entries
    added, no file edited."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    grown = json.loads(json.dumps(MANIFEST))
    have = {c["name"] for c in grown["configs"]}
    for name, job in CELLS.items():
        if name in HELD:
            continue
        grown["workloads"].append({"name": name, **{
            k: job[k] for k in ("config", "traffic", "chips", "why")}})
        if job["config"] not in have:
            have.add(job["config"])
            body = load("benchmark", "configs", f"{job['config']}.json")
            grown["configs"].append({
                "name": job["config"], "source": body["source"],
                "file": f"benchmark/configs/{job['config']}.json",
                "reduced": body["reduced"], "why": "parked"})
    (root / "BENCHMARK.json").write_text(json.dumps(grown))
    return root


def run_cell(where, cell, devices, *extra, seconds=2, trace=0, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, "benchmark/run_cell.py", "--workload", cell, "--seed",
         "2147483659", "--seconds", str(seconds), "--trace", str(trace), *extra],
        cwd=where, env=env, capture_output=True, text=True, timeout=timeout,
    )


def result_lines(stdout):
    lines = [json.loads(l) for l in stdout.splitlines() if l.startswith("{")]
    return [l for l in lines if "metrics" in l]


def metric_names(group, cell):
    return {m["name"] for m in MANIFEST[group]
            if "workloads" not in m or cell in m["workloads"]}


def test_the_cpu_is_refused_without_the_rehearsal_switch():
    out = run_cell(ROOT, next(iter(HELD)), 1, timeout=120)
    assert out.returncode != 0
    assert not result_lines(out.stdout)


def test_a_wrong_device_count_is_refused():
    cell = next(n for n in HELD if CELLS[n]["chips"] == 1)
    out = run_cell(ROOT, cell, 2, "--rehearsal", timeout=120)
    assert out.returncode != 0
    assert not result_lines(out.stdout)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_each_cell_rehearses_through_the_real_driver(cell, checkout):
    where = ROOT if cell in HELD else checkout
    out = run_cell(where, cell, CELLS[cell]["chips"], "--rehearsal")
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["attempted"] > 0
    assert line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in MANIFEST["end_to_end"]}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == CELLS[cell]["chips"]
    assert line["device"]["memory_peak_bytes"] > 0


def test_a_traced_rehearsal_reads_the_host_side_layer_metrics():
    """On the CPU there is no device plane, so the device readers find
    nothing and are left out; the host-side readers report."""
    cell = next(n for n in HELD if CELLS[n]["chips"] == 1)
    out = run_cell(ROOT, cell, 1, "--rehearsal", seconds=3, trace=1)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert {"compile_s", "input_host_ms_unit", "unit_ms_p50"} <= set(line["metrics"])
    assert set(line["metrics"]) <= metric_names("per_layer", cell)
    assert "mfu_pct" not in line["metrics"]  # never a device metric from the CPU
