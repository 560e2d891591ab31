"""The CI lint gate, exercised exactly the way CI runs it.

The acceptance contract for the analysis subsystem:

- ``python -m mpit_tpu.analysis --format json`` over the package exits 0
  with ZERO non-baseline findings (and the baseline itself stays small and
  reviewed);
- the whole-package scan is fast enough for a pre-commit hook;
- the scan IMPORTS NOTHING it analyzes — it must be safe on code that
  would crash, hang, or initialize a TPU backend at import time.
"""

from __future__ import annotations

import json
import subprocess
import sys
import time
from pathlib import Path

import pytest

from mpit_tpu.analysis import lint

REPO = Path(__file__).resolve().parent.parent
PKG = REPO / "mpit_tpu"


def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "mpit_tpu.analysis", *args],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )


def test_gate_json_exits_clean_with_no_new_findings():
    proc = _cli("--format", "json", str(PKG))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["findings"] == []
    assert doc["baselined"] > 0  # the baseline is in use, not bypassed
    assert doc["total_scanned"] == doc["baselined"]


def _run_gate_script():
    """``scripts/lint.sh`` as CI runs it: the finished process, its
    wall-clock seconds and the per-gate seconds it printed."""
    start = time.monotonic()
    proc = subprocess.run(
        ["bash", str(REPO / "scripts" / "lint.sh")],
        capture_output=True, text=True, cwd=REPO, timeout=300,
    )
    elapsed = time.monotonic() - start
    timings = {}
    for line in proc.stdout.splitlines():
        if line.startswith("[lint] gate "):
            parts = line.split()
            timings[parts[2]] = float(parts[3].rstrip("s"))
    return proc, elapsed, timings


def test_gate_script_passes_and_runs_every_gate():
    """The full default run — all ten gates — stays green, and each
    gate ran: what the gates found, whatever the machine's speed."""
    proc, _, timings = _run_gate_script()
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # state counts + conformance tally + the wire-schema trio (lock
    # check, fixtures, fuzz) + numerics
    assert "states" in proc.stdout, proc.stdout
    assert "violation(s)" in proc.stdout, proc.stdout
    assert "15 tag(s) match" in proc.stdout, proc.stdout
    assert "fuzz gate ok" in proc.stdout, proc.stdout
    assert "RT104 smoke ok" in proc.stdout, proc.stdout
    # ten numbered gates + the warn-only bench-trend tail
    assert len(timings) == 11, sorted(timings)


@pytest.mark.slow
def test_gate_script_within_wall_clock_budgets():
    """The budgets the model checker and the fuzz gate were sized for
    (state space and example count are knobs; this test is the
    governor): 35 s for the whole run, wire-schema (the 10k-example
    fuzz run plus corpus replay and the lockfile check) under 20 s,
    numerics (three fixture scans plus the RT104 smoke) under 8 s, read
    from the per-gate timing lines the script prints for exactly this
    purpose. Marked slow: the budgets were sized on a faster machine,
    and a wall-clock bound under a loaded six-worker run says nothing
    of the gates."""
    proc, elapsed, timings = _run_gate_script()
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert elapsed < 35.0, f"lint gate took {elapsed:.1f}s (budget 35s)"
    assert timings["wire-schema"] < 20.0, timings
    assert timings["numerics"] < 8.0, timings


def test_gate_fails_on_a_new_finding(tmp_path):
    bad = tmp_path / "drifted.py"
    bad.write_text(
        "import pickle\n"
        "# mpit-analysis: wire-boundary\n"
        "def frame(x):\n"
        "    return pickle.dumps(x, protocol=4)\n"
    )
    proc = _cli("--format", "json", "--no-baseline", str(bad))
    assert proc.returncode == 1
    doc = json.loads(proc.stdout)
    assert [f["rule"] for f in doc["findings"]] == ["MPT007"]


def test_whole_package_scan_is_fast():
    """< 5 s in-process for the full package, cross-module passes
    included — the pre-commit-hook budget from the acceptance bar."""
    start = time.monotonic()
    lint.run_lint([PKG])
    assert time.monotonic() - start < 5.0


def test_scan_never_imports_analyzed_code(tmp_path):
    """Linting a module whose import has a visible side effect must not
    trigger that side effect (and must not crash on its bare
    ``raise``)."""
    marker = tmp_path / "imported.marker"
    mod = tmp_path / "boobytrap.py"
    mod.write_text(
        f"open({str(marker)!r}, 'w').close()\n"
        "raise RuntimeError('imported, not parsed')\n"
    )
    lint.run_lint([mod])
    assert not marker.exists()
