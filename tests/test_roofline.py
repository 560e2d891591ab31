"""Roofline attribution tests (ISSUE 6: device/wire/idle split).

Layers under test: the interval algebra and the per-rank join in
``mpit_tpu.obs.merge.roofline`` (synthetic journals with known answers),
the real AsyncPSTrainer integration (client compute spans, server idle,
fractions summing to ~1.0), the chaos acceptance criterion (seeded
injected delay must land in the WIRE phase, not compute), the CLI, and
bench.py's two reporting paths (``phase_source: "timed-leg"`` for the
collective legs, ``"obs"`` for the host-async PS preset) plus the probe
cache/env-knob satellite.
"""

import json
import os

import numpy as np
import pytest

from mpit_tpu.obs import ObsConfig, roofline
from mpit_tpu.obs.__main__ import main as obs_main
from mpit_tpu.obs.merge import _merge_intervals, _overlap


def _write_rank(tmp_path, rank, recs):
    """Hand-authored journal with CONTROLLED wall-clock: the Journal class
    stamps its own ``t``, so synthetic known-answer fixtures write the
    JSONL directly."""
    with open(os.path.join(str(tmp_path), f"obs_rank{rank}.jsonl"),
              "w") as f:
        for r in recs:
            f.write(json.dumps({"rank": rank, **r}) + "\n")


class TestIntervalAlgebra:
    def test_merge_intervals(self):
        assert _merge_intervals([]) == []
        assert _merge_intervals([(1, 2), (3, 4)]) == [(1, 2), (3, 4)]
        assert _merge_intervals([(3, 5), (1, 2), (2, 4)]) == [(1, 5)]
        assert _merge_intervals([(1, 1), (2, 3)]) == [(2, 3)]  # empty drop

    def test_overlap(self):
        merged = _merge_intervals([(1, 3), (5, 7)])
        assert _overlap(0, 10, merged) == 4
        assert _overlap(2, 6, merged) == 2
        assert _overlap(3, 5, merged) == 0
        assert _overlap(8, 9, merged) == 0


class TestRooflineSynthetic:
    def test_known_answer_attribution(self, tmp_path):
        """Client: 1.0 s compute span, 0.1 s send + 0.5 s in-exchange recv
        wait (wire), 0.3 s out-of-span wait (idle) over a 2.5 s window —
        overhead is the 0.6 s remainder. Server: span-less, so its waits
        are idle."""
        _write_rank(tmp_path, 1, [
            {"ev": "span_b", "t": 0.0, "name": "compute", "span": 1},
            {"ev": "span_e", "t": 1.0, "name": "compute", "span": 1},
            {"ev": "span_b", "t": 1.0, "name": "exchange", "span": 2},
            {"ev": "send", "t": 1.1, "dst": 0, "mtag": 1, "n": 0,
             "bytes": 10, "dur": 0.1},
            {"ev": "recv", "t": 1.8, "src": 0, "mtag": 4, "n": 0,
             "bytes": 20, "wait": 0.5},
            {"ev": "span_e", "t": 2.0, "name": "exchange", "span": 2},
            {"ev": "recv", "t": 2.5, "src": 0, "mtag": 4, "n": 1,
             "bytes": 20, "wait": 0.3},
        ])
        _write_rank(tmp_path, 0, [
            {"ev": "recv", "t": 1.0, "src": 1, "mtag": 1, "n": 0,
             "bytes": 10, "wait": 0.8},
            {"ev": "send", "t": 1.5, "dst": 1, "mtag": 4, "n": 0,
             "bytes": 20, "dur": 0.1},
        ])
        rep = roofline([str(tmp_path)])
        cli = rep["ranks"][1]
        assert cli["role"] == "client"
        assert cli["compute_s"] == pytest.approx(1.0)
        assert cli["wire_s"] == pytest.approx(0.6)
        assert cli["idle_s"] == pytest.approx(0.3)
        assert cli["overhead_s"] == pytest.approx(0.6)
        assert cli["window_s"] == pytest.approx(2.5)
        assert cli["phases"]["compute"] == pytest.approx(0.4)
        assert sum(cli["phases"].values()) == pytest.approx(1.0)
        assert cli["exchanges"] == 1
        assert cli["exchange_mean_s"] == pytest.approx(1.0)
        srv = rep["ranks"][0]
        assert srv["role"] == "server"
        assert srv["idle_s"] == pytest.approx(0.8)  # span-less wait
        assert srv["wire_s"] == pytest.approx(0.1)
        assert sum(srv["phases"].values()) == pytest.approx(1.0)
        assert rep["run"]["ranks"] == 2 and rep["run"]["clients"] == 1
        assert sum(rep["run"]["phases"].values()) == pytest.approx(1.0)
        assert rep["straggler"] is None  # one client: no comparison

    def test_straggler_flagged(self, tmp_path):
        for rank, dur in ((1, 1.0), (2, 2.0)):
            _write_rank(tmp_path, rank, [
                {"ev": "span_b", "t": 0.0, "name": "compute", "span": 1},
                {"ev": "span_e", "t": dur, "name": "compute", "span": 1},
            ])
        rep = roofline([str(tmp_path)])
        assert rep["straggler"] == 2

    def test_unclosed_span_and_empty(self, tmp_path):
        # a killed rank's dangling span_b must not crash or count
        _write_rank(tmp_path, 1, [
            {"ev": "span_b", "t": 0.0, "name": "compute", "span": 1},
            {"ev": "send", "t": 0.5, "dst": 0, "mtag": 1, "n": 0,
             "bytes": 1, "dur": 0.1},
        ])
        rep = roofline([str(tmp_path)])
        assert rep["ranks"][1]["compute_s"] == 0.0
        assert rep["ranks"][1]["role"] == "client"  # the span DID open
        assert roofline([]) == {
            "ranks": {}, "run": None, "straggler": None
        }


class TestRooflineCLI:
    def test_exit_codes_and_output(self, tmp_path, capsys):
        empty = tmp_path / "empty"
        empty.mkdir()
        assert obs_main(["roofline", str(empty)]) == 2
        run = tmp_path / "run"
        run.mkdir()
        _write_rank(run, 0, [
            {"ev": "recv", "t": 0.0, "src": 1, "mtag": 1, "n": 0,
             "bytes": 1, "wait": 0.2},
            {"ev": "send", "t": 0.5, "dst": 1, "mtag": 4, "n": 0,
             "bytes": 1, "dur": 0.1},
        ])
        assert obs_main(["roofline", str(run)]) == 0
        out = capsys.readouterr().out
        assert "server" in out and "compute" in out
        assert obs_main(["roofline", str(run), "--json"]) == 0
        rep = json.loads(capsys.readouterr().out)
        assert set(rep["ranks"]["0"]["phases"]) == {
            "compute", "wire", "idle", "overhead"
        }


def _trainer(tmp_path, chaos=None):
    import jax.numpy as jnp
    import optax

    from mpit_tpu.models import MLP
    from mpit_tpu.parallel import AsyncPSTrainer

    return AsyncPSTrainer(
        MLP(compute_dtype=jnp.float32),
        optax.sgd(0.05, momentum=0.9),
        num_clients=2,
        num_servers=1,
        algo="easgd",
        tau=4,
        transport="inproc",
        chaos=chaos,
        obs=ObsConfig(dir=str(tmp_path)),
        max_exchange_failures=5,
        fetch_timeout=2.0,
        fetch_retries=3,
    )


@pytest.fixture(scope="module")
def mnist():
    from mpit_tpu.data import load_mnist

    return load_mnist(synthetic_train=2048, synthetic_test=512)


class TestRooflineTrainerIntegration:
    def test_real_run_attribution(self, tmp_path, mnist):
        x_tr, y_tr, *_ = mnist
        trainer = _trainer(tmp_path)
        trainer.train(x_tr, y_tr, steps=16, batch_size=32)
        rep = roofline([str(tmp_path)])
        assert set(rep["ranks"]) == {0, 1, 2}
        srv, c1, c2 = rep["ranks"][0], rep["ranks"][1], rep["ranks"][2]
        assert srv["role"] == "server" and srv["idle_s"] > 0
        for c in (c1, c2):
            assert c["role"] == "client"
            assert c["compute_s"] > 0  # the ps_roles compute spans landed
            assert c["exchanges"] == 16 // 4
        for row in rep["ranks"].values():
            assert abs(sum(row["phases"].values()) - 1.0) <= 0.02
        assert abs(sum(rep["run"]["phases"].values()) - 1.0) <= 0.02
        # the proof-of-completion barrier makes compute the clients'
        # dominant measured phase on this CPU workload
        assert c1["phases"]["compute"] > c1["phases"]["wire"]

    def test_chaos_delay_lands_in_wire_not_compute(self, tmp_path, mnist):
        """The ISSUE acceptance criterion: a seeded ChaosTransport delay
        run must attribute the injected latency to the WIRE phase. The
        chaos sleep happens inside the send path, under the telemetry
        wrapper's timer — so send ``dur`` (wire) absorbs it while the
        compute spans stay clean."""
        from mpit_tpu.transport import ChaosConfig

        x_tr, y_tr, *_ = mnist
        clean_dir = tmp_path / "clean"
        chaos_dir = tmp_path / "chaos"
        clean_dir.mkdir(), chaos_dir.mkdir()
        _trainer(clean_dir).train(x_tr, y_tr, steps=16, batch_size=32)
        chaos = ChaosConfig(
            seed=7, delay=1.0, delay_s=0.05, tags=(1, 2, 4)
        )
        _trainer(chaos_dir, chaos=chaos).train(
            x_tr, y_tr, steps=16, batch_size=32
        )
        clean = roofline([str(clean_dir)])
        delayed = roofline([str(chaos_dir)])
        clean_wire = sum(
            r["wire_s"] for r in clean["ranks"].values()
        )
        delayed_wire = sum(
            r["wire_s"] for r in delayed["ranks"].values()
        )
        # every send on tags 1/2/4 sleeps U(0, 50 ms); across ~9 sends
        # per client plus the PARAM replies the injected total is far
        # above anything the clean inproc run can produce
        assert delayed_wire > max(2 * clean_wire, 0.05), (
            clean_wire, delayed_wire,
        )
        # compute is real device time in BOTH runs — the injected sleep
        # must not inflate it (generous 2.5x bound for CPU timing noise)
        clean_compute = sum(
            r["compute_s"] for r in clean["ranks"].values()
        )
        delayed_compute = sum(
            r["compute_s"] for r in delayed["ranks"].values()
        )
        assert delayed_compute < 2.5 * clean_compute
        for rep in (clean, delayed):
            for row in rep["ranks"].values():
                assert abs(sum(row["phases"].values()) - 1.0) <= 0.02


class TestBenchIntegration:
    def test_leg_phases_schema_and_sum(self):
        import bench

        ph = bench._leg_phases(2.0, 1.8)
        assert set(ph) == {"compute", "wire", "idle", "overhead"}
        assert ph["compute"] == pytest.approx(0.9)
        assert sum(ph.values()) == pytest.approx(1.0, abs=1e-3)
        # degenerate leg: all overhead, still sums to 1.0
        assert sum(bench._leg_phases(0.0, 0.0).values()) == pytest.approx(
            1.0
        )
        # correction can never manufacture compute > 1
        assert bench._leg_phases(1.0, 2.0)["compute"] == 1.0

    def test_bench_ps_literal_reports_obs_phases(self):
        """THE acceptance assertion: the CPU bench emits
        ``phases: {compute, wire, idle, overhead}`` summing to
        1.0 ± 0.02, measured from real obs journals."""
        import bench

        res = bench.bench_ps_literal(cpu_smoke=True)
        assert res["phase_source"] == "obs"
        ph = res["phases"]
        assert set(ph) == {"compute", "wire", "idle", "overhead"}
        assert abs(sum(ph.values()) - 1.0) <= 0.02
        assert ph["compute"] > 0

    def test_chip_smoke_refuses_cpu_before_any_leg(self):
        """No TPU -> non-zero exit, no leg, no pass marker: the chip check
        can never be satisfied by a CPU run."""
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run(
            [sys.executable, os.path.join(repo, "chip_smoke.py")],
            cwd=repo, capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "JAX_COMPILATION_CACHE_DIR": "/nonexistent/unused"},
        )
        assert r.returncode not in (0, None), r.stdout + r.stderr
        assert "platform: cpu" in r.stdout
        assert "leg " not in r.stdout and '"ok"' not in r.stdout
        assert "no TPU" in r.stderr

    def test_compile_cache_placement(self, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR set: jax reads it itself and the
        helper sets nothing. Unset: one fixed in-checkout path."""
        import jax

        from mpit_tpu.utils import compile_cache

        updates = []
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: updates.append((k, v))
        )
        monkeypatch.setenv(compile_cache.CACHE_ENV, "/somewhere/else")
        assert compile_cache.enable_compile_cache() == "/somewhere/else"
        assert updates == []
        monkeypatch.delenv(compile_cache.CACHE_ENV)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert compile_cache.enable_compile_cache() == want  # never moves
        assert updates == [("jax_compilation_cache_dir", want)] * 2

    def test_peak_flops_keyed_by_exact_device_kind(self):
        """An unknown accelerator is an error, not a row without mfu."""
        import types

        import bench

        dev = lambda platform, kind: types.SimpleNamespace(
            platform=platform, device_kind=kind
        )
        assert bench._peak_flops_per_chip(dev("cpu", "cpu")) is None
        assert bench._peak_flops_per_chip(dev("tpu", "TPU v5 lite")) == 197e12
        with pytest.raises(KeyError, match="TPU v5 lite pod"):
            bench._peak_flops_per_chip(dev("tpu", "TPU v5 lite pod"))
