"""Training-dynamics plane tests (docs/OBSERVABILITY.md, "dynamics").

Layers under test: the versioned PARAM/push protocol (server version
counter, client basis echo, per-source staleness attribution under a
seeded chaos delay), the journal reducer (``mpit_tpu.obs.dynamics``)
and its gate/CLI exit codes, conformance rule TC204 on the checked-in
golden journals (green) and a mutated copy (red), the divergence and
staleness-runaway alert rules — fired from a real unstable-alpha run's
trajectory and quiet on the golden fixture — the Perfetto counter
tracks, the faulthandler forensics knob, bench_gate's dynamics
comparison, and the obs-off zero-cost guard in the client loop.
"""

import importlib.util
import json
import os
import shutil
import time

import jax.numpy as jnp
import numpy as np
import optax
import pytest

from mpit_tpu.data.datasets import load_mnist
from mpit_tpu.models.mlp import MLP
from mpit_tpu.obs import ObsConfig
from mpit_tpu.obs.__main__ import main as obs_main
from mpit_tpu.obs.alerts import AlertConfig, AlertEngine
from mpit_tpu.obs.core import _parse_faulthandler, arm_faulthandler, \
    config_from_env, disarm_faulthandler
from mpit_tpu.obs.dynamics import (
    aggregate_dynamics,
    check_dynamics_gate,
    diverging,
    load_gate,
)
from mpit_tpu.obs.live import M_ELASTIC_DIST, M_STALENESS, MetricsRegistry
from mpit_tpu.obs.merge import merge_to_chrome_trace, read_journal
from mpit_tpu.parallel import ps_roles
from mpit_tpu.parallel.ps_trainer import AsyncPSTrainer
from mpit_tpu.parallel.pserver import TAG_PUSH_EASGD
from mpit_tpu.transport.chaos import ChaosConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "dynamics", "good_run")
SMOKE_GATE = os.path.join(REPO, "scripts", "dynamics_smoke.json")


def _mnist():
    x, y, _, _ = load_mnist(synthetic_train=1024, synthetic_test=256)
    return x, y


def _trainer(tmp_path, **kw):
    kw.setdefault("num_clients", 2)
    kw.setdefault("obs", ObsConfig(dir=str(tmp_path)))
    return AsyncPSTrainer(
        MLP(compute_dtype=jnp.float32),
        optax.sgd(0.05, momentum=0.9),
        num_servers=1,
        algo="easgd",
        tau=4,
        transport="inproc",
        max_exchange_failures=5,
        fetch_timeout=5.0,
        fetch_retries=3,
        **kw,
    )


def _stamped(reg, t, seq, interval_s=0.1):
    snap = reg.snapshot()
    snap["seq"] = seq
    snap["interval_s"] = interval_s
    snap["t"] = t
    return snap


# ------------------------------------------------- aggregation + gate


class TestAggregateFixture:
    def test_golden_report_shape(self):
        report = aggregate_dynamics([FIXTURE])
        run = report["run"]
        assert run is not None
        assert run["clients"] == 2 and run["servers"] == 1
        assert run["versions_monotonic"] is True
        assert run["diverging"] is False
        assert run["staleness_p99"] >= 0
        assert run["elastic_dist_final"] > 0
        assert 0 < run["norm_ratio"] < 1
        for rank in (1, 2):
            row = report["clients"][rank]
            assert row["algo"] == "easgd" and row["rounds"] == 6
            assert row["elastic"]["final"] > 0
            assert not row["diverging"]
            assert len(row["trajectory"]) == 6
            st = report["staleness"][rank]
            assert st["pushes"] == 6
            assert st["p50"] <= st["p99"] <= st["max"]
        srv = report["servers"][0]
        assert srv["monotonic"] and srv["param_replies"] > 0
        assert srv["first_version"] <= srv["final_version"]

    def test_smoke_gate_passes_and_tight_gate_fails(self):
        report = aggregate_dynamics([FIXTURE])
        assert check_dynamics_gate(report, load_gate(SMOKE_GATE)) == []
        viol = check_dynamics_gate(report, {"elastic_dist_final_max": 0.0})
        assert len(viol) == 1 and "elastic_dist_final" in viol[0]

    def test_gated_metric_absent_is_a_violation(self):
        # journals with no staleness records but a staleness gate: the
        # instrumentation regressed — exactly what the gate must catch
        report = {"run": {"elastic_dist_final": 1.0}, "clients": {}}
        viol = check_dynamics_gate(report, {"staleness_p99_max": 5})
        assert viol and "absent" in viol[0]

    def test_load_gate_rejects_typos_and_types(self, tmp_path):
        p = tmp_path / "gate.json"
        p.write_text('{"stalness_p99_max": 1}')
        with pytest.raises(ValueError, match="unknown"):
            load_gate(str(p))
        p.write_text('{"staleness_p99_max": true}')
        with pytest.raises(ValueError, match="expected"):
            load_gate(str(p))
        p.write_text('{"allow_diverging": 1}')
        with pytest.raises(ValueError, match="expected"):
            load_gate(str(p))
        p.write_text('[1]')
        with pytest.raises(ValueError, match="object"):
            load_gate(str(p))

    def test_diverging_verdict(self):
        assert diverging([1.0, 2.0, 4.0, 8.0])
        assert not diverging([1.0, 2.0, 4.0])  # too short
        assert not diverging([8.0, 1.0, 2.0, 4.0, 3.9])  # not monotone
        assert not diverging([1.0, 1.1, 1.2, 1.3])  # grows < factor
        assert not diverging([0.0, 1.0, 2.0, 3.0])  # zero base


class TestDynamicsCLI:
    def test_exit_codes(self, tmp_path, capsys):
        assert obs_main(["dynamics", FIXTURE]) == 0
        assert obs_main(
            ["dynamics", FIXTURE, "--gate", SMOKE_GATE]
        ) == 0
        tight = tmp_path / "tight.json"
        tight.write_text('{"staleness_p99_max": 0}')
        assert obs_main(
            ["dynamics", FIXTURE, "--gate", str(tight)]
        ) == 1
        assert "DYNAMICS VIOLATION" in capsys.readouterr().out
        typo = tmp_path / "typo.json"
        typo.write_text('{"nope": 1}')
        assert obs_main(["dynamics", FIXTURE, "--gate", str(typo)]) == 2
        empty = tmp_path / "empty"
        empty.mkdir()
        assert obs_main(["dynamics", str(empty)]) == 2

    def test_json_output_carries_violations(self, tmp_path, capsys):
        tight = tmp_path / "tight.json"
        tight.write_text('{"norm_ratio_max": 0.0}')
        assert obs_main(
            ["dynamics", FIXTURE, "--json", "--gate", str(tight)]
        ) == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["run"]["clients"] == 2
        assert len(doc["violations"]) == 1


# ------------------------------------------------------ conformance


def _project():
    from mpit_tpu.analysis import lint

    modules = []
    pkg = os.path.join(REPO, "mpit_tpu")
    for ap, rel in lint.collect_files([pkg]):
        ctx = lint.load_module(ap, rel)
        if ctx is not None:
            modules.append(ctx)
    return lint.Project(modules=modules, config=lint.Config())


class TestTC204:
    def test_golden_run_is_monotonic(self):
        from mpit_tpu.analysis import conformance

        report = conformance.check_conformance(FIXTURE, _project())
        assert report.ok, [str(v) for v in report.violations]

    def test_version_regression_is_flagged(self, tmp_path):
        from mpit_tpu.analysis import conformance

        for name in os.listdir(FIXTURE):
            shutil.copy(os.path.join(FIXTURE, name), tmp_path / name)
        # rewind the version in the server's LAST param_version record:
        # a counter that went backwards, invisible to TC201-203
        path = tmp_path / "obs_rank0.jsonl"
        recs = [json.loads(l) for l in path.read_text().splitlines()]
        pv = [i for i, r in enumerate(recs) if r.get("ev") == "param_version"]
        assert len(pv) >= 2
        recs[pv[-1]]["version"] = recs[pv[0]]["version"] - 1 \
            if recs[pv[0]]["version"] > 0 else -1
        path.write_text("".join(json.dumps(r) + "\n" for r in recs))

        report = conformance.check_conformance(str(tmp_path), _project())
        rules = sorted({v.rule for v in report.violations})
        assert rules == ["TC204"], [str(v) for v in report.violations]
        # the post-mortem reducer reaches the same verdict
        agg = aggregate_dynamics([str(tmp_path)])
        assert agg["servers"][0]["monotonic"] is False
        assert agg["run"]["versions_monotonic"] is False


# ------------------------------------------- staleness attribution


class TestStalenessAttribution:
    def test_chaos_delayed_client_owns_the_staleness(self, tmp_path):
        """3-rank run where client rank 1's EASGD *pushes* (tag 2 only
        — fetches stay fast, so its basis stays old) go through a
        400 ms chaos delay, probability 1 so the seed is irrelevant.
        Each delayed push lands after the undelayed client has moved
        the center — the per-source staleness accounting must
        attribute the gap to rank 1, in the journals AND the stats.
        Staleness here comes from message *ordering* (old basis held
        across other ranks' applied pushes), not from racing the
        round time, so the assertion is load-tolerant."""
        x, y = _mnist()
        trainer = _trainer(
            tmp_path,
            chaos=ChaosConfig(
                delay=1.0,
                delay_s=0.4,
                edges=((1, 0),),
                tags=(TAG_PUSH_EASGD,),
            ),
        )
        _, stats = trainer.train(x, y, steps=24, batch_size=32, seed=0)

        by_src = stats["staleness_by_src"][0]
        assert set(by_src) == {1, 2}
        assert by_src[1]["pushes"] == by_src[2]["pushes"] == 6
        # the delayed client's window spans several center updates
        assert by_src[1]["max"] >= 2
        assert by_src[1]["sum"] > by_src[2]["sum"]

        report = aggregate_dynamics([str(tmp_path)])
        st = report["staleness"]
        assert st[1]["pushes"] == 6 and st[2]["pushes"] == 6
        assert st[1]["mean"] > st[2]["mean"]
        assert st[1]["max"] == by_src[1]["max"]
        assert report["servers"][0]["monotonic"]
        # versions: one bump per applied push
        assert stats["server_versions"] == [12]

    def test_clean_run_carries_dynamics_in_stats(self, tmp_path):
        x, y = _mnist()
        trainer = _trainer(tmp_path)
        _, stats = trainer.train(x, y, steps=8, batch_size=32, seed=0)
        assert stats["server_versions"] == [4]
        by_src = stats["staleness_by_src"][0]
        assert sum(s["pushes"] for s in by_src.values()) == 4


# ------------------------------------------------------- divergence


class TestDivergence:
    def test_unstable_alpha_fires_alert_and_verdict(self, tmp_path):
        """alpha=1.9 makes the elastic map amplify the worker-center
        gap ~2.8x per exchange — elastic distance grows strictly. The
        reducer must say diverging, the default gate must flag it, and
        replaying the trajectory through the AlertEngine as live
        snapshots must fire `divergence` exactly once (then dedup)."""
        x, y = _mnist()
        trainer = _trainer(tmp_path, num_clients=1, alpha=1.9)
        trainer.train(x, y, steps=24, batch_size=32, seed=0)

        report = aggregate_dynamics([str(tmp_path)])
        row = report["clients"][1]
        assert row["diverging"] and report["run"]["diverging"]
        traj = row["trajectory"]
        assert traj[-1] / traj[0] > 10  # the ~2.8x/exchange amplifier
        viol = check_dynamics_gate(report, load_gate(SMOKE_GATE))
        assert any("diverging" in v for v in viol)
        assert check_dynamics_gate(
            report, {"allow_diverging": True}
        ) == []

        engine = AlertEngine(None, AlertConfig())
        fired = []
        for i, v in enumerate(traj):
            reg = MetricsRegistry(1)
            reg.set_gauge(M_ELASTIC_DIST, v)
            fired += engine.evaluate(
                {1: _stamped(reg, t=100.0 + i, seq=i + 1)}
            )
        kinds = [(f["kind"], f["rank"]) for f in fired]
        assert ("divergence", 1) in kinds
        assert kinds.count(("divergence", 1)) == 1  # dedup held
        div = next(f for f in fired if f["kind"] == "divergence")
        assert div["detail"]["growth"] > 2.0

    def test_golden_trajectories_stay_quiet(self):
        """The checked-in healthy run replayed through the engine: no
        divergence, no staleness_runaway — the default thresholds must
        not cry wolf on an equilibrating EASGD run."""
        report = aggregate_dynamics([FIXTURE])
        engine = AlertEngine(None, AlertConfig())
        fired = []
        for rank, row in report["clients"].items():
            for i, v in enumerate(row["trajectory"]):
                reg = MetricsRegistry(rank)
                reg.set_gauge(M_ELASTIC_DIST, v)
                fired += engine.evaluate(
                    {rank: _stamped(reg, t=100.0 + i, seq=i + 1)}
                )
        assert fired == []


class TestStalenessRunaway:
    def test_spike_over_own_baseline_fires_once(self):
        engine = AlertEngine(None, AlertConfig())
        fired = []
        for i, s in enumerate((1.0, 1.0, 1.0, 8.0)):
            reg = MetricsRegistry(0)
            reg.observe(M_STALENESS, s)
            fired += engine.evaluate(
                {0: _stamped(reg, t=100.0 + i, seq=i + 1)}
            )
        kinds = [(f["kind"], f["rank"]) for f in fired]
        assert kinds == [("staleness_runaway", 0)]
        detail = fired[0]["detail"]
        assert detail["staleness_p99"] > 3 * detail["baseline"]
        # unchanged snapshot seq: histories must not advance, the
        # active alert must stay suppressed
        reg = MetricsRegistry(0)
        reg.observe(M_STALENESS, 8.0)
        snap = _stamped(reg, t=104.0, seq=4)
        assert engine.evaluate({0: snap}) == []
        assert engine.evaluate({0: snap}) == []

    def test_steady_staleness_is_quiet(self):
        engine = AlertEngine(None, AlertConfig())
        fired = []
        for i in range(6):
            reg = MetricsRegistry(0)
            reg.observe(M_STALENESS, 2.0)
            fired += engine.evaluate(
                {0: _stamped(reg, t=100.0 + i, seq=i + 1)}
            )
        assert fired == []


# ------------------------------------------------- counter tracks


class TestMergeCounters:
    def test_perfetto_counter_tracks_from_golden(self):
        trace = merge_to_chrome_trace([FIXTURE])
        counters = [
            e for e in trace["traceEvents"] if e.get("ph") == "C"
        ]
        names = {e["name"] for e in counters}
        assert "elastic_dist" in names
        assert {"staleness src 1", "staleness src 2"} <= names
        for e in counters:
            assert "value" in e["args"] and e["tid"] == 0


# --------------------------------------------------- faulthandler


class TestFaulthandler:
    def test_knob_parse(self):
        assert _parse_faulthandler(None) == 0.0
        assert _parse_faulthandler("0") == 0.0
        assert _parse_faulthandler("false") == 0.0
        assert _parse_faulthandler("1") == 300.0
        assert _parse_faulthandler("true") == 300.0
        assert _parse_faulthandler("2.5") == 2.5
        with pytest.raises(ValueError):
            _parse_faulthandler("soon")
        cfg = config_from_env(
            {"MPIT_OBS_DIR": "/x", "MPIT_OBS_FAULTHANDLER": "1"}
        )
        assert cfg.faulthandler == 300.0
        with pytest.raises(ValueError):
            ObsConfig(faulthandler=-1.0)

    def test_disabled_config_never_arms(self, tmp_path):
        assert arm_faulthandler(None, "t") is None
        assert arm_faulthandler(
            ObsConfig(dir=str(tmp_path)), "t"
        ) is None
        assert not os.listdir(tmp_path)

    def test_armed_dump_lands_in_stacks_file(self, tmp_path):
        """A sub-interval hang leaves all-thread stacks on disk — the
        forensics a wedged run is killed without. Process-global: the
        first armed file serves every later arm in this process."""
        cfg = ObsConfig(dir=str(tmp_path), faulthandler=0.05)
        path = arm_faulthandler(cfg, "t")
        try:
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if path and os.path.getsize(path) > 0:
                    break
                time.sleep(0.05)
        finally:
            disarm_faulthandler()
        text = open(path).read()
        assert "Thread" in text and "test_dynamics" in text


# ---------------------------------------------- bench_gate dynamics


def _bench_gate():
    spec = importlib.util.spec_from_file_location(
        "bench_gate", os.path.join(REPO, "scripts", "bench_gate.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_round(d, n, parsed):
    with open(os.path.join(str(d), f"BENCH_r{n:02d}.json"), "w") as f:
        json.dump({"n": n, "cmd": "bench", "rc": 0, "tail": "",
                   "parsed": parsed}, f)


class TestBenchGateDynamics:
    BASE = {
        "metric": "ps_mnist_throughput", "value": 100.0,
        "platform": "cpu",
        "dynamics": {"staleness_p99": 2, "elastic_dist_final": 1.0,
                     "norm_ratio": 0.02},
    }

    def test_quality_regressions_flagged(self, tmp_path, capsys):
        bg = _bench_gate()
        _bench_round(tmp_path, 1, self.BASE)
        _bench_round(tmp_path, 2, {
            **self.BASE,
            "dynamics": {"staleness_p99": 4, "elastic_dist_final": 2.0,
                         "norm_ratio": 0.01},
        })
        assert bg.main([str(tmp_path)]) == 0  # warn-only default
        out = capsys.readouterr().out
        assert "dynamics.staleness_p99 2 -> 4" in out
        assert "dynamics.elastic_dist_final" in out
        assert "dynamics.norm_ratio" in out and "drift" in out
        assert bg.main(["--strict", str(tmp_path)]) == 1

    def test_zero_baseline_appearance_warns(self, tmp_path, capsys):
        bg = _bench_gate()
        _bench_round(tmp_path, 1, {
            **self.BASE, "dynamics": {"staleness_p99": 0},
        })
        _bench_round(tmp_path, 2, {
            **self.BASE, "dynamics": {"staleness_p99": 3},
        })
        bg.main([str(tmp_path)])
        assert "zero baseline" in capsys.readouterr().out

    def test_within_threshold_and_platform_change_quiet(
        self, tmp_path, capsys
    ):
        bg = _bench_gate()
        _bench_round(tmp_path, 1, self.BASE)
        _bench_round(tmp_path, 2, {
            **self.BASE,
            "dynamics": {"staleness_p99": 2, "elastic_dist_final": 1.05,
                         "norm_ratio": 0.021},
        })
        assert bg.main(["--strict", str(tmp_path)]) == 0
        _bench_round(tmp_path, 3, {
            **self.BASE, "platform": "tpu",
            "dynamics": {"staleness_p99": 50},
        })
        assert bg.main(["--strict", str(tmp_path)]) == 0
        assert "not comparable" in capsys.readouterr().out


# ------------------------------------------------ obs-off zero cost


class TestObsOffGuard:
    def test_record_dynamics_never_called_without_obs(
        self, tmp_path, monkeypatch
    ):
        """The dynamics norms are guarded by the transport's obs_tracer:
        with obs off the helper must never run (no extra O(n) norms on
        the exchange path), while the protocol's version ints still
        flow (they are O(1) and always on)."""

        def boom(*a, **k):  # pragma: no cover - the assertion IS no call
            raise AssertionError("_record_dynamics ran with obs off")

        monkeypatch.setattr(ps_roles, "_record_dynamics", boom)
        x, y = _mnist()
        trainer = _trainer(tmp_path, obs=None)
        _, stats = trainer.train(x, y, steps=8, batch_size=32, seed=0)
        assert "telemetry" not in stats
        assert stats["server_versions"] == [4]
