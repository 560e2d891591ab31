"""Load harness + SLO gates (docs/SERVING.md).

Layers under test: seeded workload generation (replay contract),
the open-loop harness against real Server/RNNServer instances with
request-lifecycle journaling, the constant-memory SLO aggregation and
its gate files, the `obs slo` CLI exit-code contract (0/1/2), seeded
serving chaos (the p99-moves-p50-doesn't pin and kill -> unfinished
accounting), the obs-off null path's cost bound, the Perfetto merge's
request tracks, and scripts/bench_gate.py's trajectory warnings.
"""

import importlib.util
import json
import os
import time

import pytest

from mpit_tpu.loadgen import (
    LoadHarness,
    LoadSpec,
    Request,
    ServeChaos,
    aggregate_paths,
    evaluate_gate,
    make_workload,
    validate_gate,
)
from mpit_tpu.loadgen.slo import _Hist
from mpit_tpu.obs.__main__ import main as obs_main

V, T = 17, 64


def _journals(d):
    import glob

    return sorted(glob.glob(os.path.join(str(d), "obs_rank*.jsonl")))


# ---------------------------------------------------------------- workload


class TestWorkload:
    def test_same_seed_token_identical_schedule(self):
        spec = LoadSpec(requests=40, rate=50.0, seed=7, cancel_prob=0.3)
        a = make_workload(spec, 101, max_len=64)
        b = make_workload(spec, 101, max_len=64)
        assert a == b
        c = make_workload(
            LoadSpec(requests=40, rate=50.0, seed=8, cancel_prob=0.3),
            101, max_len=64,
        )
        assert a != c

    def test_arrivals_strictly_increase(self):
        work = make_workload(LoadSpec(requests=30, seed=1), 101)
        times = [r.arrival_s for r in work]
        assert times == sorted(times) and times[0] > 0

    def test_max_len_clamp_and_token_range(self):
        spec = LoadSpec(
            requests=50, seed=2,
            prompt_buckets=((1, 60, 1.0),),
            output_buckets=((1, 60, 1.0),),
        )
        for r in make_workload(spec, V, max_len=16):
            assert 1 <= len(r.prompt)
            assert 1 <= r.max_new
            assert len(r.prompt) + r.max_new <= 16
            assert all(1 <= t < V for t in r.prompt)

    def test_cancel_prob_extremes(self):
        none = make_workload(
            LoadSpec(requests=20, seed=3, cancel_prob=0.0), 101
        )
        assert all(r.cancel_after_s is None for r in none)
        every = make_workload(
            LoadSpec(requests=20, seed=3, cancel_prob=1.0), 101
        )
        assert all(r.cancel_after_s is not None for r in every)
        # the cancel knob must not perturb the rest of the schedule
        # (unconditional draws keep the stream aligned)
        assert [r.prompt for r in none] == [r.prompt for r in every]

    def test_slo_scales_with_budget(self):
        spec = LoadSpec(requests=10, seed=4, slo_base_ms=100.0,
                        slo_per_token_ms=10.0)
        for r in make_workload(spec, 101):
            assert r.slo_ms == 100.0 + 10.0 * r.max_new

    def test_validation(self):
        with pytest.raises(ValueError, match="requests"):
            LoadSpec(requests=0)
        with pytest.raises(ValueError, match="rate"):
            LoadSpec(rate=0)
        with pytest.raises(ValueError, match="prompt_buckets"):
            LoadSpec(prompt_buckets=())
        with pytest.raises(ValueError, match="lo < hi"):
            LoadSpec(output_buckets=((5, 5, 1.0),))
        with pytest.raises(ValueError, match="vocab_size"):
            make_workload(LoadSpec(), 1)


class TestServeChaos:
    def test_draws_are_pure_functions_of_seed_and_boundary(self):
        a = ServeChaos(seed=9, delay_p=0.5, delay_s=0.1)
        b = ServeChaos(seed=9, delay_p=0.5, delay_s=0.1)
        draws = [a.draw(i) for i in range(50)]
        assert draws == [b.draw(i) for i in range(50)]
        assert any(d is not None for d in draws)
        assert any(d is None for d in draws)
        for d in draws:
            if d is not None:
                kind, s = d
                assert kind == "delay"
                assert 0.05 <= s <= 0.15  # +-50% jitter around delay_s

    def test_kill_after(self):
        c = ServeChaos(seed=0, kill_after=3)
        assert c.draw(2) is None
        assert c.draw(3) == ("kill", 0.0)
        assert c.draw(7) == ("kill", 0.0)

    def test_validation(self):
        with pytest.raises(ValueError, match="delay_p"):
            ServeChaos(delay_p=1.5)
        with pytest.raises(ValueError, match="kill_after"):
            ServeChaos(kill_after=-1)


# ------------------------------------------------------------- aggregation


class TestHist:
    def test_percentiles_within_geometric_quantization(self):
        h = _Hist()
        for _ in range(90):
            h.add(0.001)
        for _ in range(10):
            h.add(1.0)
        s = h.summary()
        assert s["count"] == 100
        assert s["p50_ms"] <= 1.0 * 1.1  # ~1ms, one bucket of slack
        assert 900.0 <= s["p99_ms"] <= 1100.0
        assert s["mean_ms"] == pytest.approx(100.9, rel=0.01)

    def test_empty(self):
        assert _Hist().summary() == {"count": 0}
        assert _Hist().percentile_ms(0.99) is None


def _write_lifecycle_journal(d, rows):
    path = os.path.join(str(d), "obs_rank0.jsonl")
    with open(path, "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return path


def _three_request_rows():
    """2 finishes (one in SLO, one out) + 1 cancel, with segment time."""
    return [
        {"ev": "req_enqueue", "rid": 0, "t": 0.0, "p_len": 4,
         "max_new": 5, "slo_ms": 500.0},
        {"ev": "req_enqueue", "rid": 1, "t": 0.01, "p_len": 2,
         "max_new": 3, "slo_ms": 100.0},
        {"ev": "req_enqueue", "rid": 2, "t": 0.02, "p_len": 2,
         "max_new": 3},
        {"ev": "segment", "t": 0.2, "seg": 0, "occupied": 2,
         "nslots": 2, "waiting": 1, "dur": 0.18},
        {"ev": "req_first_token", "rid": 0, "t": 0.1},
        {"ev": "req_first_token", "rid": 1, "t": 0.12},
        {"ev": "req_finish", "rid": 0, "t": 0.3, "gen": 5,
         "reason": "budget"},
        {"ev": "req_finish", "rid": 1, "t": 0.4, "gen": 3,
         "reason": "eos"},
        {"ev": "req_cancel", "rid": 2, "t": 0.41, "where": "queued"},
    ]


class TestAggregator:
    def test_lifecycle_reduction(self, tmp_path):
        path = _write_lifecycle_journal(tmp_path, _three_request_rows())
        rep = aggregate_paths([path])
        assert rep["requests"] == {
            "submitted": 3, "finished": 2, "cancelled": 1,
            "unfinished": 0,
        }
        assert rep["finish_reasons"] == {"budget": 1, "eos": 1}
        assert rep["ttft"]["count"] == 2
        # rid 0: e2e 300ms <= 500 SLO; rid 1: 390ms > 100 -> missed;
        # cancelled rid 2 leaves the denominator
        assert rep["goodput"] == 0.5
        assert rep["queue_depth"]["max"] == 1
        assert rep["occupancy"] == 1.0  # 2 occupied of 2 slots
        assert rep["tokens"] == 8
        assert rep["dropped_records"] == 0

    def test_no_slo_meets_vacuously_and_default_retrofits(self, tmp_path):
        rows = [
            {"ev": "req_enqueue", "rid": 0, "t": 0.0},
            {"ev": "req_first_token", "rid": 0, "t": 0.1},
            {"ev": "req_finish", "rid": 0, "t": 0.5, "gen": 2,
             "reason": "eos"},
        ]
        path = _write_lifecycle_journal(tmp_path, rows)
        assert aggregate_paths([path])["goodput"] == 1.0
        assert aggregate_paths(
            [path], default_slo_ms=100.0
        )["goodput"] == 0.0

    def test_unfinished_counts_against_goodput(self, tmp_path):
        rows = [
            {"ev": "req_enqueue", "rid": 0, "t": 0.0, "slo_ms": 500.0},
            {"ev": "req_enqueue", "rid": 1, "t": 0.0, "slo_ms": 500.0},
            {"ev": "req_first_token", "rid": 0, "t": 0.05},
            {"ev": "req_finish", "rid": 0, "t": 0.1, "gen": 2,
             "reason": "eos"},
            {"ev": "serve_fault", "t": 0.2, "kind": "kill",
             "boundary": 3},
        ]
        path = _write_lifecycle_journal(tmp_path, rows)
        rep = aggregate_paths([path])
        assert rep["requests"]["unfinished"] == 1
        assert rep["goodput"] == 0.5
        assert rep["faults"] == {"kill": 1}

    def test_torn_tail_skipped(self, tmp_path):
        path = _write_lifecycle_journal(tmp_path, _three_request_rows())
        with open(path, "a") as f:
            f.write('{"ev": "req_enq')  # a crashed writer's last line
        assert aggregate_paths([path])["requests"]["submitted"] == 3


class TestGateFiles:
    def test_unknown_key_and_bad_value_rejected(self):
        with pytest.raises(ValueError, match="unknown gate key"):
            validate_gate({"ttft_p98_ms": 5})
        with pytest.raises(ValueError, match="unknown gate key"):
            validate_gate({"goodput": 0.9})
        with pytest.raises(ValueError, match="must be a number"):
            validate_gate({"ttft_p99_ms": True})
        validate_gate({"ttft_p99_ms": 250, "goodput_min": 0.9,
                       "min_finished": 1, "max_unfinished": 0,
                       "max_dropped_records": 0})

    def test_evaluate_directions(self, tmp_path):
        path = _write_lifecycle_journal(tmp_path, _three_request_rows())
        rep = aggregate_paths([path])
        assert evaluate_gate(rep, {"e2e_p99_ms": 10_000}) == []
        assert evaluate_gate(rep, {"e2e_p99_ms": 1}) != []
        assert evaluate_gate(rep, {"goodput_min": 0.4}) == []
        assert evaluate_gate(rep, {"goodput_min": 0.9}) != []
        assert evaluate_gate(rep, {"min_finished": 3}) != []
        assert evaluate_gate(rep, {"max_unfinished": 0}) == []

    def test_gated_percentile_without_samples_violates(self):
        rep = {"requests": {"submitted": 1, "finished": 0,
                            "cancelled": 0, "unfinished": 1},
               "ttft": {"count": 0}, "tpot": {"count": 0},
               "e2e": {"count": 0}, "goodput": None,
               "dropped_records": 0}
        out = evaluate_gate(rep, {"ttft_p99_ms": 250})
        assert out and "no samples" in out[0]
        out = evaluate_gate(rep, {"goodput_min": 0.5})
        assert out and "no eligible" in out[0]


class TestSloCli:
    """The exit-code contract: 0 clean, 1 gate violation, 2 usage/empty."""

    def test_report_and_pass_gate(self, tmp_path, capsys):
        _write_lifecycle_journal(tmp_path, _three_request_rows())
        assert obs_main(["slo", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "requests: 3 submitted" in out and "goodput" in out
        gate = tmp_path / "gate.json"
        gate.write_text('{"e2e_p99_ms": 10000, "min_finished": 2}')
        assert obs_main(["slo", str(tmp_path), "--gate",
                         str(gate)]) == 0

    def test_violation_exits_1(self, tmp_path, capsys):
        _write_lifecycle_journal(tmp_path, _three_request_rows())
        gate = tmp_path / "gate.json"
        gate.write_text('{"ttft_p99_ms": 0.001}')
        assert obs_main(["slo", str(tmp_path), "--gate",
                         str(gate)]) == 1
        assert "SLO VIOLATION" in capsys.readouterr().out

    def test_empty_and_bad_gate_exit_2(self, tmp_path, capsys):
        assert obs_main(["slo", str(tmp_path)]) == 2  # no journals
        sub = tmp_path / "norequests"
        sub.mkdir()
        _write_lifecycle_journal(sub, [{"ev": "send", "t": 0.0, "n": 0}])
        assert obs_main(["slo", str(sub)]) == 2  # journals, no requests
        _write_lifecycle_journal(tmp_path, _three_request_rows())
        gate = tmp_path / "gate.json"
        gate.write_text('{"nope_p99_ms": 5}')
        assert obs_main(["slo", str(tmp_path), "--gate",
                         str(gate)]) == 2
        capsys.readouterr()

    def test_json_output_carries_violations(self, tmp_path, capsys):
        _write_lifecycle_journal(tmp_path, _three_request_rows())
        gate = tmp_path / "gate.json"
        gate.write_text('{"goodput_min": 0.9}')
        assert obs_main(["slo", str(tmp_path), "--gate", str(gate),
                         "--json"]) == 1
        payload = json.loads(capsys.readouterr().out)
        assert payload["goodput"] == 0.5
        assert payload["violations"]


# ------------------------------------------------- harness against servers


def _model_params():
    import jax
    import jax.numpy as jnp

    from mpit_tpu.models.transformer import TransformerLM

    model = TransformerLM(
        vocab_size=V, num_layers=2, d_model=32, num_heads=4, max_len=T,
        compute_dtype=jnp.float32,
    )
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def _immediate_requests(n, seed=0, max_new=(3, 8)):
    """All-at-once arrivals: the harness submits every request before
    the first step, which makes boundary counts deterministic (the
    chaos comparison tests need identical scheduling across runs)."""
    import random

    rng = random.Random(seed)
    lo, hi = max_new
    return [
        Request(
            arrival_s=0.0,
            prompt=tuple(rng.randrange(1, V) for _ in range(
                rng.randrange(1, 7)
            )),
            max_new=rng.randrange(lo, hi),
            slo_ms=60_000.0,
        )
        for _ in range(n)
    ]


def _server(model, params, tmp_path=None, **kw):
    from mpit_tpu.models import Server
    from mpit_tpu.obs.core import ObsConfig

    obs = ObsConfig(dir=str(tmp_path)) if tmp_path is not None else None
    return Server(model, params, max_batch=2, segment=4, obs=obs, **kw)


class TestHarness:
    def test_load_run_journals_full_lifecycle(self, topo8, tmp_path):
        model, params = _model_params()
        srv = _server(model, params, tmp_path)
        reqs = _immediate_requests(8)
        rep = LoadHarness(srv, reqs).run()
        assert rep.submitted == 8 and not rep.killed
        assert len(rep.results) == 8  # every request completed
        report = aggregate_paths(_journals(tmp_path))
        assert report["requests"] == {
            "submitted": 8, "finished": 8, "cancelled": 0,
            "unfinished": 0,
        }
        # every finished request produced a TTFT and an e2e sample
        assert report["ttft"]["count"] == 8
        assert report["e2e"]["count"] == 8
        assert report["goodput"] == 1.0  # 60s SLOs on a warm smoke run
        assert report["segments"] == rep.boundaries
        assert report["tokens"] == sum(
            len(rep.results[r.rid]) - len(r.prompt) for r in reqs
        )
        assert report["occupancy"] is not None
        # ordering sanity on one rid: enqueue < first_token < finish
        recs = [json.loads(l) for l in open(_journals(tmp_path)[0])]
        by_rid = [r for r in recs if r.get("rid") == reqs[0].rid]
        evs = [r["ev"] for r in by_rid]
        assert evs.index("req_enqueue") < evs.index("req_first_token")
        assert evs.index("req_first_token") <= evs.index("req_finish")

    def test_results_match_obs_off_run(self, topo8, tmp_path):
        """Journaling must not change a single token."""
        model, params = _model_params()
        reqs = _immediate_requests(6, seed=5)
        on = LoadHarness(
            _server(model, params, tmp_path), _immediate_requests(6, seed=5)
        ).run()
        off = LoadHarness(_server(model, params), reqs).run()
        assert [on.results[r.rid] for r in on.requests.values()] == [
            off.results[r.rid] for r in off.requests.values()
        ]

    def test_cancellations_journaled_and_leave_denominator(
        self, topo8, tmp_path
    ):
        model, params = _model_params()
        srv = _server(model, params, tmp_path)
        reqs = _immediate_requests(8, seed=1, max_new=(20, 30))
        for r in reqs[:3]:
            r.cancel_after_s = 0.0  # due immediately after submission
        rep = LoadHarness(srv, reqs).run()
        assert rep.cancelled == 3
        report = aggregate_paths(_journals(tmp_path))
        assert report["requests"]["cancelled"] == 3
        assert report["requests"]["finished"] == 5
        assert report["goodput"] == 1.0  # cancelled leave the denominator
        wheres = [
            json.loads(l).get("where")
            for l in open(_journals(tmp_path)[0])
            if '"req_cancel"' in l
        ]
        assert len(wheres) == 3 and all(
            w in ("queued", "slot") for w in wheres
        )

    def test_kill_leaves_unfinished_and_penalizes_goodput(
        self, topo8, tmp_path
    ):
        model, params = _model_params()
        srv = _server(model, params, tmp_path)
        rep = LoadHarness(
            srv, _immediate_requests(8, max_new=(10, 20)),
            chaos=ServeChaos(seed=0, kill_after=1),
        ).run()
        assert rep.killed and rep.boundaries == 1
        report = aggregate_paths(_journals(tmp_path))
        assert report["requests"]["unfinished"] > 0
        assert report["faults"] == {"kill": 1}
        assert report["goodput"] < 1.0
        assert evaluate_gate(report, {"max_unfinished": 0}) != []

    def test_injected_delay_moves_p99_not_p50(self, topo8, tmp_path):
        """THE chaos-closure pin: a rare seeded stall late in the run
        stretches the tail (the requests spanning it) while the median
        request never sees it."""
        model, params = _model_params()
        delay_s = 0.5
        # warm every bucket shape first: a mid-run XLA compile is a
        # stall too, and it must not masquerade as (or mask) the
        # injected one in either run's tail
        LoadHarness(
            _server(model, params),
            _immediate_requests(24, seed=2, max_new=(3, 6)),
        ).run()
        clean = LoadHarness(
            _server(model, params, tmp_path / "clean"),
            _immediate_requests(24, seed=2, max_new=(3, 6)),
        ).run()
        nb = clean.boundaries
        assert nb >= 8  # enough boundaries for "late" to mean something
        # find a seed whose ONE delay lands in the last quarter of the
        # boundary schedule — deterministic, and the draw is a pure
        # function of (seed, boundary) so the search result replays
        seed = next(
            s for s in range(500)
            if (hits := [
                b for b in range(nb)
                if ServeChaos(seed=s, delay_p=0.04,
                              delay_s=delay_s).draw(b) is not None
            ]) and len(hits) == 1 and hits[0] >= (3 * nb) // 4
        )
        chaotic = LoadHarness(
            _server(model, params, tmp_path / "chaos"),
            _immediate_requests(24, seed=2, max_new=(3, 6)),
            chaos=ServeChaos(seed=seed, delay_p=0.04, delay_s=delay_s),
        ).run()
        assert chaotic.boundaries == nb  # identical scheduling
        a = aggregate_paths(_journals(tmp_path / "clean"))
        b = aggregate_paths(_journals(tmp_path / "chaos"))
        assert b["faults"] == {"delay": 1}
        # jitter bounds the injected stall to [0.5, 1.5] * delay_s
        p99_shift = b["e2e"]["p99_ms"] - a["e2e"]["p99_ms"]
        p50_shift = abs(b["e2e"]["p50_ms"] - a["e2e"]["p50_ms"])
        assert p99_shift > 0.3 * delay_s * 1e3, (p99_shift, p50_shift)
        assert p50_shift < 0.25 * delay_s * 1e3, (p99_shift, p50_shift)

    def test_rnn_server_under_load(self, topo8, tmp_path):
        import jax
        import jax.numpy as jnp

        from mpit_tpu.models import RNNServer
        from mpit_tpu.models.lstm import LSTMLM
        from mpit_tpu.obs.core import ObsConfig

        model = LSTMLM(
            vocab_size=V, embed_dim=12, hidden=16, num_layers=2,
            compute_dtype=jnp.float32,
        )
        params = model.init(
            jax.random.key(3), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        srv = RNNServer(
            model, params, max_batch=2, segment=3,
            obs=ObsConfig(dir=str(tmp_path)),
        )
        # no horizon: max_len=None exercises the RNN budget path
        work = make_workload(
            LoadSpec(requests=6, rate=1e4, seed=6), V, max_len=None
        )
        rep = LoadHarness(srv, work).run()
        assert len(rep.results) == 6
        report = aggregate_paths(_journals(tmp_path))
        assert report["requests"]["finished"] == 6
        assert report["ttft"]["count"] == 6
        assert report["tpot"]["count"] >= 1

    def test_obs_off_is_the_null_path(self, topo8):
        """The 2% pin, analytically: servers default to _obs None, and
        (hook sites per drain) x (measured cost of one is-None check)
        must stay under 2% of the drain's wall-clock."""
        model, params = _model_params()
        srv = _server(model, params)
        assert srv._obs is None
        n = 50_000
        t0 = time.perf_counter()
        for _ in range(n):
            if srv._obs is not None:  # the exact guard every hook uses
                raise AssertionError
        per_check = (time.perf_counter() - t0) / n
        reqs = _immediate_requests(6, seed=4)
        t0 = time.perf_counter()
        rep = LoadHarness(srv, reqs).run()
        wall = time.perf_counter() - t0
        # generous over-count of guard sites: submit + admission +
        # per-segment + per-retirement, x10 slack
        hooks = 10 * (rep.boundaries + len(reqs))
        assert hooks * per_check < 0.02 * wall, (
            f"{hooks} checks x {per_check*1e9:.0f}ns vs {wall:.3f}s drain"
        )

    def test_merge_renders_request_tracks(self, topo8, tmp_path):
        from mpit_tpu.obs import merge_to_chrome_trace

        model, params = _model_params()
        srv = _server(model, params, tmp_path)
        LoadHarness(
            srv, _immediate_requests(5, seed=8),
            chaos=ServeChaos(seed=1, delay_p=1.0, delay_s=0.001),
        ).run()
        trace = merge_to_chrome_trace(_journals(tmp_path))
        evs = trace["traceEvents"]
        serve = [e for e in evs if e.get("cat") == "serve"]
        assert any(e["name"].startswith("prefill") for e in serve)
        assert any(e["name"] == "segment" for e in serve)
        assert all(e["ph"] == "X" and e["dur"] >= 1.0 for e in serve)
        # every request opens and closes one async span on tid 2
        opens = {e["id"] for e in evs
                 if e.get("cat") == "request" and e["ph"] == "b"}
        closes = {e["id"] for e in evs
                  if e.get("cat") == "request" and e["ph"] == "e"}
        assert len(opens) == 5 and opens == closes
        faults = [e for e in evs if e.get("cat") == "chaos"]
        assert faults and all(
            e["name"] == "fault delay" for e in faults
        )
        # timestamps non-negative and sorted (the merger's contract)
        ts = [e.get("ts", 0.0) for e in evs]
        assert min(ts) >= 0.0 and ts == sorted(ts)


# ------------------------------------------------------------- bench_gate


def _bench_gate():
    spec = importlib.util.spec_from_file_location(
        "bench_gate",
        os.path.join(os.path.dirname(__file__), os.pardir, "scripts",
                     "bench_gate.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_round(d, n, parsed):
    with open(os.path.join(str(d), f"BENCH_r{n:02d}.json"), "w") as f:
        json.dump({"n": n, "cmd": "bench", "rc": 0, "tail": "",
                   "parsed": parsed}, f)


class TestBenchGate:
    def test_throughput_drop_and_slo_rise_flagged(self, tmp_path, capsys):
        bg = _bench_gate()
        base = {"metric": "serve_load_tokens_per_sec", "value": 100.0,
                "platform": "tpu", "ttft_p99_ms": 50.0, "goodput": 1.0}
        _bench_round(tmp_path, 1, base)
        _bench_round(tmp_path, 2, {**base, "value": 80.0,
                                   "ttft_p99_ms": 60.0, "goodput": 0.8})
        assert bg.main([str(tmp_path)]) == 0  # warn-only by default
        out = capsys.readouterr().out
        assert out.count("WARNING") == 3  # value, ttft_p99_ms, goodput
        assert bg.main(["--strict", str(tmp_path)]) == 1
        capsys.readouterr()

    def test_within_threshold_ok(self, tmp_path, capsys):
        bg = _bench_gate()
        base = {"metric": "m", "value": 100.0, "platform": "tpu",
                "e2e_p99_ms": 100.0}
        _bench_round(tmp_path, 1, base)
        _bench_round(tmp_path, 2, {**base, "value": 95.0,
                                   "e2e_p99_ms": 105.0})
        assert bg.main(["--strict", str(tmp_path)]) == 0
        assert "OK" in capsys.readouterr().out

    def test_platform_change_not_comparable(self, tmp_path, capsys):
        bg = _bench_gate()
        _bench_round(tmp_path, 1, {"metric": "m", "value": 1000.0,
                                   "platform": "tpu"})
        _bench_round(tmp_path, 2, {"metric": "m", "value": 1.0,
                                   "platform": "cpu"})
        assert bg.main(["--strict", str(tmp_path)]) == 0
        assert "not comparable" in capsys.readouterr().out

    def test_exchange_mode_change_not_comparable(self, tmp_path, capsys):
        """An int8 round must never be scored against a raw round (or a
        different bucket/overlap/codec config) — that's an A/B pair, not
        a trajectory; the trend series must also stop at the boundary."""
        bg = _bench_gate()
        base = {"metric": "sync_dp_exchange_throughput",
                "platform": "cpu", "dp_bucket_bytes": 65536,
                "dp_overlap": False}
        _bench_round(tmp_path, 1, {**base, "value": 200.0,
                                   "dp_quant": "off"})
        _bench_round(tmp_path, 2, {**base, "value": 100.0,
                                   "dp_quant": "int8"})
        assert bg.main(["--strict", str(tmp_path)]) == 0
        assert "not comparable" in capsys.readouterr().out
        # wire-codec knobs gate the PS legs the same way
        _bench_round(tmp_path, 3, {"metric": "m", "value": 100.0,
                                   "platform": "cpu",
                                   "wire_format": "pickle"})
        _bench_round(tmp_path, 4, {"metric": "m", "value": 50.0,
                                   "platform": "cpu",
                                   "wire_format": "framed"})
        assert bg.main(["--strict", str(tmp_path)]) == 0
        assert "not comparable" in capsys.readouterr().out
        # same mode on both sides still flags a real drop
        _bench_round(tmp_path, 5, {**base, "value": 100.0,
                                   "dp_quant": "int8"})
        _bench_round(tmp_path, 6, {**base, "value": 50.0,
                                   "dp_quant": "int8"})
        assert bg.main(["--strict", str(tmp_path)]) == 1
        assert "WARNING" in capsys.readouterr().out
        # the trend series stops at the exchange-mode boundary: rounds
        # 2/5/6 share int8 but round 2's predecessor is raw — series is
        # the int8 suffix only (5,6 + 2 is non-contiguous; suffix = 5,6)
        tflags, tlabel = bg.trend(bg._load_rounds(str(tmp_path)), 0.10)
        assert tlabel == "" or "int8" in tlabel

    def test_shard_topology_change_not_comparable(self, tmp_path, capsys):
        """A resharded round (different shard count, or a ring-version
        bump from churn) serves different slices from different servers
        — score it as a new series, not a regression of the old one."""
        bg = _bench_gate()
        base = {"metric": "ps_exchange_throughput", "platform": "cpu",
                "ps_shards": 8, "ring_version": 0}
        _bench_round(tmp_path, 1, {**base, "value": 200.0})
        _bench_round(tmp_path, 2, {**base, "value": 100.0,
                                   "ps_shards": 16})
        assert bg.main(["--strict", str(tmp_path)]) == 0
        assert "not comparable" in capsys.readouterr().out
        # same shard count but the ring churned: also a boundary
        _bench_round(tmp_path, 3, {**base, "value": 100.0,
                                   "ring_version": 2})
        assert bg.main(["--strict", str(tmp_path)]) == 0
        assert "not comparable" in capsys.readouterr().out
        # identical topology on both sides still flags a real drop
        _bench_round(tmp_path, 4, {**base, "value": 50.0,
                                   "ring_version": 2})
        assert bg.main(["--strict", str(tmp_path)]) == 1
        assert "WARNING" in capsys.readouterr().out

    def test_fleet_shape_change_not_comparable(self, tmp_path, capsys):
        """A 3-replica round must never be scored against a 1-replica
        round (per-replica goodput/latency scales with fleet size), nor
        p2c against least-loaded — different fleet, not a regression."""
        bg = _bench_gate()
        base = {"metric": "serve_load_tokens_per_sec", "platform": "cpu",
                "replica_count": 3, "router_policy": "p2c"}
        _bench_round(tmp_path, 1, {**base, "value": 200.0})
        _bench_round(tmp_path, 2, {**base, "value": 100.0,
                                   "replica_count": 1})
        assert bg.main(["--strict", str(tmp_path)]) == 0
        assert "not comparable" in capsys.readouterr().out
        # same fleet size but the routing policy changed: also a boundary
        _bench_round(tmp_path, 3, {**base, "value": 100.0,
                                   "router_policy": "least"})
        assert bg.main(["--strict", str(tmp_path)]) == 0
        assert "not comparable" in capsys.readouterr().out
        # identical fleet shape on both sides still flags a real drop
        _bench_round(tmp_path, 4, {**base, "value": 50.0,
                                   "router_policy": "least"})
        assert bg.main(["--strict", str(tmp_path)]) == 1
        assert "WARNING" in capsys.readouterr().out

    def test_fewer_than_two_rounds_is_clean(self, tmp_path, capsys):
        bg = _bench_gate()
        assert bg.main([str(tmp_path)]) == 0
        _bench_round(tmp_path, 1, {"metric": "m", "value": 1.0})
        assert bg.main([str(tmp_path)]) == 0
        assert "nothing to compare" in capsys.readouterr().out


# ------------------------------------------------------------- slow soak


@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_serve_soak(topo8, tmp_path, seed, capsys):
    """Multi-seed serving soak: every seeded load run (cancels + mild
    chaos) must pass the checked-in smoke gate. scripts/serve_soak.sh
    widens the seed space per round via MPIT_SERVE_SOAK_OFFSET."""
    from mpit_tpu.loadgen.__main__ import main as loadgen_main

    seed += 10 * int(os.environ.get("MPIT_SERVE_SOAK_OFFSET", "0"))
    out = str(tmp_path / f"soak_{seed}")
    assert loadgen_main([
        "--out", out, "--seed", str(seed), "--requests", "16",
        "--rate", "500", "--cancel-prob", "0.1",
        "--chaos-delay-p", "0.05",
    ]) == 0
    gate = os.path.join(os.path.dirname(__file__), os.pardir,
                        "scripts", "slo_smoke.json")
    assert obs_main(["slo", out, "--gate", gate]) == 0
    capsys.readouterr()
