"""Benchmark: async-SGD (EASGD) samples/sec/chip on MNIST LeNet.

North-star metric per BASELINE.json:2. The reference published no numbers
(BASELINE.json:13); its bundled example ran Torch7 on CPU (BASELINE.json:7),
so ``vs_baseline`` is measured against the same LeNet training loop in
torch (CPU) built here — the closest live stand-in for the reference stack.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "samples/sec/chip", "vs_baseline": N}
Extra fields are informative; the driver keys on the four required ones.

Flags (SURVEY.md §7 step 7 — the harness covers every BASELINE config):
  --preset NAME   time one workload config instead (same JSON-line shape)
  --all           headline metric + a "configs" map over all five workloads
  --profile DIR   capture a jax.profiler trace of the whole benchmark run
                  (staging + compile + timed legs) into DIR; opens in
                  Perfetto/TensorBoard: XLA op timeline, collectives
                  included. Profiling adds overhead — the JSON line carries
                  "profiled": true so the number is never mistaken for a
                  clean benchmark result.
"""

import json
import os
import sys
import time

import numpy as np


def _force_completion(state, m) -> float:
    """Proof of execution, not just dispatch — shared implementation in
    ``mpit_tpu.utils.profiling.force_completion`` (see its docstring for
    the platform finding): one fused scalar, data-dependent on both the
    final state (optimizer update) and the last metrics (fwd/bwd chain),
    fetched with a single host transfer."""
    from mpit_tpu.utils.profiling import force_completion

    return force_completion(state, m)


def _leg_phases(raw_dt: float, dt: float) -> dict:
    """Roofline phase fractions for a collective timed leg (the schema
    docs/OBSERVABILITY.md §roofline defines; ``phase_source:
    "timed-leg"``). The collective trainers run compute and collective
    transfer fused inside one XLA program, so the leg cannot split wire
    from compute — the honest attribution is: corrected time is compute
    (which here INCLUDES in-program collectives), the subtracted fetch
    RTT is harness overhead, wire/idle are unmeasured zeros. The
    host-async PS bench reports the real four-way split from its obs
    journals instead (``phase_source: "obs"``)."""
    compute = min(dt / raw_dt, 1.0) if raw_dt > 0 else 0.0
    return {
        "compute": round(compute, 4),
        "wire": 0.0,
        "idle": 0.0,
        "overhead": round(1.0 - compute, 4),
    }


# Dense bf16 peak FLOP/s per chip (models here compute in bfloat16) — the
# MFU denominator — keyed by the exact ``device_kind`` string jax reports.
# Only devices a run of this repo has met are listed; an unknown non-CPU
# kind is an error, not a silently missing ``mfu`` field.
_PEAK_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip;
    # device_kind as printed by chip_smoke.py on the v5e (PERF.md)
    "TPU v5 lite": 197e12,
}


def _peak_flops_per_chip(device):
    """Peak bf16 FLOP/s of ``device``; ``None`` on CPU, where no device
    metric is reported at all."""
    if device.platform == "cpu":
        return None
    try:
        return _PEAK_FLOPS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no peak-FLOP/s entry for device_kind {device.device_kind!r} "
            f"(platform {device.platform!r}); add it to bench._PEAK_FLOPS "
            "with its source rather than reporting a run without mfu"
        ) from None


def _device_tag() -> dict:
    """What every JSON line says about where it ran."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }


def _jaxpr_flops(jaxpr) -> float:
    """Matmul/conv FLOPs (2/MAC) in a jaxpr, recursing into sub-jaxprs
    (pjit, custom_vjp, ...) and multiplying scan bodies by trip count."""
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            rhs = eqn.invars[1].aval.shape
            batch = float(np.prod([lhs[i] for i in lb], dtype=np.float64))
            contract = float(np.prod([lhs[i] for i in lc], dtype=np.float64))
            lhs_free = float(np.prod(
                [d for i, d in enumerate(lhs) if i not in lc and i not in lb],
                dtype=np.float64,
            ))
            rhs_free = float(np.prod(
                [d for i, d in enumerate(rhs) if i not in rc and i not in _rb],
                dtype=np.float64,
            ))
            total += 2.0 * batch * contract * lhs_free * rhs_free
        elif name == "conv_general_dilated":
            out = eqn.outvars[0].aval.shape
            rhs = eqn.invars[1].aval.shape
            rhs_spec = eqn.params["dimension_numbers"].rhs_spec
            k_spatial = float(np.prod(
                [rhs[i] for i in rhs_spec[2:]], dtype=np.float64
            ))
            # rhs input-feature dim is already per-group (C_in / groups)
            in_ch = float(rhs[rhs_spec[1]])
            total += (
                2.0 * float(np.prod(out, dtype=np.float64)) * k_spatial * in_ch
            )
        elif eqn.params:
            mult = float(eqn.params.get("length", 1)) if name == "scan" else 1.0
            for val in eqn.params.values():
                for sub in val if isinstance(val, (tuple, list)) else (val,):
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None and hasattr(inner, "eqns"):
                        total += mult * _jaxpr_flops(inner)
                    elif hasattr(sub, "eqns"):
                        total += mult * _jaxpr_flops(sub)
    return total


def _model_flops_per_sample(trainer, state, x, y):
    """Fwd+bwd FLOPs per sample: dot/conv FLOPs counted in the jaxpr of a
    plain grad of the trainer's loss — the standard MFU accounting basis
    (matmul FLOPs only). Host-side tracing, no XLA compile: an AOT compile
    of ResNet-50@224 for cost analysis doubled the bench's wall time, and
    the compiled cost model undercounts ``lax.scan`` bodies (counted once
    regardless of trip count). Calibration on LeNet grad: 67.6M
    flops/sample here vs 58.2M from XLA's compiled cost analysis — the
    delta is first-layer input-gradients the compiler DCEs; this counter
    follows the standard analytic convention (≈3× forward) and is applied
    uniformly across presets."""
    import jax

    try:
        if isinstance(state, dict):  # pipeline trainer: dict state
            params = state["params"]
        else:
            params = state.center if hasattr(state, "center") else state.params
        loss_fn = trainer.loss_fn
        model = getattr(trainer, "model", None)
        if model is not None and getattr(model, "seq_axis", None):
            # the sharded model needs a mesh axis to trace; its dense twin
            # computes the same FLOPs per sample
            from mpit_tpu.parallel.common import default_loss_fn

            loss_fn = default_loss_fn(model.clone(seq_axis=None).apply)
        jaxpr = jax.make_jaxpr(jax.grad(loss_fn))(params, x, y)
        flops = _jaxpr_flops(jaxpr.jaxpr)
        return flops / len(x) if np.isfinite(flops) and flops > 0 else None
    except Exception:
        return None


def _stage_and_time(
    trainer, is_sync, topo, x_tr, y_tr, pwb, tau,
    rounds=None, target_seconds=2.0, input_dtype="float32", repeats=1,
):
    """The one timing harness (both the headline and the preset benches).

    Dataset lives on device, loaded once outside the timed region: the
    reference's Torch example equally held it in host RAM, and a production
    input pipeline overlaps transfers; timing a per-step host->device copy
    would benchmark this harness's host link, not the training system.
    Several distinct pre-staged rounds are cycled so no single batch
    is hot in any cache-like path, staged with the step's own input sharding
    (leading worker axis) — a default device_put would commit to device 0
    and sneak a redistribute-to-mesh back INTO every timed step.

    ``rounds=None`` sizes the timed leg adaptively from a short calibration
    run so every preset times ~``target_seconds`` of steady state regardless
    of how fast its step is. Completion of each leg is proven by
    ``_force_completion`` (its docstring compares it with
    ``block_until_ready`` on this machine; which barrier the benchmark
    keeps is ROADMAP Queue 1 item 1).
    """
    import jax

    from mpit_tpu.data import cast_input_dtype

    w = topo.num_workers
    gb = pwb * w
    rng = np.random.default_rng(0)
    # the seq trainer's inputs shard over BOTH mesh axes; everything else
    # shards the leading batch axis over the worker axis
    sharding = (
        trainer.data_sharding()
        if hasattr(trainer, "data_sharding")
        else topo.worker_sharding()
    )
    x_tr = cast_input_dtype(x_tr, input_dtype)
    staged = []
    for _ in range(8):
        idx = rng.integers(0, len(x_tr), tau * gb)
        if is_sync:
            xb, yb = x_tr[idx], y_tr[idx]
        else:
            xb, yb = trainer.round_batches(
                x_tr[idx].reshape(tau, gb, *x_tr.shape[1:]),
                y_tr[idx].reshape(tau, gb, *y_tr.shape[1:]),
            )
        staged.append(
            (jax.device_put(xb, sharding), jax.device_put(yb, sharding))
        )

    state = trainer.init_state(jax.random.key(0), x_tr[:2])
    # grab the compiled step AFTER init_state: some trainers (MoE) build
    # it lazily from the state template
    step = trainer._step if is_sync else trainer._round
    flops_per_sample = _model_flops_per_sample(
        trainer, state, x_tr[:gb], y_tr[:gb]
    )
    # warmup (compile; also compiles _force_completion's reduction)
    from mpit_tpu.parallel.common import bound_cpu_dispatch

    for _ in range(3):
        state, m = step(state, *staged[0])
        bound_cpu_dispatch(topo, m)  # cpu-mesh rendezvous deadlock guard
    _force_completion(state, m)
    # Pure fetch latency: everything is already complete here, so timing a
    # second completion fetch measures the host round-trip alone. It is
    # subtracted from each timed leg — the fetch proves completion but its
    # fixed cost is the harness's, not training time. (Whether the
    # subtraction is worth keeping on a local chip is the benchmark PR's
    # call — ROADMAP Queue 1 item 1.)
    t_f = time.perf_counter()
    _force_completion(state, m)
    fetch_overhead = time.perf_counter() - t_f

    def time_leg(state, m, n_rounds):
        """THE timed-leg rule, in one place (every leg — adaptive sizing
        and variance repeats — must measure under identical rules): run
        ``n_rounds``, prove completion, subtract the calibrated fetch
        RTT clamped to half the leg (the correction must trim bias, not
        manufacture throughput out of a mis-measured RTT)."""
        t0 = time.perf_counter()
        for r in range(n_rounds):
            state, m = step(state, *staged[r % len(staged)])
            bound_cpu_dispatch(topo, m)  # no-op on real chips (async)
        _force_completion(state, m)
        raw = time.perf_counter() - t0
        return state, m, raw, max(raw - fetch_overhead, raw * 0.5)

    adaptive = rounds is None
    if adaptive:
        rounds = 10
    while True:
        state, m, raw_dt, dt = time_leg(state, m, rounds)
        # The completion fetch pays one host round-trip, so a leg sized
        # from a short calibration undershoots; grow until the leg
        # genuinely covers the target.
        if not adaptive or raw_dt >= 0.7 * target_seconds or rounds >= 50_000:
            break
        rounds = int(
            min(max(rounds * target_seconds / raw_dt * 1.2, rounds * 2),
                50_000)
        )

    samples = rounds * tau * gb
    # variance control (host-interference outliers): re-run the
    # same-sized leg repeats-1 more times, report the MEDIAN rate and the
    # relative spread so a host-interference outlier is visible in the
    # row instead of silently kept. One leg (the default) reports
    # spread=None — absence of evidence, not zero variance.
    leg_rates = [samples / dt]
    for _ in range(repeats - 1):
        state, m, _raw, leg_dt = time_leg(state, m, rounds)
        leg_rates.append(samples / leg_dt)
    rate = float(np.median(leg_rates))
    spread = (
        round((max(leg_rates) - min(leg_rates)) / rate, 4)
        if len(leg_rates) > 1 else None
    )
    chips = topo.num_devices  # == w except on the 2-D seq-sync mesh
    res = {
        "samples_per_sec": rate,
        "samples_per_sec_per_chip": rate / chips,
        "chips": chips,
        "platform": topo.platform,
        "tau": tau,
        "per_worker_batch": pwb,
        "timed_rounds": rounds,
        "timed_samples": samples,
        "timed_seconds": round(samples / rate, 3),
        "repeats": len(leg_rates),
        # phase split of the last calibration leg (raw vs corrected time)
        "phases": _leg_phases(raw_dt, dt),
        "phase_source": "timed-leg",
        "spread": spread,
        # >10% leg-to-leg swing: host interference suspected — the row
        # needs a solo re-run before it is quoted
        "variance_flagged": bool(spread is not None and spread > 0.10),
    }
    peak = _peak_flops_per_chip(topo.devices[0])
    if flops_per_sample is not None:
        achieved = flops_per_sample * res["samples_per_sec_per_chip"]
        res["model_flops_per_sample"] = round(flops_per_sample, 1)
        res["model_flops_per_sec_per_chip"] = round(achieved, 1)
        if peak is not None:
            res["mfu"] = round(achieved / peak, 4)
            res["mfu_peak_flops"] = peak
    return res


def bench_jax(
    per_worker_batch: int = 1024,
    tau: int = 4,
    num_workers=None,
    rounds=None,
    input_dtype: str = "float32",
    repeats: int = 1,
) -> dict:
    import jax
    import optax

    import mpit_tpu
    from mpit_tpu.data import load_mnist
    from mpit_tpu.models import LeNet
    from mpit_tpu.parallel import EASGDTrainer

    mpit_tpu.finalize()  # allow re-init at a different world size
    topo = mpit_tpu.init(num_workers=num_workers)
    x_tr, y_tr, *_ = load_mnist(synthetic_train=4096)
    trainer = EASGDTrainer(
        LeNet(), optax.sgd(0.05, momentum=0.9), topo, tau=tau
    )
    return _stage_and_time(
        trainer, False, topo, x_tr, y_tr, per_worker_batch, tau, rounds,
        input_dtype=input_dtype, repeats=repeats,
    )


# per-worker batch for each workload preset; the timed-leg length is sized
# adaptively by _stage_and_time so every preset times ~2 s of steady state
# at whatever rate the platform actually delivers.
_PRESET_BENCH = {
    "mnist-easgd": 1024,
    "cifar-vgg-sync": 256,
    "alexnet-downpour": 64,
    "resnet50-sync": 32,
    "ptb-lstm-easgd": 128,
    # beyond-parity long-context config (T=256 tokens/sample; sp=1 on one
    # chip — the ring is exercised by the CPU-mesh tests and dryrun)
    "ptb-transformer-seq": 64,
    # beyond-parity pipeline config (pp=1 on one chip — microbatching and
    # the schedule still run; multi-stage proven on the CPU mesh/dryrun)
    "ptb-transformer-pp": 64,
    # MFU-ceiling config: GPT-2-small shape (768/3072, T=512) — the row
    # that shows the low parity-preset MFUs are model shapes, not the
    # framework
    "ptb-transformer-large": 8,
}
# every benchmarkable preset (the staged collective ones above plus the
# host-async literal-PS shape, which has its own harness)
ALL_BENCH_PRESETS = (*_PRESET_BENCH, "mnist-ps")


def bench_ps_literal(
    cpu_smoke: bool = False, input_dtype: str = "float32"
) -> dict:
    """The reference's literal shape (BASELINE.json:7): host-async PS,
    2 pclients + 1 pserver, concurrent actors over the tagged transport.

    Unlike the collective presets this measures the HOST-ASYNC path: the
    wall clock covers the whole concurrent run (client threads, tagged
    messages, server dispatch), and client losses are host-fetched in one
    batched transfer at every τ exchange (the exchange itself proves
    completion; fetching EVERY step timed the device round-trip instead
    of the system). A short untimed run first warms the shared jitted local step
    (one compiled function for all clients), so the timed leg measures
    steady state like the other presets; smoke mode shrinks the per-client
    batch too (XLA-CPU conv compile time explodes with batch size).

    The timed run is obs-armed: journals land in a throwaway dir and the
    roofline join (``mpit_tpu.obs.roofline``) turns them into the
    ``phases: {compute, wire, idle, overhead}`` split every bench JSON
    line now carries — here measured for real (``phase_source: "obs"``),
    compute spans proof-of-completion-closed by the training loop. The
    warmup run stays un-instrumented: journals append, so a warmed
    journal would pollute the timed window.

    The same journals also yield the ``dynamics`` roll-up (staleness
    p99, final elastic distance, update/param norm ratio) — update
    QUALITY riding next to samples/s, so an async-speedup comparison
    carries its own convergence-cost evidence (``scripts/bench_gate.py``
    compares the fields across runs)."""
    import tempfile

    import optax

    from mpit_tpu.data import load_mnist
    from mpit_tpu.run import _build_model
    from mpit_tpu.parallel import AsyncPSTrainer
    from mpit_tpu.utils.config import TrainConfig

    from mpit_tpu.data import cast_input_dtype

    cfg = TrainConfig().apply_preset("mnist-ps")
    per_client = 8 if cpu_smoke else max(cfg.global_batch // cfg.clients, 1)
    steps = 24 if cpu_smoke else 600
    x_tr, y_tr, x_te, y_te = load_mnist(synthetic_train=2048)
    x_tr = cast_input_dtype(x_tr, input_dtype)
    # the wire-format A/B lever (docs/WIRE.md): MPIT_BENCH_PS_TRANSPORT=
    # socket runs the same actors over real loopback TCP, where
    # MPIT_WIRE_FORMAT / MPIT_WIRE_QUANT select the codec — the framed-vs-
    # pickle serialize+deserialize comparison the fast-wire item records
    ps_transport = os.environ.get("MPIT_BENCH_PS_TRANSPORT", "auto")
    trainer = AsyncPSTrainer(
        _build_model(cfg, {}),
        optax.sgd(cfg.lr, momentum=cfg.momentum),
        num_clients=cfg.clients,
        num_servers=cfg.servers,
        algo=cfg.resolved_algo().removeprefix("ps-"),
        alpha=cfg.alpha if cfg.alpha is not None else 0.9 / cfg.clients,
        tau=cfg.tau,
        transport=ps_transport,
    )
    from mpit_tpu.obs import ObsConfig, roofline
    from mpit_tpu.obs.live import aggregate, read_snapshots, validate_snapshot

    # warm the shared jitted local step outside the timed region —
    # deliberately WITHOUT obs (journals append; see docstring)
    trainer.train(x_tr, y_tr, steps=2 * cfg.tau, batch_size=per_client)
    with tempfile.TemporaryDirectory(prefix="mpit_bench_obs_") as obs_dir:
        # arm obs for the timed run only: train() reads self.obs per
        # call, and the shared jitted step is already compiled, so the
        # attribute swap changes instrumentation, not the compute. live
        # rides along — the exporter is one 1 Hz daemon thread per rank,
        # and every bench run then doubles as a live-plane schema check
        trainer.obs = ObsConfig(dir=obs_dir, live=True)
        t0 = time.perf_counter()
        center, stats = trainer.train(
            x_tr, y_tr, steps=steps, batch_size=per_client, seed=1
        )
        wall = time.perf_counter() - t0
        trainer.obs = None
        report = roofline([obs_dir])
        snaps = read_snapshots(os.path.join(obs_dir, "live"))
        live_rep = aggregate(snaps) if snaps else None
        live_invalid = sum(
            1 for s in snaps.values() if validate_snapshot(s)
        )
        # update-quality roll-up from the same journals (must run inside
        # the with-block — the tempdir dies at dedent): staleness p99,
        # final elastic distance, update/param norm ratio — the quality
        # counterweight to samples/s for async-speedup comparisons
        from mpit_tpu.obs.dynamics import aggregate_dynamics

        dyn_run = aggregate_dynamics([obs_dir])["run"]
    run = report["run"]
    samples = steps * per_client * cfg.clients
    # wire-phase seconds summed across ranks from the telemetry
    # summaries: serialize/queue_wait/write off the SendHandles,
    # transfer/deserialize off the socket read loops — the exact
    # quantity the framed codec is meant to shrink (zero when the
    # transport measures no split, i.e. the reference-passing brokers)
    wire_detail = {
        "serialize_s": 0.0, "queue_wait_s": 0.0, "write_s": 0.0,
        "transfer_s": 0.0, "deserialize_s": 0.0,
    }
    for tel in stats.get("telemetry", []):
        for s in tel.get("send", {}).values():
            ph = s.get("phase_s", {})
            wire_detail["serialize_s"] += ph.get("serialize", 0.0)
            wire_detail["queue_wait_s"] += ph.get("queue_wait", 0.0)
            wire_detail["write_s"] += ph.get("write", 0.0)
        for v in tel.get("rx_phase_s", {}).values():
            wire_detail["transfer_s"] += v.get("transfer", 0.0)
            wire_detail["deserialize_s"] += v.get("deserialize", 0.0)
    wire_detail = {k: round(v, 4) for k, v in wire_detail.items()}
    from mpit_tpu.transport import wire as _wirecodec

    return {
        "samples_per_sec": samples / wall,
        # one host (and on this rig one chip) runs all actors
        "samples_per_sec_per_chip": samples / wall,
        "chips": 1,
        "algo": cfg.algo,
        "model": cfg.model,
        "clients": cfg.clients,
        "servers": cfg.servers,
        "accuracy": trainer.evaluate(center, x_te, y_te),
        "timed_seconds": round(wall, 3),
        "per_client_batch": per_client,
        "ps_transport": ps_transport,
        # effective codec knobs: the framed/pickle split only exists on
        # the socket path; broker modes pass references (no codec at all)
        "wire_format": (
            _wirecodec.wire_format_from_env()
            if ps_transport == "socket" else "none"
        ),
        "wire_quant": _wirecodec.quant_mode_from_env(),
        "wire_detail": wire_detail,
        **({
            "wire_bytes_total": sum(
                w["tx"] for w in stats["wire_bytes"]
            ),
        } if "wire_bytes" in stats else {}),
        **({
            "phases": {
                k: round(v, 4) for k, v in run["phases"].items()
            },
            "phase_source": "obs",
        } if run is not None else {}),
        **({
            # live-plane cross-check: rank count and final rolling
            # throughput from the in-run snapshots (the wall-clock
            # metric above remains the headline number)
            "live": {
                "ranks": live_rep["run"]["ranks"],
                "throughput": live_rep["run"]["throughput"],
                "invalid_snapshots": live_invalid,
            },
        } if live_rep is not None else {}),
        **({
            "dynamics": {
                "staleness_p99": dyn_run["staleness_p99"],
                "elastic_dist_final": (
                    None if dyn_run["elastic_dist_final"] is None
                    else round(dyn_run["elastic_dist_final"], 4)
                ),
                "norm_ratio": (
                    None if dyn_run["norm_ratio"] is None
                    else round(dyn_run["norm_ratio"], 5)
                ),
            },
        } if dyn_run is not None else {}),
    }


def bench_wire(cpu_smoke: bool = False) -> dict:
    """Codec microbench (the ``--wire`` preset): per-payload-size
    round-trip cost of the three wire paths — pickle (the old format),
    framed (``transport/wire.py``, zero-copy binary), and framed+int8
    quantized — plus a loopback-TCP one-way leg through real
    :class:`SocketTransport` pairs in both formats.

    The headline ``value`` is framed encode+decode throughput (MB/s,
    largest payload — higher is better); the per-size ``*_ms`` fields are
    what ``scripts/bench_gate.py --trend`` watches for codec regressions.
    Payloads are the PS push envelope shape ``(epoch, seq, basis,
    chunk)`` — the hot-path message this codec exists for."""
    import pickle
    import socket as _socket

    from mpit_tpu.transport import wire
    from mpit_tpu.transport.socket_transport import (
        WIRE_PICKLE_PROTOCOL,
        SocketTransport,
    )

    sizes = (
        {"4kb": 1 << 10, "64kb": 1 << 14}
        if cpu_smoke else
        {"64kb": 1 << 14, "1mb": 1 << 18, "4mb": 1 << 20}
    )
    rng = np.random.default_rng(7)
    fields: dict = {}
    framed_mbps = 0.0
    for label, n in sizes.items():
        arr = rng.standard_normal(n).astype(np.float32)
        payload = (1 << 62, 17, 3, arr)
        nbytes = arr.nbytes
        reps = max(3, min(200, int(2e8 / max(nbytes, 1))))

        t0 = time.perf_counter()
        for _ in range(reps):
            blob = pickle.dumps(payload, protocol=WIRE_PICKLE_PROTOCOL)
            pickle.loads(blob)
        fields[f"pickle_{label}_ms"] = (
            (time.perf_counter() - t0) / reps * 1e3
        )

        t0 = time.perf_counter()
        for _ in range(reps):
            bufs = wire.encode_frame(
                1, 2, payload, version=wire.WIRE_FORMAT_VERSION
            )
            head = bytes(bufs[0])
            body = b"".join(bytes(b) for b in bufs[1:])
            _v, flags, hlen, hcrc = wire.split_preamble(
                head[: wire.PREAMBLE_SIZE]
            )
            wire.decode_frame(
                flags, hcrc, head[wire.PREAMBLE_SIZE:], body
            )
        dt = (time.perf_counter() - t0) / reps
        fields[f"framed_{label}_ms"] = dt * 1e3
        framed_mbps = nbytes / dt / 1e6  # last (largest) size wins

        t0 = time.perf_counter()
        for _ in range(reps):
            q = wire.quantize(arr, "int8")
            bufs = wire.encode_frame(
                1, 2, (1 << 62, 17, 3, q),
                version=wire.WIRE_FORMAT_VERSION,
            )
            head = bytes(bufs[0])
            body = b"".join(bytes(b) for b in bufs[1:])
            _v, flags, hlen, hcrc = wire.split_preamble(
                head[: wire.PREAMBLE_SIZE]
            )
            _s, _t, out = wire.decode_frame(
                flags, hcrc, head[wire.PREAMBLE_SIZE:], body
            )
            wire.dequantize(out[3])
        fields[f"quant_int8_{label}_ms"] = (
            (time.perf_counter() - t0) / reps * 1e3
        )

    # loopback-TCP one-way leg: real sockets, both codecs. Same payload
    # count and size; the delta is the serialize+copy the framed path
    # removed (plus the 4x bytes the pickle of an f32 array still moves).
    msg_n = sizes[max(sizes, key=lambda k: sizes[k])]
    msgs = 8 if cpu_smoke else 32
    arr = rng.standard_normal(msg_n).astype(np.float32)
    for fmt in ("pickle", "framed"):
        probes = []
        addrs = []
        for _ in range(2):
            s = _socket.socket()
            s.bind(("127.0.0.1", 0))
            addrs.append(("127.0.0.1", s.getsockname()[1]))
            probes.append(s)
        for s in probes:
            s.close()
        ta = SocketTransport(0, 2, addresses=addrs, wire_format=fmt)
        tb = SocketTransport(1, 2, addresses=addrs, wire_format=fmt)
        try:
            ta.send(1, 2, (1, 0, 0, arr))  # warm the connection + hello
            tb.recv(timeout=30)
            t0 = time.perf_counter()
            for i in range(msgs):
                ta.send(1, 2, (1, i + 1, 0, arr))
            for _ in range(msgs):
                tb.recv(timeout=30)
            fields[f"loopback_{fmt}_ms"] = (
                (time.perf_counter() - t0) / msgs * 1e3
            )
        finally:
            ta.close()
            tb.close()
    return {
        "framed_mb_per_sec": framed_mbps,
        "sizes": sorted(sizes),
        "loopback_msgs": msgs,
        **{k: round(v, 4) for k, v in fields.items()},
    }


def bench_preset(
    name: str, num_workers=None, cpu_smoke: bool = False,
    input_dtype: str = "float32", stem: str = None, remat: bool = False,
    overrides: dict = None, repeats: int = 1,
) -> dict:
    """Steady-state training samples/sec/chip for one BASELINE workload
    config (same staging/timing harness as the headline metric).

    ``overrides``: extra TrainConfig field replacements applied on top of
    the preset — the generic channel for measuring variant axes
    (``{"attn_impl": "flash"}``, ``{"seq_impl": "ulysses"}``,
    ``{"algo": "zero-sync"}``, ``{"pp_schedule": "1f1b"}``, ...) without
    a dedicated flag per axis. Unknown fields raise."""
    import dataclasses

    import optax

    import mpit_tpu
    from mpit_tpu.run import _build_model, _load_dataset, build_trainer
    from mpit_tpu.utils.config import TrainConfig

    if name not in ALL_BENCH_PRESETS:
        raise ValueError(
            f"unknown bench preset {name!r}; have "
            f"{sorted(ALL_BENCH_PRESETS)}"
        )
    cfg = TrainConfig().apply_preset(name)
    if overrides:
        unknown = set(overrides) - {
            f.name for f in dataclasses.fields(TrainConfig)
        }
        if unknown:
            raise ValueError(
                f"unknown TrainConfig override(s) {sorted(unknown)}"
            )
        # fields the harness OWNS — an override would be silently stomped
        # (train_size/image_size are replaced below; the batch comes from
        # the per-preset table, epochs from the adaptive timed leg)
        harness_owned = {
            "input_dtype": "pass input_dtype=... instead",
            "train_size": "the harness sizes the staged dataset itself",
            "image_size": "the harness caps resolution itself",
            "global_batch": "per-worker batch comes from _PRESET_BENCH",
            "epochs": "the timed leg is sized adaptively, not by epochs",
        }
        clashes = set(overrides) & set(harness_owned)
        if clashes:
            raise ValueError(
                "override(s) the bench harness owns would be silently "
                "ignored: "
                + "; ".join(f"{k}: {harness_owned[k]}" for k in clashes)
            )
        cfg = dataclasses.replace(cfg, **overrides)
    if name == "mnist-ps" and overrides:
        raise ValueError(
            "mnist-ps runs the dedicated host-async harness "
            "(bench_ps_literal), which takes no config overrides — drop "
            "--set for this preset"
        )
    if stem is not None:  # measure the s2d-stem variant of a stem model
        from mpit_tpu.models import STEM_MODELS

        if cfg.model.lower() not in STEM_MODELS:
            raise ValueError(
                f"preset {name!r} (model {cfg.model!r}) has no stem "
                f"choice; stem applies to {STEM_MODELS}"
            )
        cfg = dataclasses.replace(cfg, stem=stem)
    if remat:
        from mpit_tpu.models import REMAT_MODELS

        if cfg.model.lower() not in REMAT_MODELS:
            raise ValueError(
                f"preset {name!r} (model {cfg.model!r}) has no remat "
                f"support; remat applies to {REMAT_MODELS}"
            )
        cfg = dataclasses.replace(cfg, remat=True)
    if name == "mnist-ps":
        return bench_ps_literal(cpu_smoke, input_dtype=input_dtype)
    pwb, rounds = _PRESET_BENCH[name], None
    # On real hardware run the config's true resolution (224px for the
    # ImageNet configs — the large-tensor stress BASELINE.json:10 names);
    # only the CPU smoke path shrinks the workload.
    image_cap = cfg.image_size
    if cpu_smoke:
        # tiny wiring run: the XLA-CPU backend's conv compile time explodes
        # with batch AND image size (see main()); shrink both
        pwb, rounds, image_cap = 8, 3, 64

    mpit_tpu.finalize()
    from mpit_tpu.run import second_axis_for

    second_axis = second_axis_for(cfg)
    if cfg.resolved_algo() in second_axis:
        ax, extent = second_axis[cfg.resolved_algo()]
        if num_workers is not None:  # honor a carved-down world here too
            usable = (num_workers // extent) * extent
            topo = mpit_tpu.init(
                axis_names=("dp", ax),
                mesh_shape=(usable // extent, extent),
                num_workers=usable,
            )
        else:
            from mpit_tpu.run import _world_for

            topo = _world_for(cfg)
    else:
        topo = mpit_tpu.init(num_workers=num_workers)
    # all devices execute every step; on the 2-D seq-sync mesh that is
    # dp*sp chips, not just the worker-axis extent
    gb = pwb * topo.num_workers
    from mpit_tpu.run import SYNC_ALGOS

    is_sync = cfg.resolved_algo() in SYNC_ALGOS
    tau = 1 if is_sync else cfg.tau
    cfg = dataclasses.replace(
        cfg, train_size=tau * gb * 2, image_size=min(cfg.image_size, image_cap)
    )
    x_tr, y_tr, *_rest, _meta = _load_dataset(cfg)
    model = _build_model(cfg, _meta, worker_axis=topo.worker_axis)
    # honor --set optimizer=.../lr_schedule=... (adam state math changes
    # step cost; the schedule is a count-based scalar, timing-neutral).
    # The horizon only shapes the cosine curve, not throughput.
    from mpit_tpu.run import build_optimizer

    opt = build_optimizer(cfg, 10_000)
    trainer = build_trainer(cfg, model, opt, topo)
    res = _stage_and_time(
        trainer, is_sync, topo, x_tr, y_tr, pwb, tau, rounds,
        input_dtype=input_dtype, repeats=repeats,
    )
    return {**res, "algo": cfg.algo, "model": cfg.model,
            **({"stem": cfg.stem} if stem is not None else {}),
            **({"remat": True} if remat else {})}


def measure_scaling_efficiency(full: dict) -> dict:
    """Scaling efficiency vs single chip (the BASELINE.md north-star's
    second half: per-chip throughput at W chips / per-chip throughput at 1).

    Only meaningful with >1 REAL device — on one chip (or a CPU-simulated
    mesh sharing one host) the honest answer is null, not a fake 100%."""
    import jax

    n = len(jax.devices())
    if n < 2 or jax.devices()[0].platform == "cpu":
        return {"scaling_efficiency": None, "scaling_note":
                f"needs >1 real chip (found {n} "
                f"{jax.devices()[0].platform} device(s))"}
    # same adaptive ~2 s budget as the numerator: a short denominator leg
    # would put run-to-run noise straight into the efficiency ratio
    single = bench_jax(num_workers=1)
    eff = full["samples_per_sec_per_chip"] / single["samples_per_sec_per_chip"]
    return {
        "scaling_efficiency": round(eff, 4),
        "single_chip_samples_per_sec": round(
            single["samples_per_sec_per_chip"], 1
        ),
    }


def bench_decode(
    cpu_smoke: bool = False, weights_dtype: str = None,
    mixed: bool = False,
) -> dict:
    """Serving throughput: greedy tokens/sec of the batched KV-cached
    decode (``models.sampling.generate_batch``) on the GPT-2-small-shaped
    LM (the ptb-transformer-large dims), random params.

    ``mixed=True`` is the realistic serving shape: prompt lengths spread
    across the batch (rows get p_len, p_len-7, p_len-13, ... down to
    ~p_len/2). Per-row cache clocks prefill every row's entire prompt in
    the same dense pass, so this measures the same kernel as the uniform
    run on an unequal batch. tokens/sec counts GENERATED tokens, and
    every row generates ``steps``, so the metric is comparable to the
    uniform run.

    Completion needs no separate proof here: the sampled tokens
    themselves are host-fetched by the API (the return value IS the
    data-dependent fetch), so the wall clock covers real device work by
    construction. One fetch per CALL (not per token) — its cost
    amortizes over batch x steps generated tokens.
    """
    import jax
    import jax.numpy as jnp

    from mpit_tpu.models import generate_batch
    from mpit_tpu.models.transformer import TransformerLM

    if cpu_smoke:  # wiring run: tiny model, tiny budget
        dims = dict(vocab_size=101, num_layers=2, d_model=32,
                    num_heads=4, max_len=64)
        nb, p_len, steps = 2, 8, 24
    else:
        # prompt+steps == max_len == the 512 scan bucket exactly, so NO
        # timed tick is bucket-overrun waste (total-1=511 kept ticks of
        # a 512-tick scan)
        dims = dict(vocab_size=10_000, num_layers=6, d_model=768,
                    num_heads=12, max_len=512)
        nb, p_len, steps = 8, 64, 512 - 64
    model = TransformerLM(**dims)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    rng = np.random.default_rng(0)
    if mixed:
        # spread lengths over [p_len/2, p_len]: realistic unequal prompts
        lens = [
            max(p_len // 2, p_len - 1 - (7 * i) % (p_len // 2 + 1))
            for i in range(nb)
        ]
        # longest row at p_len keeps the prefill/scan buckets identical
        # to the uniform run, so the two metrics compare like for like
        lens[0] = p_len
    else:
        lens = [p_len] * nb
    prompts = [
        rng.integers(0, dims["vocab_size"], n).tolist() for n in lens
    ]
    if weights_dtype == "bf16":
        # cast ONCE, before the timing loop — steady-state serving pays
        # this once, so per-call casting would bias the very bandwidth
        # metric the flag measures (and hold f32+bf16 live at 1.5x)
        from mpit_tpu.models.sampling import cast_weights

        params = cast_weights(params, jnp.bfloat16)
    gen = lambda: generate_batch(model, params, prompts, steps)
    first = gen()  # compile + warmup
    assert all(
        len(r) == n + steps for r, n in zip(first, lens)
    )
    # same variance control as the training legs: median of N timed
    # legs + relative spread, flagged >10% (the one-core-host
    # interference class)
    leg_rates, calls = [], 0
    for _ in range(1 if cpu_smoke else 3):
        legc = 0
        t0 = time.perf_counter()
        while legc < 2 or time.perf_counter() - t0 < 2.0:
            gen()
            legc += 1
        leg_rates.append(legc * nb * steps / (time.perf_counter() - t0))
        calls += legc
    rate = float(np.median(leg_rates))
    spread = (
        round((max(leg_rates) - min(leg_rates)) / rate, 4)
        if len(leg_rates) > 1 else None
    )
    return {
        "tokens_per_sec": rate,
        "spread": spread,
        "variance_flagged": bool(spread is not None and spread > 0.10),
        "batch": nb,
        "prompt_len": p_len,
        **({"mixed_prompt_lens": lens} if mixed else {}),
        "steps": steps,
        "calls": calls,
        # wall ms per decode TICK (all nb rows advance one token/tick)
        "per_token_ms": 1e3 * nb / rate,
        "model": "transformer-large" if not cpu_smoke else "tiny",
        **({"weights_dtype": weights_dtype} if weights_dtype else {}),
    }


def bench_serve(
    cpu_smoke: bool = False, weights_dtype: str = None,
    burst: bool = False, prefix_len: int = 0,
) -> dict:
    """Continuous-batching throughput: sustained generated tokens/sec of
    ``models.serving.Server`` draining a queue of unequal requests
    (prompt lengths AND budgets spread) through a fixed slot count —
    the serving metric with retirement + admission in the loop, where
    ``--decode`` measures one static batch. Completion is by
    construction: every generated token is host-fetched by the drain.

    ``burst``: instead of a pre-filled queue, submit only the first
    slot-full, run one segment, then dump EVERY remaining request
    mid-flight — the admission-cost regime (grouped same-bucket
    prefills at a scheduling boundary) that the plain drain never
    exercises because its queue admits into free slots one segment at
    a time.

    ``prefix_len``: share a prefix_len-token prompt prefix across every
    request (the system-prompt regime) — the server prefills it once
    into a cache template; admission pays suffix FLOPs only.
    """
    import jax
    import jax.numpy as jnp

    from mpit_tpu.models import Server
    from mpit_tpu.models.transformer import TransformerLM

    if cpu_smoke:
        dims = dict(vocab_size=101, num_layers=2, d_model=32,
                    num_heads=4, max_len=64)
        reqs = [(6 + (i * 3) % 10, 8 + (i * 5) % 12) for i in range(6)]
        max_batch, segment, legs = 2, 8, 1
    else:
        dims = dict(vocab_size=10_000, num_layers=6, d_model=768,
                    num_heads=12, max_len=512)
        # 24 requests over 8 slots: prompts 32..128, budgets 128..320
        reqs = [
            (32 + (i * 13) % 97, 128 + (i * 29) % 193) for i in range(24)
        ]
        max_batch, segment, legs = 8, 64, 3
    model = TransformerLM(**dims)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    if weights_dtype == "bf16":
        from mpit_tpu.models.sampling import cast_weights

        params = cast_weights(params, jnp.bfloat16)
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, dims["vocab_size"], p).tolist() for p, _ in reqs
    ]
    prefix = (
        rng.integers(0, dims["vocab_size"], prefix_len).tolist()
        if prefix_len else None
    )
    if prefix_len:
        # keep prefix + prompt + budget within max_len (>=1 so an
        # impossible prefix fails loudly in submit, not silently here)
        reqs = [(p, max(1, min(mn, dims["max_len"] - prefix_len - p - 1)))
                for p, mn in reqs]

    def drain_once():
        srv = Server(model, params, max_batch=max_batch, segment=segment,
                     prefix=prefix)
        pairs = list(zip(prompts, (mn for _, mn in reqs)))
        head = pairs[:max_batch] if burst else pairs
        for q, mn in head:
            srv.submit(q, mn)
        if burst:
            srv.step()  # head requests are mid-flight...
            for q, mn in pairs[max_batch:]:
                srv.submit(q, mn)  # ...when the burst arrives at once
        out = srv.drain()
        return sum(mn for _, mn in reqs), srv.segments_run, out

    drain_once()  # compile + warmup (all bucket shapes)
    leg_rates, segments = [], 0
    for _ in range(legs):
        t0 = time.perf_counter()
        tokens, segments, _ = drain_once()
        leg_rates.append(tokens / (time.perf_counter() - t0))
    rate = float(np.median(leg_rates))
    spread = (
        round((max(leg_rates) - min(leg_rates)) / rate, 4)
        if len(leg_rates) > 1 else None
    )
    return {
        "tokens_per_sec": rate,
        "spread": spread,
        "variance_flagged": bool(spread is not None and spread > 0.10),
        "requests": len(reqs),
        "max_batch": max_batch,
        "segment": segment,
        "segments_per_drain": segments,
        "model": "transformer-large" if not cpu_smoke else "tiny",
        **({"weights_dtype": weights_dtype} if weights_dtype else {}),
        **({"admission": "burst"} if burst else {}),
        **({"prefix_len": prefix_len} if prefix_len else {}),
    }


def bench_load(cpu_smoke: bool = False, seed: int = 0) -> dict:
    """Serving under traffic: the open-loop load harness
    (``mpit_tpu.loadgen``) drives a Server with Poisson arrivals and
    mixed length buckets while the server journals every request
    lifecycle; the reported numbers are the journal's reduction (the
    same one ``python -m mpit_tpu.obs slo`` computes) — tokens/sec AND
    the latency scorecard (TTFT/TPOT/e2e percentiles, goodput) that a
    drain-style bench cannot see. Seeded end to end: the schedule is a
    pure function of ``seed``, so a regression replays.
    """
    import glob
    import tempfile

    import jax
    import jax.numpy as jnp

    from mpit_tpu.loadgen import (
        LoadHarness, LoadSpec, aggregate_paths, make_workload,
    )
    from mpit_tpu.models import Server
    from mpit_tpu.models.transformer import TransformerLM
    from mpit_tpu.obs.core import ObsConfig

    if cpu_smoke:
        dims = dict(vocab_size=101, num_layers=2, d_model=32,
                    num_heads=4, max_len=64)
        spec = LoadSpec(requests=12, rate=500.0, seed=seed)
        max_batch, segment = 2, 8
    else:
        dims = dict(vocab_size=10_000, num_layers=6, d_model=768,
                    num_heads=12, max_len=512)
        spec = LoadSpec(
            requests=48, rate=50.0, seed=seed,
            prompt_buckets=((8, 48, 0.6), (48, 128, 0.4)),
            output_buckets=((16, 64, 0.6), (64, 160, 0.4)),
        )
        max_batch, segment = 8, 32
    model = TransformerLM(**dims)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    work = make_workload(spec, dims["vocab_size"],
                         max_len=dims["max_len"])

    # warmup drain without obs: compile every bucket shape the measured
    # run will hit, so TTFT measures scheduling rather than XLA
    warm = Server(model, params, max_batch=max_batch, segment=segment)
    for r in work:
        warm.submit(list(r.prompt), r.max_new)
    warm.drain()

    with tempfile.TemporaryDirectory() as obs_dir:
        srv = Server(
            model, params, max_batch=max_batch, segment=segment,
            obs=ObsConfig(dir=obs_dir),
        )
        rep = LoadHarness(srv, work).run()
        report = aggregate_paths(
            sorted(glob.glob(os.path.join(obs_dir, "obs_rank*.jsonl")))
        )
    tps = report["tokens_per_sec"]
    return {
        "tokens_per_sec": (
            float(tps) if tps is not None
            else report["tokens"] / max(rep.wall_s, 1e-9)
        ),
        "requests": spec.requests,
        "rate": spec.rate,
        "seed": seed,
        "max_batch": max_batch,
        "segment": segment,
        "ttft_p50_ms": report["ttft"].get("p50_ms"),
        "ttft_p99_ms": report["ttft"].get("p99_ms"),
        "tpot_p50_ms": report["tpot"].get("p50_ms"),
        "e2e_p99_ms": report["e2e"].get("p99_ms"),
        "goodput": report["goodput"],
        "finished": report["requests"]["finished"],
        "unfinished": report["requests"]["unfinished"],
        "model": "transformer-large" if not cpu_smoke else "tiny",
    }


def bench_fleet_load(
    cpu_smoke: bool = False, seed: int = 0, n_replicas: int = 3,
    policy: str = "p2c",
) -> dict:
    """The fleet variant of :func:`bench_load`: the same seeded open-loop
    workload offered to a ``mpit_tpu.fleet`` router over ``n_replicas``
    in-process replicas instead of one Server. e2e/goodput/tokens come
    from the ROUTER journal (admission-to-ack, the number a client
    feels); TTFT/TPOT come from the replica journals pooled per-replica
    (replica rid spaces collide, so they aggregate separately and the
    histograms merge). ``replica_count``/``router_policy`` ride the JSON
    line as comparability keys — scripts/bench_gate.py never trends a
    3-replica round against a 1-replica round.
    """
    import glob
    import tempfile

    import jax
    import jax.numpy as jnp

    from mpit_tpu.fleet import FleetHarness, audit_lifecycle
    from mpit_tpu.loadgen import (
        LoadSpec, aggregate_paths, make_workload, pooled_latencies,
    )
    from mpit_tpu.models import Server
    from mpit_tpu.models.transformer import TransformerLM
    from mpit_tpu.obs.core import ObsConfig

    # same workload shapes as bench_load, cancellations off (the fleet
    # wire has no CANCEL lane)
    if cpu_smoke:
        dims = dict(vocab_size=101, num_layers=2, d_model=32,
                    num_heads=4, max_len=64)
        spec = LoadSpec(requests=12, rate=500.0, seed=seed,
                        cancel_prob=0.0)
        max_batch, segment = 2, 8
    else:
        dims = dict(vocab_size=10_000, num_layers=6, d_model=768,
                    num_heads=12, max_len=512)
        spec = LoadSpec(
            requests=48, rate=50.0, seed=seed, cancel_prob=0.0,
            prompt_buckets=((8, 48, 0.6), (48, 128, 0.4)),
            output_buckets=((16, 64, 0.6), (64, 160, 0.4)),
        )
        max_batch, segment = 8, 32
    model = TransformerLM(**dims)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    work = make_workload(spec, dims["vocab_size"],
                         max_len=dims["max_len"])

    # warmup drain: replicas share this process's compile cache, so one
    # drain of every bucket shape warms the whole fleet
    warm = Server(model, params, max_batch=max_batch, segment=segment)
    for r in work:
        warm.submit(list(r.prompt), r.max_new)
    warm.drain()

    with tempfile.TemporaryDirectory() as out:
        rep_dirs = {}

        def factory(rank):
            d = os.path.join(out, f"rep{rank}")
            os.makedirs(d, exist_ok=True)
            rep_dirs[rank] = d
            return Server(model, params, max_batch=max_batch,
                          segment=segment, obs=ObsConfig(dir=d))

        router_dir = os.path.join(out, "router")
        os.makedirs(router_dir)
        fleet = FleetHarness(
            factory, work, n_replicas=n_replicas, policy=policy,
            seed=seed, obs_dir=router_dir,
        )
        rep = fleet.run()
        router_paths = sorted(
            glob.glob(os.path.join(router_dir, "obs_rank*.jsonl"))
        )
        report = aggregate_paths(router_paths)
        audit = audit_lifecycle(router_paths)
        lat = pooled_latencies(
            sorted(glob.glob(os.path.join(d, "obs_rank*.jsonl")))
            for d in rep_dirs.values()
        )
    tps = report["tokens_per_sec"]
    return {
        "tokens_per_sec": (
            float(tps) if tps is not None
            else report["tokens"] / max(rep.wall_s, 1e-9)
        ),
        "requests": spec.requests,
        "rate": spec.rate,
        "seed": seed,
        "max_batch": max_batch,
        "segment": segment,
        "replica_count": n_replicas,
        "router_policy": policy,
        "ttft_p50_ms": lat["ttft"].get("p50_ms"),
        "ttft_p99_ms": lat["ttft"].get("p99_ms"),
        "tpot_p50_ms": lat["tpot"].get("p50_ms"),
        "e2e_p99_ms": report["e2e"].get("p99_ms"),
        "goodput": report["goodput"],
        "finished": report["requests"]["finished"],
        "unfinished": report["requests"]["unfinished"],
        "lost": len(audit["lost"]),
        "audit_ok": bool(audit["ok"]),
        "model": "transformer-large" if not cpu_smoke else "tiny",
    }


def bench_spec(cpu_smoke: bool = False, k: int = 4) -> dict:
    """Speculative-decoding throughput: greedy tokens/sec of
    ``generate_speculative`` vs the plain cached decode on the SAME
    trained target — the serving-acceleration metric. Both models train
    briefly on a deterministic next-token pattern so the draft's
    proposals actually agree with the target (random-init models agree
    at chance, which would measure nothing); the draft has ~1/6 the
    target's width/depth, so accepted chunks pay draft-sized FLOPs for
    target-sized progress. Completion is by construction (the returned
    tokens are the host fetch). ``mean_emitted`` reports tokens emitted
    per verification chunk (in [1, k+1]) — the measured draft quality.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from mpit_tpu.models import generate_fast, generate_speculative
    from mpit_tpu.models.transformer import TransformerLM

    V = 512
    if cpu_smoke:
        t_dims, d_dims = (2, 64, 4), (1, 32, 2)
        max_len, steps, train_steps, legs = 128, 48, 60, 1
    else:
        t_dims, d_dims = (6, 512, 8), (2, 128, 4)
        max_len, steps, train_steps, legs = 1024, 512, 300, 3

    def build(layers, d, heads):
        return TransformerLM(
            vocab_size=V, num_layers=layers, d_model=d, num_heads=heads,
            max_len=max_len,
        )

    def pattern(n, t, seed):
        rng = np.random.default_rng(seed)
        starts = rng.integers(0, V, (n, 1))
        stepixs = np.arange(t + 1)[None, :]
        seq = (starts + 3 * stepixs * (starts % 5 + 1)) % V
        return seq[:, :t].astype(np.int32), seq[:, 1:].astype(np.int32)

    def train(model, seed):
        x, y = pattern(32, 64, seed=1)
        params = model.init(jax.random.key(seed), x[:2])["params"]
        opt = optax.adam(3e-3)
        ost = opt.init(params)

        @jax.jit
        def step(p, o, xb, yb):
            def loss_fn(p):
                logits = model.apply({"params": p}, xb)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, yb
                ).mean()

            loss, g = jax.value_and_grad(loss_fn)(p)
            up, o = opt.update(g, o)
            return optax.apply_updates(p, up), o, loss

        for _ in range(train_steps):
            params, ost, _ = step(params, ost, x, y)
        return params

    target, draft = build(*t_dims), build(*d_dims)
    tp, dp = train(target, seed=0), train(draft, seed=5)
    # the prompt is a TRAINING row: both models continue a sequence they
    # learned, so draft/target agreement is high — the regime speculative
    # decoding exists for (an unseen start would measure two models
    # disagreeing about noise: mean_emitted ~1, no draft signal)
    prompt = [int(t) for t in pattern(32, 64, seed=1)[0][0][:32]]

    def time_fn(fn):
        fn()  # compile + warmup
        rates = []
        for _ in range(legs):
            t0 = time.perf_counter()
            fn()
            rates.append(steps / (time.perf_counter() - t0))
        med = float(np.median(rates))
        spread = (
            round((max(rates) - min(rates)) / med, 4)
            if len(rates) > 1 else None
        )
        return med, spread

    plain, _ = time_fn(lambda: generate_fast(target, tp, prompt, steps))
    spec, spread = time_fn(lambda: generate_speculative(
        target, tp, draft, dp, prompt, steps, k=k
    ))

    # the same trained pair through the CONTINUOUS-BATCHING tier:
    # speculative Server vs plain Server on a queue of pattern prompts
    from mpit_tpu.models import Server

    x_rows, _ = pattern(8, 48, seed=1)
    q_prompts = [[int(t) for t in row[:24]] for row in x_rows]
    q_mn = min(steps, max_len - 24 - k - 1)

    def drain(srv_kw):
        # segment applies to the plain server only; the spec server's
        # granularity is its spec_rounds
        srv = Server(target, tp, max_batch=4, segment=16, **srv_kw)
        for q in q_prompts:
            srv.submit(q, q_mn)
        srv.drain()
        return len(q_prompts) * q_mn

    def time_drain(srv_kw):
        drain(srv_kw)  # compile + warmup
        rates = []
        for _ in range(legs):
            t0 = time.perf_counter()
            toks = drain(srv_kw)
            rates.append(toks / (time.perf_counter() - t0))
        return float(np.median(rates))

    serve_plain = time_drain({})
    serve_spec = time_drain(dict(
        draft_model=draft, draft_params=dp, spec_k=k,
        spec_rounds=4,
    ))
    toks, stats = generate_speculative(
        target, tp, draft, dp, prompt, steps, k=k, return_stats=True
    )
    # exactness is the feature's contract — assert it on the bench pair
    # so a published speedup can never come from a wrong decode
    assert toks == generate_fast(target, tp, prompt, steps)
    return {
        "tokens_per_sec": spec,
        "spread": spread,
        "variance_flagged": bool(spread is not None and spread > 0.10),
        "plain_tokens_per_sec": round(plain, 1),
        "speedup": round(spec / plain, 3) if plain else None,
        "k": k,
        "mean_emitted": round(stats["mean_emitted"], 2),
        "steps": steps,
        "serve_tokens_per_sec": round(serve_spec, 1),
        "serve_plain_tokens_per_sec": round(serve_plain, 1),
        "serve_speedup": (
            round(serve_spec / serve_plain, 3) if serve_plain else None
        ),
        "model": "512d-6L vs 128d-2L draft" if not cpu_smoke else "tiny",
    }


def bench_torch_cpu(
    batch: int = 256, steps: int = 12, target_seconds: float = 2.0
) -> float:
    """Reference-stack stand-in: the same LeNet trained with torch on CPU
    (the reference's ptest example ran Torch on CPU, BASELINE.json:7).
    ``steps`` is a floor; the timed leg extends until ``target_seconds``
    elapse so the denominator gets the same noise attenuation as the
    adaptive JAX numerator."""
    try:
        import torch
        import torch.nn as tnn
    except Exception:
        return float("nan")

    torch.manual_seed(0)
    model = tnn.Sequential(
        tnn.Conv2d(1, 32, 5, padding=2), tnn.ReLU(), tnn.MaxPool2d(2),
        tnn.Conv2d(32, 64, 5, padding=2), tnn.ReLU(), tnn.MaxPool2d(2),
        tnn.Flatten(),
        tnn.Linear(64 * 7 * 7, 256), tnn.ReLU(),
        tnn.Linear(256, 10),
    )
    opt = torch.optim.SGD(model.parameters(), lr=0.05, momentum=0.9)
    loss_fn = tnn.CrossEntropyLoss()
    x = torch.rand(batch, 1, 28, 28)
    y = torch.randint(0, 10, (batch,))
    # warmup
    for _ in range(2):
        opt.zero_grad(); loss_fn(model(x), y).backward(); opt.step()
    done = 0
    t0 = time.perf_counter()
    while done < steps or time.perf_counter() - t0 < target_seconds:
        opt.zero_grad(); loss_fn(model(x), y).backward(); opt.step()
        done += 1
    dt = time.perf_counter() - t0
    return batch * done / dt


def main():
    # runs on whatever jax.devices() gives — no probe, no platform switch;
    # every JSON line names the platform, device_kind and device count, and
    # on cpu the sizes shrink to a wiring run whose numbers are not device
    # metrics (README "Benchmarks")
    from mpit_tpu.utils.compile_cache import enable_compile_cache
    from mpit_tpu.utils.profiling import trace

    enable_compile_cache()
    device_tag = _device_tag()
    cpu = device_tag["platform"] == "cpu"

    def flag_arg(flag):
        """Value of `flag <arg>` from argv; usage-errors via SystemExit(2)
        when the argument is missing or another flag."""
        if flag not in sys.argv:
            return None
        i = sys.argv.index(flag) + 1
        if i >= len(sys.argv) or sys.argv[i].startswith("--"):
            print(f"{flag} requires an argument", file=sys.stderr)
            raise SystemExit(2)
        return sys.argv[i]

    profile_dir = flag_arg("--profile")
    profiled = {"profiled": True} if profile_dir else {}
    input_dtype = flag_arg("--input-dtype") or "float32"
    from mpit_tpu.data import INPUT_DTYPES

    if input_dtype not in INPUT_DTYPES:  # fail at flag parse, not mid-run
        print(
            f"--input-dtype must be one of {INPUT_DTYPES}, "
            f"got {input_dtype!r}",
            file=sys.stderr,
        )
        raise SystemExit(2)
    dtype_tag = (
        {"input_dtype": input_dtype} if input_dtype != "float32" else {}
    )

    def emit_tokens_metric(metric, res, fields, opt_fields):
        """THE reporting contract every tokens/sec bench shares
        (--decode, --serve, --load, --spec): one JSON line. A change to
        the reporting rules lands here once."""
        print(json.dumps({
            "metric": metric,
            "value": round(res["tokens_per_sec"], 1),
            "unit": "tokens/sec/chip",
            "vs_baseline": None,  # the reference cannot sample at all
            **{k: res[k] for k in fields},
            **{k: res[k] for k in opt_fields if res.get(k) is not None},
            **device_tag,
            **profiled,
        }))

    def weights_dtype_flag():
        wd = flag_arg("--weights-dtype")
        if wd is not None and wd != "bf16":
            print("--weights-dtype supports: bf16", file=sys.stderr)
            raise SystemExit(2)
        return wd

    if "--serve" in sys.argv:
        wd = weights_dtype_flag()
        burst = "--burst" in sys.argv
        plen = int(flag_arg("--prefix-len") or 0)
        with trace(profile_dir):
            res = bench_serve(cpu_smoke=cpu, weights_dtype=wd, burst=burst,
                              prefix_len=plen)
        emit_tokens_metric(
            "serve_tokens_per_sec",
            res,
            ("requests", "max_batch", "segment", "segments_per_drain",
             "model"),
            ("weights_dtype", "spread", "admission", "prefix_len"),
        )
        return

    if "--load" in sys.argv:
        seed = int(flag_arg("--seed") or 0)
        fleet = flag_arg("--fleet")
        if fleet is not None:
            n = int(fleet)
            if n < 1:
                print("--fleet requires N >= 1", file=sys.stderr)
                raise SystemExit(2)
            policy = flag_arg("--policy") or "p2c"
            with trace(profile_dir):
                res = bench_fleet_load(
                    cpu_smoke=cpu, seed=seed, n_replicas=n,
                    policy=policy,
                )
            emit_tokens_metric(
                "serve_load_tokens_per_sec", res,
                ("requests", "rate", "seed", "max_batch", "segment",
                 "replica_count", "router_policy", "finished",
                 "unfinished", "lost", "audit_ok", "model"),
                ("ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms",
                 "e2e_p99_ms", "goodput"),
            )
            return
        with trace(profile_dir):
            res = bench_load(cpu_smoke=cpu, seed=seed)
        emit_tokens_metric(
            "serve_load_tokens_per_sec", res,
            ("requests", "rate", "seed", "max_batch", "segment",
             "finished", "unfinished", "model"),
            ("ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms", "e2e_p99_ms",
             "goodput"),
        )
        return

    if "--spec" in sys.argv:
        with trace(profile_dir):
            res = bench_spec(cpu_smoke=cpu)
        emit_tokens_metric(
            "spec_tokens_per_sec", res,
            ("plain_tokens_per_sec", "speedup", "k", "mean_emitted",
             "steps", "serve_tokens_per_sec",
             "serve_plain_tokens_per_sec", "serve_speedup", "model"),
            ("spread",),
        )
        return

    if "--decode" in sys.argv:
        wd = weights_dtype_flag()
        mixed = "--mixed" in sys.argv
        with trace(profile_dir):
            res = bench_decode(cpu_smoke=cpu, weights_dtype=wd, mixed=mixed)
        emit_tokens_metric(
            "decode_tokens_per_sec",
            res,
            ("batch", "prompt_len", "steps", "per_token_ms", "model"),
            ("weights_dtype", "spread", "mixed_prompt_lens"),
        )
        return

    if "--wire" in sys.argv:
        with trace(profile_dir):
            res = bench_wire(cpu_smoke=cpu)
        print(json.dumps({
            "metric": "wire_codec_throughput",
            "value": round(res["framed_mb_per_sec"], 1),
            "unit": "MB/sec",
            "vs_baseline": None,  # pickle_*_ms columns ARE the baseline
            **{k: v for k, v in res.items() if k != "framed_mb_per_sec"},
            **device_tag,
            **profiled,
        }))
        return

    name = flag_arg("--preset")
    if name is not None:
        try:
            with trace(profile_dir):
                res = bench_preset(
                    name, cpu_smoke=cpu, input_dtype=input_dtype,
                    repeats=1 if cpu else 3,
                )
        except ValueError as e:
            print(str(e), file=sys.stderr)
            return 2
        print(json.dumps({
            "metric": f"{name}_throughput",
            "value": round(res["samples_per_sec_per_chip"], 1),
            "unit": "samples/sec/chip",
            "vs_baseline": None,  # only the headline config has a baseline
            **{k: res[k] for k in ("chips", "algo", "model")},
            **{
                k: res[k]
                for k in ("mfu", "spread", "phases", "phase_source",
                          "live", "dynamics", "ps_transport",
                          "wire_format", "wire_quant", "wire_detail",
                          "wire_bytes_total")
                if k in res
            },
            **device_tag,
            **profiled,
            **dtype_tag,
        }))
        return

    # smoke-run sizing on cpu: a CPU mesh shares one host's cores AND the
    # CPU backend's conv compile time grows steeply with batch size (>200s
    # at 64/worker); keep the smoke run tiny — the number it prints is
    # wiring validation, not a benchmark. On hardware: adaptive timed leg,
    # completion-proven.
    pwb, rounds = (8, 3) if cpu else (1024, None)
    configs = None
    with trace(profile_dir):  # covers the headline AND (with --all) every
        jax_res = bench_jax(  # preset
            per_worker_batch=pwb, rounds=rounds, input_dtype=input_dtype,
            repeats=1 if cpu else 3,
        )
        if "--all" in sys.argv:
            configs = {
                name: round(
                    bench_preset(
                        name, cpu_smoke=cpu, input_dtype=input_dtype,
                        repeats=1 if cpu else 3,  # same variance rule as
                    )["samples_per_sec_per_chip"],  # every other leg
                    1,
                )
                for name in ALL_BENCH_PRESETS
                if name != "mnist-easgd"  # the headline metric above
            }
    scaling = measure_scaling_efficiency(jax_res)
    # baseline at the SAME per-worker batch as the numerator (a 1024-batch
    # TPU rate over a 256-batch CPU rate would not be apples-to-apples)
    torch_sps = bench_torch_cpu(batch=pwb, steps=3)
    value = jax_res["samples_per_sec_per_chip"]
    # no torch -> no baseline measurement; report null, not fake parity
    vs = round(value / torch_sps, 2) if np.isfinite(torch_sps) else None
    out = {
        "metric": "easgd_mnist_lenet_throughput",
        "value": round(value, 1),
        "unit": "samples/sec/chip",
        "vs_baseline": vs,
        "baseline": "torch-cpu LeNet train step (reference ran Torch on CPU)",
        "baseline_samples_per_sec": round(torch_sps, 1)
        if np.isfinite(torch_sps)
        else None,
        "chips": jax_res["chips"],
        "platform": jax_res["platform"],
        **{
            k: jax_res[k]
            for k in ("mfu", "model_flops_per_sec_per_chip", "timed_seconds",
                      "timed_rounds", "spread", "phases", "phase_source")
            if k in jax_res and jax_res[k] is not None
        },
        **scaling,
        **device_tag,
        **profiled,
        **dtype_tag,
    }
    if configs is not None:
        out["configs"] = configs
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
