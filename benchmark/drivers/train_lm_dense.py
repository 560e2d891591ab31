"""``drivers/train_lm_ref.py``'s procedure for a described model with NO expert
layer (dense feed-forwards throughout): the configuration's ``comparison``
block names the reference module, the leaf groups, the counters and the
limits as there, and has no ``experts_held_key``.

``train_lm_ref`` is an accepted file, and two of its functions cannot serve
such a model: ``reference_check`` reads ``arch[compare["experts_held_key"]]``
and ``arch["num_experts_per_tok"]``, and ``kernel_shapes`` finds its kernels
by ``hybrid_override_pattern``. So this file repeats ``run`` with those two
written for the dense case and everything else imported (``grouping``,
``gradient_errors``, ``decide``, ``move_check``, the clocks, the tracer): a
``benchmark`` issue folds the two drivers into one (PERF.md section 7).

The comparison that decides ``correct`` is ``train_lm.decide`` on the same
numbers in the same order: the parameters alone; the reference's loss and
gradient a layer at a time; the step program's own gradient
(``trainer._local_vg``) by leaf group; two units through ``fit`` and the move
against the same optimizer's from the reference's gradient; then the window.
The routing reads are vacuous (``rows_held`` empty, mismatch 0, dropped 0),
which ``decide`` passes as written. ``setup_s`` leaves the comparison's
seconds out as ``train_lm.run`` does.

``run["kernels"]`` gives the readers the shapes of what the step runs:
``delta`` (``lib/delta_kernels``) for the gated-delta-rule layers. The
full-attention layer's causal flash kernels have no reader in a cell of this
driver (the accepted ``flash_causal_roofline_pct`` and ``attn_full_ms_unit``
list the Laguna cell alone: PERF.md section 7 row 11), so no shape is handed
on for them.
"""

import dataclasses
import importlib
import math
import os
import shutil
import statistics

from benchmark.drivers.train import (
    TimedBatches, Tracer, _Stop, _unit_memory, build_config,
)
from benchmark.drivers.train_lm import (
    LIMITS, CountingClock, OneBatch, Stopwatch, _paths, arch_of, decide,
    move_check,
)
from benchmark.drivers.train_lm_ref import gradient_errors, grouping
from benchmark.lib import flops, timing, trace_reduce, traffic


def reference_check(reference, compare, model, trainer, arch, key, bx, by,
                    detail):
    """``train_lm_ref.reference_check`` without the routing: the start
    parameters and the reference's gradient (both on the host), its loss and
    the gradient's error by leaf group."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    bx, by = jnp.asarray(bx), jnp.asarray(by)
    params = jax.jit(lambda k, x: model.init(k, x)["params"])(key, bx)
    ref_loss, ref_grads, _ = reference.loss_and_grad_by_layer(
        params, bx, by, arch, to_host=True)
    # the step program's own value-and-gradient function, not a second
    # loss: what is compared is what the timed step differentiates
    _, sys_grads = jax.jit(trainer._local_vg)(params, bx, by)
    grad_err, by_leaf = gradient_errors(
        sys_grads, ref_grads, grouping(compare["leaf_groups"]))
    del sys_grads
    start = jax.device_get(params)
    detail("reference", {
        "loss": float(ref_loss), "grad_rel_err_by_group": grad_err,
        "grad_by_leaf": by_leaf,
        "parameters": int(sum(np.size(l) for l in jax.tree.leaves(start))),
    })
    return start, ref_grads, float(ref_loss), grad_err


def kernel_shapes(arch: dict, per_chip: int, seq_len: int) -> dict:
    """The shapes of the scans the step runs that a reader reads (bfloat16
    operands), by the first ``num_hidden_layers`` of ``layer_types``."""
    kinds = list(arch.get("layer_types", []))[:arch["num_hidden_layers"]]
    out = {}
    if "linear_attention" in kinds:
        out["delta"] = {
            "batch": per_chip, "t": seq_len,
            "layers": kinds.count("linear_attention"),
            "heads": arch["linear_num_key_heads"],
            "key_dim": arch["linear_key_head_dim"],
            "value_dim": arch["linear_value_head_dim"], "itemsize": 2}
    return out


def run(ctx) -> dict:
    import jax
    import numpy as np

    import mpit_tpu
    from mpit_tpu import run as program
    from mpit_tpu.data import Batches

    args, detail, meter = ctx["args"], ctx["detail"], ctx["meter"]
    compare = ctx["config"]["comparison"]
    reference = importlib.import_module(
        f"benchmark.lib.{compare['reference']}")
    cfg, job, sizes = build_config(ctx)
    if cfg.arch is None:  # a rehearsal brings its own, tiny one
        cfg = dataclasses.replace(cfg, arch=arch_of(ctx["config"]))
    arch = cfg.arch
    detail("train_config", dataclasses.asdict(cfg))

    # -- the job, built by the functions run() itself calls ----------------
    topo = mpit_tpu.init()
    model = program._build_model(cfg, sizes, worker_axis=topo.worker_axis)
    opt = program.build_optimizer(cfg, job["total_updates"])
    trainer = program.build_trainer(cfg, model, opt, topo)
    chips, per_chip = ctx["chips"], job["per_chip_batch"]
    samples_per_unit = per_chip * chips

    x, y = traffic.make(args.seed, job["data"], seq_len=cfg.seq_len, **sizes)
    batches = Batches(x, y, global_batch=cfg.global_batch, seed=args.seed)
    detail("data", {"samples": len(x), "pool": len(x.pool),
                    "units_per_epoch": batches.steps_per_epoch()})
    key = jax.random.key(args.seed % (2**31 - 1))

    # -- the reference, before the optimizer's state exists -----------------
    bx, by = next(iter(batches.epoch(0)))
    check = Stopwatch(meter)  # the comparison's own seconds and compiles
    with check:
        start, ref_grads, ref_loss, grad_err = reference_check(
            reference, compare, model, trainer, arch, key, bx, by, detail)
    detail("after_reference", {**meter.summary(), "check_s": check.seconds})

    # -- the state, and two units through fit on that batch -----------------
    state = trainer.init_state(key, bx[:per_chip])
    same_start = all(
        bool(np.array_equal(np.asarray(a.addressable_data(0)), b))
        for (_, a), (_, b) in zip(_paths(state.params), _paths(start)))
    timed = TimedBatches(batches)

    def fit(clock, feed, epoch, st):
        clock.open()
        try:
            trainer.fit(feed, st, epochs=10**9, start_epoch=epoch,
                        prefetch=cfg.prefetch, on_step=clock)
        except _Stop:
            pass
        finally:
            clock.drain()
        return clock.state

    warm = CountingClock(max_units=1)  # stops once unit 1 is done: 2 ran
    state = fit(warm, OneBatch(bx, by), 0, state)
    first_losses = [float(m["loss"]) for m in warm.metrics]
    with check:
        move_err, moved, expected = move_check(
            opt, start, ref_grads, state.params)
    del start, ref_grads
    compiled = meter.summary()
    detail("after_warm_up", {**compiled, "check_s": check.seconds,
                             "check_compile_s": check.compile_seconds})

    # -- the window ----------------------------------------------------------
    tracer = None
    if args.trace:
        trace_dir = os.path.join(ctx["out_dir"], "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        span = job.get("trace_seconds", 4.0)
        tracer = Tracer(trace_dir, max((args.seconds - span) / 2, 0.0), span,
                        min_units=6)
    clock = CountingClock(seconds=args.seconds, tracer=tracer)
    try:
        state = fit(clock, timed, 1, state)
    finally:
        if tracer is not None:
            tracer.stop()
    compiled_in_window = meter.programs - compiled["programs"]

    # -- from stamps to numbers (drivers/train.py's arithmetic, tau = 1) ------
    units = len(clock.stamps)
    window_s = clock.stamps[-1] - clock.t_open
    losses = [float(l) for l in clock.losses]
    group = job.get("units_per_interval", 1)
    edges = [clock.t_open] + clock.stamps[group - 1::group]
    dirty = [any(clock.dirty[j * group:(j + 1) * group])
             for j in range(len(edges) - 1)]
    intervals = [(b - a) / group for a, b in zip(edges, edges[1:])]
    clean = [iv for iv, d in zip(intervals, dirty) if not d]
    counters = {name: [float(m[name]) for m in clock.metrics[:units]
                       if name in m] for name in compare["counters"]}
    quarter = max(units // 4, 1)
    loss_fell = (statistics.fmean(losses[-quarter:])
                 < statistics.fmean(losses[:quarter]))
    failed = sum(not math.isfinite(l) for l in losses)
    limits = {**LIMITS, **compare.get("limits", {}), **job.get("limits", {})}
    read = {
        "same_start": same_start, "first_losses": first_losses,
        "reference_loss": ref_loss, "grad_rel_err_by_group": grad_err,
        "move_rel_err": move_err, "move_norm": moved,
        # no expert layer: the routing reads are vacuous, and pass as written
        "routing_mismatch": 0.0, "rows_dropped": 0.0, "rows_held": [],
        "rows_expected": 0.0, "losses_not_finite": failed,
        "compiled_in_window": compiled_in_window, "loss_fell": loss_fell,
    }
    checks = decide(read, limits, job.get("loss_must_fall", False))
    detail("checks", {
        **checks, "read": {**read, "reference_move_norm": expected},
        "limits": limits,
    })
    detail("window", {
        "units": units, "seconds": window_s, "tau": 1,
        "samples_per_unit": samples_per_unit, "units_per_interval": group,
        "intervals_ms": [round(iv * 1e3, 3) for iv in intervals],
        "dirty": [j for j, d in enumerate(dirty) if d],
        "losses": [round(l, 5) for l in losses],
        "counters": {k: [round(v, 3) for v in vs]
                     for k, vs in counters.items()},
    })

    # -- after the window: memory, and what only a traced run needs ---------
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    detail("memory_stats", stats)
    sharding = topo.worker_sharding()
    staged = lambda a: jax.ShapeDtypeStruct(
        (chips * per_chip, *a.shape[1:]), a.dtype, sharding=sharding)
    unit_mem = _unit_memory(trainer._step, state, staged(x), staged(y))
    detail("unit_program_memory", unit_mem)
    scratch = unit_mem["temp"] + unit_mem["output"] - unit_mem["alias"]
    memory_peak = max(
        max(s.get("peak_bytes_in_use", 0), s.get("bytes_in_use", 0) + scratch)
        for s in stats
    )

    reduced, flops_per_sample = None, None
    if args.trace:
        reduced = trace_reduce.reduce_trace(
            trace_reduce.load(trace_reduce.newest_xplane(trace_dir)))
        detail("trace", {k: v for k, v in reduced.items() if k != "breakdown"})
        abstract = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        batch = lambda a: jax.ShapeDtypeStruct((per_chip, *a.shape[1:]), a.dtype)
        flops_per_sample = flops.train_flops_per_sample(
            trainer.loss_fn, jax.tree.map(abstract, state.params),
            batch(x), batch(y))
        detail("flops_per_sample", flops_per_sample)

    return {
        "correct": all(checks.values()),
        "attempted": units,
        "failed": failed,
        "memory_peak_bytes": memory_peak,
        "setup_s": clock.t_open - ctx["t0"] - check.seconds,
        "end_to_end": {
            "samples_per_s_chip": units * samples_per_unit / window_s / chips,
            "unit_ms_p90": timing.percentile(intervals, 90) * 1e3,
        },
        # what the per-layer readers are given (benchmark/readers/)
        "run": {
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "chips": chips,
            "samples_per_unit": samples_per_unit,
            "compile_s": compiled["seconds"] - check.compile_seconds,
            "intervals_s": intervals,
            "clean_intervals_s": clean,
            "input_host_s_unit": list(timed.seconds),
            "flops_per_sample": flops_per_sample,
            "trace": reduced,
            "counters": counters,
            "kernels": kernel_shapes(arch, per_chip, cfg.seq_len),
        },
    }
