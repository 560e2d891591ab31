"""``drivers/train_lm.py``'s procedure for any model described by
``TrainConfig.arch``: the reference module, the grouping of the parameter
leaves, the counters kept, the limits and the kernels' shapes are data, named
by the configuration's file under ``comparison``:

    "comparison": {
      "reference": "reference_nemotron_h",      a module of benchmark/lib/ with
                                                 loss_and_grad_by_layer
      "kernel_shapes": "ssm_kernels:kernel_shapes",
                                                 a function of benchmark/lib/:
                                                 (arch, per_chip, seq_len) ->
                                                 run["kernels"]
      "experts_held_key": "n_routed_experts",   arch's count of experts held;
                                                 only where the model has
                                                 expert layers
      "leaf_groups": {"ssm": ["in_proj", ...]}, group -> leaf names
      "counters": ["moe_rows_held", ...],       the step's metrics to keep
      "limits": {...}, "limits_why": {...}      replace train_lm.LIMITS singly
    }

so a further described model needs a configuration, a reference and its
kernels' module, not a driver. The comparison that decides ``correct`` is
``train_lm.decide``, on the numbers ``train_lm.run`` reads, in its order (the
parameters alone; the reference's loss and gradient a layer at a time, on
the system's own expert choices where the model has experts; the step
program's own gradient by leaf group; two units through ``fit`` and the move
against the same optimizer's from the reference's gradient; then the window,
every step of which must route within the band and drop nothing). A model
without ``experts_held_key`` has no routing to read: its routing reads are
vacuous (``rows_held`` empty, mismatch 0, dropped 0) and pass as written.
``setup_s`` leaves the comparison's seconds out as ``train_lm.run`` does.
Everything ``train_lm`` and ``train`` export is imported; what is written
again here is what their ``run`` hard-wires to one model (the reference
module, ``leaf_group``, the kernels' shapes, the key of the experts held).

A traced run also prints ``lm_spans``' detail line where the step runs one of
the kernels that file reads (``flash_causal`` in the shapes): no metric of
such a cell's is read from it.
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
    move_check, rel_err,
)
from benchmark.lib import flops, timing, trace_reduce, traffic


def grouping(leaf_groups: dict):
    """``['Block_2']['in_proj']`` -> the group it is compared in."""
    group_of = {leaf: g for g, leaves in leaf_groups.items() for leaf in leaves}
    return lambda path: group_of[path.rstrip("]'").rsplit("'", 1)[-1]]


def gradient_errors(grads, ref_grads, group):
    """``train_lm.gradient_errors`` with the grouping given: ``|g -
    g_reference| / |g_reference|`` by leaf group, and by leaf with the
    reference's norm first."""
    import jax
    import jax.numpy as jnp

    sq = jax.jit(lambda a, b: (jnp.sum(jnp.square(a - b)),
                               jnp.sum(jnp.square(b))))
    by_group, by_leaf = {}, {}
    for (path, g), (_, r) in zip(_paths(grads), _paths(ref_grads)):
        diff, norm = (float(v) for v in sq(jnp.asarray(g), jnp.asarray(r)))
        have = by_group.setdefault(group(path), [0.0, 0.0])
        have[0] += diff
        have[1] += norm
        by_leaf[path] = [math.sqrt(norm), rel_err(diff, norm)]
    return ({g: rel_err(d, n) for g, (d, n) in sorted(by_group.items())},
            by_leaf)


def system_choices(model, params, bx, layers):
    """The system's own top-k a layer on the batch (None where the layer has
    no experts), from one forward pass."""
    import jax

    _, sown = jax.jit(lambda p, x: model.apply(
        {"params": p}, x, mutable=["routing"]))(params, bx)
    routing = sown.get("routing", {})
    return [routing[f"Block_{l}"]["experts"][0]
            if f"Block_{l}" in routing else None for l in range(layers)]


def reference_check(reference, compare, model, trainer, arch, key, bx, by,
                    detail):
    """Steps 1 to 3 of ``train_lm``'s docstring. Returns the start parameters
    and the reference's gradient (both on the host), its loss, the numbers
    compared so far and the worst layer's routing mismatch (0 without
    experts)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    bx, by = jnp.asarray(bx), jnp.asarray(by)
    params = jax.jit(lambda k, x: model.init(k, x)["params"])(key, bx)
    held_key = compare.get("experts_held_key")
    routing, differ = {}, []
    if held_key:
        choices = system_choices(model, params, bx, arch["num_hidden_layers"])
        routing = {"experts_held": arch[held_key],
                   "expert_offset": arch.get("expert_offset", 0),
                   "choices": choices}
    ref_loss, ref_grads, own = reference.loss_and_grad_by_layer(
        params, bx, by, arch, **routing, to_host=True)
    if held_key:
        k = arch["num_experts_per_tok"]
        differ = [
            float(1.0 - jnp.mean(jnp.any(
                c.reshape(-1, k, 1) == o.reshape(-1, 1, k), axis=-1)))
            for c, o in zip(choices, own) if c is not None
        ]
    # the step program's own value-and-gradient function, not a second
    # loss: what is compared is what the timed step differentiates
    _, sys_grads = jax.jit(trainer._local_vg)(params, bx, by)
    grad_err, by_leaf = gradient_errors(
        sys_grads, ref_grads, grouping(compare["leaf_groups"]))
    del sys_grads
    start = jax.device_get(params)
    detail("reference", {
        "loss": float(ref_loss), "grad_rel_err_by_group": grad_err,
        "routing_mismatch_by_layer": differ, "grad_by_leaf": by_leaf,
        "parameters": int(sum(np.size(l) for l in jax.tree.leaves(start))),
    })
    return start, ref_grads, float(ref_loss), grad_err, max(differ, default=0.0)


def kernel_shapes(compare: dict, arch: dict, per_chip: int,
                  seq_len: int) -> dict:
    """``run["kernels"]``: what the function the ``comparison`` block names
    gives for this ``arch``."""
    module, _, function = compare["kernel_shapes"].partition(":")
    shapes = getattr(importlib.import_module(f"benchmark.lib.{module}"),
                     function)
    return shapes(arch, per_chip, seq_len)


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
        start, ref_grads, ref_loss, grad_err, mismatch = reference_check(
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
    dropped = float(sum(counters.get("moe_rows_dropped", [])) + sum(
        float(m.get("moe_rows_dropped", 0.0)) for m in warm.metrics))
    held = arch.get(compare.get("experts_held_key"), 0)
    # rows a layer under uniform routing
    rows_expected = (per_chip * cfg.seq_len * arch.get("num_experts_per_tok", 0)
                     * held / arch.get("num_routed_experts", held or 1))
    read = {
        "same_start": same_start, "first_losses": first_losses,
        "reference_loss": ref_loss, "grad_rel_err_by_group": grad_err,
        "move_rel_err": move_err, "move_norm": moved,
        "routing_mismatch": mismatch, "rows_dropped": dropped,
        "rows_held": counters.get("moe_rows_held", []),
        "rows_expected": rows_expected, "losses_not_finite": failed,
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

    shapes = kernel_shapes(compare, arch, per_chip, cfg.seq_len)
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
        # the scopes and kernels lm_spans reads, for PERF.md: no metric of
        # this cell's is read from them
        from benchmark.lib import lm_spans

        if set(shapes) & set(lm_spans.KERNELS):
            lm_spans.traced()

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
            "kernels": shapes,
        },
    }
