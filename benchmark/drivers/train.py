"""The training driver: a cell is a ``TrainConfig`` driven through the
program's own ``trainer.fit``, the loop ``mpit_tpu.run.run`` uses.

Everything that builds the job (``_build_model``, ``build_optimizer``,
``build_trainer``, ``data.Batches``, ``fit``) is the program's; this file adds
the data, the check against the plain reference, the clock and the trace. A
unit is one call of the trainer's jitted program: a step under ``sync``, a
round of ``tau`` steps under ``easgd``.
"""

import dataclasses
import math
import os
import shutil
import statistics
import time

from benchmark.lib import flops, reference, timing, trace_reduce, traffic


class _Stop(Exception):
    """Ends a ``fit`` call from its callback; private to this file."""


class TimedBatches:
    """The ``data.Batches`` protocol around the job's batches, with a host
    timer and a ``bench.input`` span around each ``next()``."""

    def __init__(self, batches):
        self.batches = batches
        self.seconds = []  # one entry a batch handed to ``fit``

    def steps_per_epoch(self) -> int:
        return self.batches.steps_per_epoch()

    def epoch(self, epoch_index: int):
        from jax.profiler import TraceAnnotation

        it = iter(self.batches.epoch(epoch_index))
        while True:
            t = time.perf_counter()
            with TraceAnnotation("bench.input"):
                try:
                    item = next(it)
                except StopIteration:
                    return
            self.seconds.append(time.perf_counter() - t)
            yield item


class UnitClock:
    """``fit``'s ``on_round`` / ``on_step`` callback.

    Called after unit k was dispatched, it waits for unit k-1 and stamps the
    clock: one unit stays in flight, so a dispatch is never what is timed and
    the clock never drains the device. ``stamps[j]`` is when unit j+1 of this
    ``fit`` call completed. It ends the call by raising ``_Stop`` after
    ``max_units`` completed units or ``seconds`` since ``open()``."""

    def __init__(self, seconds=math.inf, max_units=math.inf, on_first=None,
                 tracer=None):
        self.seconds = seconds
        self.max_units = max_units
        self.on_first = on_first
        self.tracer = tracer
        self.stamps, self.losses, self.dirty = [], [], []
        self.state = None
        self._in_flight = None
        self._dirty_next = 0

    def open(self):
        self.t_open = time.perf_counter()

    def __call__(self, done, state, metrics):
        import jax
        from jax.profiler import TraceAnnotation

        self.state = state
        if done == 1 and self.on_first is not None:
            self.on_first(state)
        waited, self._in_flight = self._in_flight, metrics
        if waited is None:
            return
        with TraceAnnotation("bench.wait"):
            jax.block_until_ready(waited)
        now = time.perf_counter()
        self.stamps.append(now)
        self.losses.append(waited["loss"])
        self.dirty.append(self._dirty_next > 0)
        self._dirty_next = max(self._dirty_next - 1, 0)
        if self.tracer is not None and self.tracer.poll(
                now - self.t_open, lambda: jax.block_until_ready(metrics)):
            self._dirty_next = 2
        if len(self.stamps) >= self.max_units or now - self.t_open >= self.seconds:
            raise _Stop

    def drain(self):
        import jax

        jax.block_until_ready((self._in_flight, self.state))


class Tracer:
    """Starts ``jax.profiler`` once ``start_s`` into the window and stops it
    once ``span_s`` and ``min_units`` units have passed."""

    def __init__(self, trace_dir, start_s, span_s, min_units):
        self.trace_dir = trace_dir
        self.start_s, self.span_s, self.min_units = start_s, span_s, min_units
        self.started_at = None
        self.done = False
        self._units = 0

    def poll(self, elapsed, drain) -> bool:
        """Called at every stamp; true where it started or stopped the
        trace. ``drain`` waits for the unit in flight first, so that every
        program run the trace holds is whole."""
        import jax

        if self.done:
            return False
        if self.started_at is None:
            if elapsed < self.start_s:
                return False
            drain()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # spans, not every Python call
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self.started_at = elapsed
            return True
        self._units += 1
        if (elapsed - self.started_at < self.span_s
                or self._units < self.min_units):
            return False
        drain()
        self.stop()
        return True

    def stop(self):
        import jax

        if self.started_at is not None and not self.done:
            jax.profiler.stop_trace()
        self.done = True


def build_config(ctx):
    """The cell's ``TrainConfig``: the configuration's fields, then the
    workload's, then (in a rehearsal) the workload's tiny overrides."""
    from mpit_tpu.utils.config import TrainConfig

    job = dict(ctx["workload"])
    fields = dict(ctx["config"]["train_config"])
    fields.update(job.get("train_config", {}))
    sizes = {k: ctx["config"][k] for k in ("vocab_size", "num_classes")
             if k in ctx["config"]}
    if ctx["rehearsal"]:
        tiny = job["rehearsal"]
        fields.update(tiny.get("train_config", {}))
        sizes.update({k: tiny[k] for k in ("vocab_size", "num_classes")
                      if k in tiny})
        job.update({k: v for k, v in tiny.items() if k != "train_config"})
    fields["global_batch"] = job["per_chip_batch"] * ctx["chips"]
    return TrainConfig(**fields), job, sizes


def _copy_on_first_device(tree):
    """A copy of ``tree`` the trainer's donation cannot reach, on device 0."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda a: jnp.copy(a.addressable_data(0)), tree)


def _unit_memory(program, state, x, y):
    """What one run of the unit's program needs beyond the buffers the
    process already holds: ``memory_analysis`` of the compiled program, whose
    scratch this backend's ``memory_stats`` does not count (PERF.md).
    ``x`` and ``y`` are the staged batch's shapes."""
    mem = program.lower(state, x, y).compile().memory_analysis()
    return {
        "temp": mem.temp_size_in_bytes,
        "argument": mem.argument_size_in_bytes,
        "output": mem.output_size_in_bytes,
        "alias": mem.alias_size_in_bytes,
        "code": mem.generated_code_size_in_bytes,
    }


def run(ctx) -> dict:
    import jax

    import mpit_tpu
    from mpit_tpu import run as program
    from mpit_tpu.data import Batches
    from mpit_tpu.parallel.common import RoundTrainer

    args, detail, meter = ctx["args"], ctx["detail"], ctx["meter"]
    cfg, job, sizes = build_config(ctx)
    detail("train_config", dataclasses.asdict(cfg))

    # -- the job, built by the functions run() itself calls ----------------
    topo = mpit_tpu.init()
    model = program._build_model(cfg, sizes, worker_axis=topo.worker_axis)
    opt = program.build_optimizer(cfg, job["total_updates"])
    trainer = program.build_trainer(cfg, model, opt, topo)
    rounds = isinstance(trainer, RoundTrainer)
    tau = cfg.tau if rounds else 1
    chips, per_chip = ctx["chips"], job["per_chip_batch"]
    samples_per_unit = tau * per_chip * chips

    x, y = traffic.make(args.seed, job["data"], seq_len=cfg.seq_len,
                        image_size=cfg.image_size, **sizes)
    batches = Batches(x, y, global_batch=cfg.global_batch, seed=args.seed)
    state = trainer.init_state(
        jax.random.key(args.seed % (2**31 - 1)), x.pool[:2]
    )
    detail("data", {"samples": len(x), "pool": len(x.pool),
                    "units_per_epoch": batches.steps_per_epoch() // tau})

    # -- the plain reference's first unit, outside the window --------------
    first = [xy for _, xy in zip(range(tau), batches.epoch(0))]
    by_worker = [
        [(bx[w * per_chip:(w + 1) * per_chip], by[w * per_chip:(w + 1) * per_chip])
         for bx, by in first]
        for w in range(chips)
    ] if rounds else [first]
    start = _copy_on_first_device(state.center if rounds else state.params)
    ref_loss, ref_move = reference.first_unit(
        trainer.loss_fn, opt, start, by_worker,
        alpha=trainer.alpha if rounds else None,
    )
    detail("after_reference", meter.summary())

    # -- warm-up: two units through fit itself ------------------------------
    seen = {}

    def after_first_unit(st):
        # dispatched before the next unit donates ``st``
        moved = st.center if rounds else st.params
        seen["move"] = reference.tree_distance(_copy_on_first_device(moved), start)

    timed = TimedBatches(batches)
    hook = "on_round" if rounds else "on_step"

    def fit(clock, epoch, st):
        clock.open()
        try:
            trainer.fit(timed, st, epochs=10**9, start_epoch=epoch,
                        prefetch=cfg.prefetch, **{hook: clock})
        except _Stop:
            pass
        finally:
            clock.drain()
        return clock.state

    warm = UnitClock(max_units=1, on_first=after_first_unit)
    state = fit(warm, 0, state)
    first_loss, first_move = float(warm.losses[0]), float(seen["move"])
    del start, seen
    compiled = meter.summary()
    detail("after_warm_up", compiled)

    # -- the window ----------------------------------------------------------
    tracer = None
    if args.trace:
        trace_dir = os.path.join(ctx["out_dir"], "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        span = job.get("trace_seconds", 4.0)
        tracer = Tracer(trace_dir, max((args.seconds - span) / 2, 0.0), span,
                        min_units=6)
    timed.seconds.clear()
    clock = UnitClock(seconds=args.seconds, tracer=tracer)
    try:
        state = fit(clock, 1, state)
    finally:
        if tracer is not None:
            tracer.stop()
    compiled_in_window = meter.programs - compiled["programs"]

    # -- from stamps to numbers ---------------------------------------------
    units = len(clock.stamps)
    window_s = clock.stamps[-1] - clock.t_open
    losses = [float(l) for l in clock.losses]
    group = job.get("units_per_interval", 1)
    edges = [clock.t_open] + clock.stamps[group - 1::group]
    # an interval is dirty where a trace was started or stopped inside it
    dirty = [any(clock.dirty[j * group:(j + 1) * group])
             for j in range(len(edges) - 1)]
    intervals = [(b - a) / group for a, b in zip(edges, edges[1:])]
    clean = [iv for iv, d in zip(intervals, dirty) if not d]
    input_s = [sum(timed.seconds[k:k + tau]) for k in
               range(0, len(timed.seconds) - tau + 1, tau)]
    quarter = max(units // 4, 1)
    loss_fell = (statistics.fmean(losses[-quarter:])
                 < statistics.fmean(losses[:quarter]))
    failed = sum(not math.isfinite(l) for l in losses)
    checks = {
        "first_unit_loss": reference.agree(first_loss, ref_loss,
                                           reference.LOSS_RTOL),
        "first_unit_move": reference.agree(first_move, ref_move,
                                           reference.MOVE_RTOL),
        "losses_finite": failed == 0,
        "nothing_compiled_in_window": compiled_in_window == 0,
    }
    if job.get("loss_must_fall"):
        checks["loss_fell"] = loss_fell
    detail("checks", {
        **checks, "loss_fell_observed": loss_fell,
        "first_unit": {"trainer_loss": first_loss, "reference_loss": ref_loss,
                       "trainer_move": first_move, "reference_move": ref_move,
                       "loss_rtol": reference.LOSS_RTOL,
                       "move_rtol": reference.MOVE_RTOL},
        "compiled_in_window": compiled_in_window,
    })
    detail("window", {
        "units": units, "seconds": window_s, "tau": tau,
        "samples_per_unit": samples_per_unit, "units_per_interval": group,
        "intervals_ms": [round(iv * 1e3, 3) for iv in intervals],
        "dirty": [j for j, d in enumerate(dirty) if d],
        "losses": [round(l, 5) for l in losses],
    })

    # -- after the window: memory, and what only a traced run needs ---------
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    detail("memory_stats", stats)
    sharding = topo.worker_sharding()
    shape_x = ((chips, tau, per_chip) if rounds else (chips * per_chip,))
    staged = lambda a: jax.ShapeDtypeStruct(
        shape_x + a.shape[1:], a.dtype, sharding=sharding)
    unit_mem = _unit_memory(trainer._round if rounds else trainer._step,
                            state, staged(x), staged(y))
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
            trainer.loss_fn,
            jax.tree.map(abstract, state.center if rounds else state.params),
            batch(x), batch(y))
        detail("flops_per_sample", flops_per_sample)

    return {
        "correct": all(checks.values()),
        "attempted": units,
        "failed": failed,
        "memory_peak_bytes": memory_peak,
        "setup_s": clock.t_open - ctx["t0"],
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
            "compile_s": compiled["seconds"],
            "intervals_s": intervals,
            "clean_intervals_s": clean,
            "input_host_s_unit": input_s,
            "flops_per_sample": flops_per_sample,
            "trace": reduced,
        },
    }
