"""The driver of a cell whose model is described by ``TrainConfig.arch`` and
checked against its own plain reference (``lib/reference_laguna_s.py``).

``drivers/train.py`` checks a first unit against ``lib/reference.first_unit``,
which uses the trainer's own loss and holds a second copy of parameters and
optimizer state: it proves the wiring and not the model's arithmetic, and at
811M parameters the second state does not fit the chip. This driver builds
the same job with the same program functions, takes the clock, the tracer,
the batch timer and the configuration from that file, and returns the same
record, so every accepted reader works on it; what differs is the comparison
that decides ``correct``, made in this order so that it fits:

1. the parameters alone (``model.init`` from ``--seed``, no optimizer state);
2. the system's own expert choices on the first batch (one forward pass), the
   reference's loss and gradient there a layer at a time with those choices
   (float32, ``highest``), moved to the host, and the share of (token, choice)
   pairs in which the reference's own top-k differs;
3. the system's gradient at the timed batch and widths, from the step
   program's own value-and-gradient function (``trainer._local_vg``, the
   function the timed step calls), against the reference's by leaf group;
4. the trainer's state (the same key: the same parameters, checked), then two
   units through ``fit`` on that batch: the trainer's loss against the
   reference's, and the parameters' move against the move the same optax
   optimizer makes from the reference's gradient. Two units, because the
   preset's warm-up starts at a learning rate of 0: the first moves nothing.

The window is then ``drivers/train.py``'s, on the job's own batches. Every
step of it must also route to the experts held between a third of and three
times the rows uniform routing sends them (``rows_held_band``): a router that leaves
the experts held, or heaps every token on one, changes what the cell
measures without a word from any other check.

``setup_s`` leaves out the seconds of steps 1 to 3 and of the move's
comparison (``check_s``: the float32 reference's seven programs compile for
some 200 s on the chip), and ``compile_s`` the compilation inside them: they
are the yardstick's cost, not the job's set-up.

Tolerances (``LIMITS``), each with its reason. Two readings set each: what the
system read on the chip over its seeds, and what two faults read when pushed
through the same ``decide`` (``scripts/laguna_controls.py``, on the chip, one
seed): the reference itself with every product's operands rounded to float8
(e4m3), the nearest precision below the configuration's bfloat16, and the
step's own value-and-gradient function given half of the tokens. Both have to
come out as not correct, and do (all in PERF.md section 6, PR 27).

``loss_rtol`` 2e-3, the accepted cells' (``lib/reference.py``): the system
read 1e-6 to 1e-4 on the chip over its seeds. float8 moves the loss by 1e-4
and half of the tokens by 6e-4, so the loss tells a wiring fault, neither a
precision nor a shortened batch.

``grad_rtol`` 0.2 by leaf group, ``|g_system - g_reference| / |g_reference|``
over the group's leaves, and ``grad_rtol_sparse`` 0.3 for the experts (and a
router that trains): bfloat16 compute read 2.9% (head) to 4.4% (embedding) on
the chip over its seeds and 8.1-9.8% for the experts (320 tokens an expert
average less rounding away than 8,192); float8 read 60% for the head and 97%
to 100% for every other group, half of the tokens 98% to 101% for every group.
This is the comparison that fails a lower precision, and it is made on the
function the timed step calls. A group whose gradient is 0 on both sides (the
router under ``moe_routing_no_grad``) reads 0.

``move_rtol`` 0.65 on ``|move_system - move_reference| / |move_reference|``:
AdamW's first moving step from two equal gradients is ``-lr (g / (|g| + eps)
+ wd p)``, a sign step, so the move keeps none of the gradient's size and
differs exactly where rounding flips the sign of a gradient element near 0:
a relative error ``2 sqrt(f)`` for a flipped share ``f``. The chip read 0.255
to 0.273 (1.8% of the elements, what 4% gradient noise flips); float8 read
1.20, half of the tokens 0.97; a state left unchanged reads 1. A first reading
over a tenth is therefore no fault here, and the limit sits nearer 1 than the
reading because fresh seeds read higher, not lower.

``mismatch_max`` 0.05: the share of (token, choice) pairs whose expert the
float32 reference would not have chosen on the same input, the worst layer;
the chip read 1.5% (first sparse layer) to 2.7% (last), the worst layer
2.5-2.7% over its seeds: near-ties among 256 softmax scores flip under
bfloat16 hidden states. The tight comparisons above are made with the
reference given the system's choices.

``rows_held_band`` (1/3, 3): the rows routed to the experts held, mean over
the sparse layers, every step of the window, over the rows uniform routing
sends (2,560). Sound runs read 0.53 to 1.67 over nine seeds (the swing comes
while the hidden states' common part arrives, steps 30 to 45); a router left
to this share's partial gradient read 0.035 by step 25, one with frozen
weights 0.2 by step 35 and 0.07 later. The lower edge lies between 0.53 and
0.2; nothing has read above 1.67, and past 8 rows are dropped, which has its
own check.

Rows past the dispatch buffer's bound must read 0 in every step: the
reference drops nothing.
"""

import dataclasses
import math
import os
import shutil
import statistics
import time

from benchmark.drivers.train import (
    TimedBatches, Tracer, UnitClock, _Stop, _unit_memory, build_config,
)
from benchmark.lib import flops, timing, trace_reduce, traffic
from benchmark.lib import reference_laguna_s as reference

#: the limits at the published widths; a job's ``limits`` block replaces
#: single ones (only the rehearsal has one: layers 32 wide average bfloat16
#: rounding over a hundredth of the terms, and read several times higher)
LIMITS = {"loss_rtol": 2e-3, "move_rtol": 0.65, "mismatch_max": 0.05,
          "grad_rtol": 0.2, "grad_rtol_sparse": 0.3,
          "rows_held_band": (1 / 3, 3.0)}
COUNTERS = ("moe_rows_held", "moe_load_max_over_mean", "moe_rows_dropped")


def arch_of(config: dict) -> dict:
    """The architecture as it is run: the configuration file's top-level
    keys that the source's config has, and the share this chip holds."""
    return {**{k: config[k] for k in config["source_config"]},
            **config.get("share", {})}


def leaf_group(path: str) -> str:
    """``['Block_2']['moe_w_up']`` -> the group it is compared in."""
    leaf = path.rstrip("]'").rsplit("'", 1)[-1]
    if leaf in ("attn_norm", "ffn_norm", "final_norm"):
        return "norms"
    if leaf in ("wq", "wk", "wv", "wo", "wg"):
        return "attention"
    if leaf == "moe_router":
        return "router"
    if leaf.startswith("moe_w"):
        return "experts"
    if leaf.startswith("shared_w"):
        return "shared"
    if leaf in ("w_gate", "w_up", "w_down"):
        return "dense_ffn"
    return {"embedding": "embedding", "head": "head"}[leaf]


class CountingClock(UnitClock):
    """``UnitClock`` that also keeps each unit's metrics (scalars)."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.metrics = []

    def __call__(self, done, state, metrics):
        self.metrics.append(metrics)
        super().__call__(done, state, metrics)


class Stopwatch:
    """Host seconds, and the compile meter's seconds, spent inside its
    ``with`` blocks, summed."""

    def __init__(self, meter):
        self.meter, self.seconds, self.compile_seconds = meter, 0.0, 0.0

    def __enter__(self):
        self._t, self._c = time.perf_counter(), self.meter.summary()["seconds"]

    def __exit__(self, *exc):
        self.seconds += time.perf_counter() - self._t
        self.compile_seconds += self.meter.summary()["seconds"] - self._c


class OneBatch:
    """The ``data.Batches`` protocol over one batch, given again and again:
    what the first units are fed, so that one reference gradient serves
    both of them."""

    def __init__(self, x, y, steps=8):
        self.x, self.y, self.steps = x, y, steps

    def steps_per_epoch(self) -> int:
        return self.steps

    def epoch(self, epoch_index: int):
        for _ in range(self.steps):
            yield self.x, self.y


def rel_err(diff_sq: float, norm_sq: float) -> float:
    """``|a - b| / |b|`` from the two sums of squares; 0 where both vanish
    (a frozen router's gradient is 0 on both sides), else infinite."""
    if norm_sq:
        return math.sqrt(diff_sq / norm_sq)
    return 0.0 if diff_sq == 0 else math.inf


def _paths(tree):
    import jax

    return [(jax.tree_util.keystr(p), leaf) for p, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def gradient_errors(grads, ref_grads):
    """``|g - g_reference| / |g_reference|`` by leaf group, and by leaf with
    the reference's norm first."""
    import jax
    import jax.numpy as jnp

    sq = jax.jit(lambda a, b: (jnp.sum(jnp.square(a - b)),
                               jnp.sum(jnp.square(b))))
    by_group, by_leaf = {}, {}
    for (path, g), (_, r) in zip(_paths(grads), _paths(ref_grads)):
        diff, norm = (float(v) for v in sq(jnp.asarray(g), jnp.asarray(r)))
        have = by_group.setdefault(leaf_group(path), [0.0, 0.0])
        have[0] += diff
        have[1] += norm
        by_leaf[path] = [math.sqrt(norm), rel_err(diff, norm)]
    return ({g: rel_err(d, n) for g, (d, n) in sorted(by_group.items())},
            by_leaf)


def reference_check(model, trainer, arch, key, bx, by, detail):
    """Steps 1 to 3 of the module's docstring. Returns the start parameters
    and the reference's gradient (both on the host), its loss and the
    numbers compared so far."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    bx, by = jnp.asarray(bx), jnp.asarray(by)
    params = jax.jit(lambda k, x: model.init(k, x)["params"])(key, bx)
    _, sown = jax.jit(lambda p, x: model.apply(
        {"params": p}, x, mutable=["routing"]))(params, bx)
    layers = arch["num_hidden_layers"]
    routing = sown.get("routing", {})
    choices = [routing[f"Block_{l}"]["experts"][0]
               if f"Block_{l}" in routing else None for l in range(layers)]
    ref_loss, ref_grads, own = reference.loss_and_grad_by_layer(
        params, bx, by, arch, experts_held=arch["num_experts"],
        expert_offset=arch.get("expert_offset", 0), choices=choices,
        to_host=True)
    k = arch["num_experts_per_tok"]
    differ = [
        float(1.0 - jnp.mean(jnp.any(
            c.reshape(-1, k, 1) == o.reshape(-1, 1, k), axis=-1)))
        for c, o in zip(choices, own) if c is not None
    ]

    # the step program's own value-and-gradient function, not a second
    # loss: what is compared is what the timed step differentiates
    _, sys_grads = jax.jit(trainer._local_vg)(params, bx, by)
    grad_err, by_leaf = gradient_errors(sys_grads, ref_grads)
    del sys_grads
    start = jax.device_get(params)
    detail("reference", {
        "loss": float(ref_loss), "grad_rel_err_by_group": grad_err,
        "routing_mismatch_by_layer": differ,
        # each leaf's reference gradient norm and relative error
        "grad_by_leaf": by_leaf,
        "parameters": int(sum(np.size(l) for l in jax.tree.leaves(start))),
    })
    return start, ref_grads, float(ref_loss), grad_err, max(differ, default=0.0)


def two_steps(opt, p0, g):
    """One leaf after two updates of ``opt`` from the gradient ``g``."""
    import optax

    tree = {"w": p0}
    state = opt.init(tree)
    for _ in range(2):
        updates, state = opt.update({"w": g}, state, tree)
        tree = optax.apply_updates(tree, updates)
    return tree["w"]


def _local(a):
    """This process's copy of a replicated array (or the array itself)."""
    import jax.numpy as jnp

    return (a.addressable_data(0) if hasattr(a, "addressable_data")
            else jnp.asarray(a))


def move_check(opt, start, ref_grads, params):
    """``|move - expected| / |expected|``, ``|move|`` and ``|expected|``
    over every leaf: the expected move is what ``opt`` makes of the
    reference's gradient applied twice (two units on one batch), a leaf at a
    time so that no second optimizer state is held."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def leaf(p0, g, p2):
        expected = two_steps(opt, p0, g) - p0
        return (jnp.sum(jnp.square((p2 - p0) - expected)),
                jnp.sum(jnp.square(expected)), jnp.sum(jnp.square(p2 - p0)))

    diff = norm = moved = 0.0
    for (_, p0), (_, g), (_, p2) in zip(
            _paths(start), _paths(ref_grads), _paths(params)):
        d, n, m = (float(v) for v in leaf(
            jnp.asarray(p0), jnp.asarray(g), _local(p2)))
        diff, norm, moved = diff + d, norm + n, moved + m
    return rel_err(diff, norm), math.sqrt(moved), math.sqrt(norm)


def decide(read: dict, limits: dict, loss_must_fall: bool) -> dict:
    """Every check that decides ``correct``, from the numbers a run read
    (``read``, as ``run`` builds it) and the limits. One function, so that a
    control (``scripts/laguna_controls.py``: the reference at a lower
    precision, half of the tokens left out) is judged by the very code a
    run is."""
    agree = lambda got, want, rtol: bool(
        math.isfinite(got) and abs(got - want) <= rtol * abs(want))
    sparse = ("router", "experts")
    low, high = limits["rows_held_band"]
    checks = {
        "same_start": bool(read["same_start"]),
        "first_unit_loss": all(
            agree(l, read["reference_loss"], limits["loss_rtol"])
            for l in read["first_losses"]),
        "gradient_by_group": all(
            err <= limits["grad_rtol_sparse" if g in sparse else "grad_rtol"]
            for g, err in read["grad_rel_err_by_group"].items()),
        "first_units_move": bool(
            read["move_rel_err"] <= limits["move_rtol"]
            and read["move_norm"] > 0),
        "routing_mismatch": read["routing_mismatch"] <= limits["mismatch_max"],
        "no_row_dropped": read["rows_dropped"] == 0,
        "rows_held_in_band": all(
            low * read["rows_expected"] <= r <= high * read["rows_expected"]
            for r in read["rows_held"]),
        "losses_finite": read["losses_not_finite"] == 0,
        "nothing_compiled_in_window": read["compiled_in_window"] == 0,
    }
    if loss_must_fall:
        checks["loss_fell"] = bool(read["loss_fell"])
    return checks


def kernel_shapes(arch: dict, per_chip: int, seq_len: int) -> dict:
    """What ``lib/lm_kernels`` needs of each flash kernel family the step
    runs (bfloat16 operands)."""
    n = arch["num_hidden_layers"]
    heads = dict(zip(arch["layer_types"][:n],
                     arch["num_attention_heads_per_layer"][:n]))
    shape = lambda kind, window: {
        "batch": per_chip, "heads": heads[kind],
        "kv_heads": arch["num_key_value_heads"], "t": seq_len,
        "d": arch["head_dim"], "window": window, "itemsize": 2}
    out = {}
    if "sliding_attention" in heads:
        out["flash_window"] = shape("sliding_attention", arch["sliding_window"])
    if "full_attention" in heads:
        out["flash_causal"] = shape("full_attention", None)
    return out


def run(ctx) -> dict:
    import jax
    import numpy as np

    import mpit_tpu
    from mpit_tpu import run as program
    from mpit_tpu.data import Batches

    args, detail, meter = ctx["args"], ctx["detail"], ctx["meter"]
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
            model, trainer, arch, key, bx, by, detail)
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
                       if name in m] for name in COUNTERS}
    quarter = max(units // 4, 1)
    loss_fell = (statistics.fmean(losses[-quarter:])
                 < statistics.fmean(losses[:quarter]))
    failed = sum(not math.isfinite(l) for l in losses)
    limits = {**LIMITS, **job.get("limits", {})}
    dropped = sum(counters["moe_rows_dropped"]) + sum(
        float(m.get("moe_rows_dropped", 0.0)) for m in warm.metrics)
    held = arch.get("num_experts", 0)  # rows a layer under uniform routing
    rows_expected = (per_chip * cfg.seq_len * arch.get("num_experts_per_tok", 0)
                     * held / arch.get("num_routed_experts", held or 1))
    read = {
        "same_start": same_start, "first_losses": first_losses,
        "reference_loss": ref_loss, "grad_rel_err_by_group": grad_err,
        "move_rel_err": move_err, "move_norm": moved,
        "routing_mismatch": mismatch, "rows_dropped": dropped,
        "rows_held": counters["moe_rows_held"],
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
