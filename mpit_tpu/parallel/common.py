"""Shared trainer plumbing: train state, losses, batch sharding."""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax

from mpit_tpu.data.prefetch import prefetch_to_device
from mpit_tpu.utils.profiling import remember_unit, scope, span


@flax.struct.dataclass
class TrainState:
    """Replicated training state (params + optimizer state + step).

    The reference's analogue is the flat parameter vector each pclient held
    plus torch-optim state tables (SURVEY.md §2 comps. 4-5); here state is a
    pytree and flattening is only done where a flat buffer genuinely helps
    (PS transport), not for every update.
    """

    params: Any
    opt_state: Any
    step: jax.Array

    @classmethod
    def create(cls, params, optimizer: optax.GradientTransformation):
        return cls(
            params=params,
            opt_state=optimizer.init(params),
            step=jnp.zeros((), jnp.int32),
        )


def placed_state(make: Callable, shardings: Any, *args) -> Any:
    """``make(*args)`` as ONE jitted program whose outputs are born with
    ``shardings`` (a prefix of their tree), waited for, so that the caller's
    span reads set-up done, not dispatched. Made op by op instead, a
    model's ``init`` runs its forward one primitive at a time: dozens of
    sub-second programs that jax's persistent cache never keeps (its floor
    is 1 s), recompiled by every run. Every leaf comes out its own buffer,
    an argument returned unchanged included, so a program that donates
    the state may take it whole."""
    return jax.block_until_ready(
        jax.jit(make, out_shardings=shardings)(*args)
    )


def cross_entropy_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    with scope("loss"):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()


def default_loss_fn(apply_fn: Callable) -> Callable:
    """(params, x, y) -> scalar loss, for classification models."""

    def loss_fn(params, x, y):
        logits = apply_fn({"params": params}, x)
        return cross_entropy_loss(logits, y)

    return loss_fn


def assert_elementwise_optimizer(
    optimizer: optax.GradientTransformation, context: str
) -> None:
    """Reject optimizers whose per-leaf update depends on OTHER leaves.

    Trainers that run ``optimizer.update`` inside ``shard_map`` on
    device-varying gradients (expert-parallel MoE) silently desynchronize
    replicated leaves under cross-leaf transforms: ``clip_by_global_norm``
    computes a different norm on every device, so the replicated leaves
    receive different updates and the replicas drift — no error, just
    corruption. (Trainers that pmean gradients before the update, and the
    GSPMD tensor-parallel trainer whose update runs under jit where XLA
    inserts the cross-device norm collectives itself, are NOT subject.)

    Detection is behavioral, not by name: probe the optimizer with
    gradient trees differing only in leaf ``b`` — once scaled (large
    magnitudes, so realistic global-norm thresholds trip) and once with
    ``b`` poisoned to NaN (so all-finite gates like
    ``optax.apply_if_finite`` trip) — and reject if leaf ``a``'s update
    changes. Elementwise transforms (sgd, momentum, adam, adamw,
    per-leaf clip, ...) pass bitwise. Best-effort by nature: coupling
    that activates only beyond the probed magnitudes (say a clip
    threshold above 4e8) still slips through, and optimizers the probe
    cannot run (e.g. ``optax.masked`` bound to the real param
    structure) are let through — the hazard stays documented on the
    trainer either way.
    """
    probe = {
        "a": jnp.full((2,), 1e8, jnp.float32),
        "b": jnp.full((2,), 1e8, jnp.float32),
    }
    try:
        st = optimizer.init(probe)
        u1, _ = optimizer.update(dict(probe), st, probe)
        u2, _ = optimizer.update(
            {"a": probe["a"], "b": probe["b"] * 3.0}, st, probe
        )
        u3, _ = optimizer.update(
            {"a": probe["a"], "b": jnp.full((2,), jnp.nan)}, st, probe
        )
    except Exception:
        return
    ua = np.asarray(u1["a"])
    if not (
        np.array_equal(ua, np.asarray(u2["a"]))
        and np.array_equal(ua, np.asarray(u3["a"]))
    ):
        raise ValueError(
            f"{context} requires an ELEMENTWISE optimizer: this one's "
            "update for a leaf depends on other leaves' gradients "
            "(global-norm clipping?), which silently desynchronizes "
            "replicated parameters when the update runs on "
            "device-varying gradients inside shard_map. Use per-leaf "
            "clipping (optax.clip, optax.clip_by_block_rms) instead."
        )


def check_clip_norm(clip_norm):
    """The ONE clip_norm guard (MoE and ZeRO trainer constructors)."""
    if clip_norm is not None and clip_norm <= 0:
        raise ValueError(f"clip_norm={clip_norm} must be > 0")
    return clip_norm


def clip_by_global_norm_in_mesh(
    grads, max_norm: float, axis: str, is_sharded=None
):
    """Global-norm gradient clipping that is CORRECT inside shard_map —
    the safe counterpart to the cross-leaf transforms
    :func:`assert_elementwise_optimizer` rejects.

    The true global norm is assembled mesh-wide: device-varying leaves
    (``is_sharded(path)`` true, e.g. expert shards or ZeRO gradient
    chunks) contribute their local sum-of-squares through a ``psum``
    over ``axis``; replicated leaves are identical everywhere and count
    once outside it. Every device therefore computes the SAME norm and
    the same scale — no replica drift. ``is_sharded=None`` treats every
    leaf as device-varying (the flat-chunk case).

    The scale rule is exactly ``optax.clip_by_global_norm``'s
    (``g * max_norm / norm`` when ``norm > max_norm``, identity
    otherwise), so a sharded run clips bit-for-bit like a dense run of
    the same model under the optax transform — pinned by the trainer
    equivalence tests.

    Returns ``(clipped_grads, global_norm)``.
    """
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    shard_sq = jnp.float32(0.0)
    repl_sq = jnp.float32(0.0)
    for path, g in leaves:
        sq = jnp.sum(jnp.square(g.astype(jnp.float32)))
        if is_sharded is None or is_sharded(path):
            shard_sq = shard_sq + sq
        else:
            repl_sq = repl_sq + sq
    norm = jnp.sqrt(jax.lax.psum(shard_sq, axis) + repl_sq)
    scale = jnp.where(norm > max_norm, max_norm / norm, 1.0)
    return (
        jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads),
        norm,
    )


def check_accum_steps(accum) -> int:
    """The ONE accum_steps guard (sync fold + ZeRO constructor)."""
    if int(accum) != accum or accum < 1:
        raise ValueError(f"accum_steps={accum} must be an integer >= 1")
    return int(accum)


def accumulated_value_and_grad(
    loss_fn: Callable, accum: int, has_aux: bool = False
) -> Callable:
    """(params, x, y) -> (loss, grads) — or ((loss, aux), grads) for a
    ``loss_fn`` that returns ``(loss, aux)``, which only ``accum=1``
    takes — processing the batch as ``accum``
    sequential ``lax.scan`` slices whose losses/gradients average —
    exactly the full-batch mean for equal slices (no model here carries
    batch statistics), at 1/accum of the peak activation memory. Used by
    the sync trainer; the ZeRO trainer carries its own fold because its
    accumulator is the reduce-scattered SHARD, not the full pytree
    (parallel/zero.py::scattered_grad). ``accum=1`` is the plain
    ``value_and_grad``. Validates via :func:`check_accum_steps`."""
    accum = check_accum_steps(accum)
    if accum == 1:
        return jax.value_and_grad(loss_fn, has_aux=has_aux)
    if has_aux:
        raise ValueError(
            f"accum_steps={accum}: a loss that returns counters beside "
            "itself is stepped without accumulation"
        )

    def value_and_grad(params, x, y):
        xs = x.reshape(accum, x.shape[0] // accum, *x.shape[1:])
        ys = y.reshape(accum, y.shape[0] // accum, *y.shape[1:])

        def fold(carry, xy):
            loss_acc, g_acc = carry
            l, g = jax.value_and_grad(loss_fn)(params, *xy)
            return (
                loss_acc + l,
                jax.tree.map(jnp.add, g_acc, g),
            ), None

        (loss, grads), _ = jax.lax.scan(
            fold,
            (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, params)),
            (xs, ys),
        )
        return loss / accum, jax.tree.map(lambda g: g / accum, grads)

    return value_and_grad


def check_accum_batch(
    global_batch: int, num_workers: int, accum: int
) -> None:
    """Sync-trainer batch check: divisible by W, per-worker shard
    divisible by the accumulation factor."""
    check_global_batch(global_batch, num_workers)
    if (global_batch // num_workers) % accum:
        raise ValueError(
            f"per-worker batch {global_batch // num_workers} not "
            f"divisible by accum_steps={accum}"
        )


def check_global_batch(global_batch: int, num_workers: int) -> int:
    if global_batch % num_workers != 0:
        raise ValueError(
            f"global batch {global_batch} not divisible by {num_workers} "
            "workers (SPMD shards must be equal)"
        )
    return global_batch // num_workers


def accuracy(logits: np.ndarray, labels: np.ndarray) -> float:
    return float((np.argmax(logits, -1) == labels).mean())


def synced_fit_loop(
    topo,
    step_fn,
    batches,
    state,
    *,
    sharding,
    check,
    log_tag: str,
    epochs: int = 1,
    log_every: int = 0,
    start_epoch: int = 0,
    skip_steps: int = 0,
    on_step=None,
    prefetch: int = 2,
):
    """The one per-step fit loop shared by the synchronous trainers
    (sync-DP and seq-parallel differ only in sharding, batch check, and
    log tag). Deterministic resume via ``start_epoch``/``skip_steps``
    (epoch index seeds the permutation); ``on_step(steps, state, metrics)``
    after every step; batches staged ``prefetch`` ahead with the step's own
    sharding. Returns (state, last_metrics).

    One iteration is tiled by four host spans (``utils/profiling.span``),
    each carrying ``unit``, the number of the step it works for:
    ``mpit.fit.group`` (the batch check), ``mpit.fit.stage`` (the
    ``device_put``, ``prefetch`` units early), ``mpit.fit.dispatch`` and
    ``mpit.fit.callback``."""
    metrics = None
    steps = 0
    grouped = 0
    # one host fetch up front so log lines can number steps across resume
    # without a per-step device round-trip (the pipeline trainer's state
    # is a dict, not a TrainState)
    step_leaf = state["step"] if isinstance(state, dict) else state.step
    base_step = int(step_leaf) if log_every else 0

    def step_batches(e, to_skip):
        nonlocal grouped
        for x, y in batches.epoch(e):
            if to_skip > 0:
                to_skip -= 1
                continue
            grouped += 1
            with span("mpit.fit.group", unit=grouped):
                check(x)
            yield x, y

    for e in range(start_epoch, epochs):
        to_skip = skip_steps if e == start_epoch else 0
        for x, y in prefetch_to_device(
            step_batches(e, to_skip), sharding, depth=prefetch,
            first_unit=steps + 1,
        ):
            with span("mpit.fit.dispatch", unit=steps + 1):
                state, metrics = step_fn(state, x, y)
                bound_cpu_dispatch(topo, metrics)
            if steps == 0:
                # after the dispatch, so the device is at work meanwhile; the
                # state that came out is what the next call takes
                remember_unit(step_fn, state, x, y)
            steps += 1
            if on_step is not None:
                with span("mpit.fit.callback", unit=steps):
                    on_step(steps, state, metrics)
            # gate on the HOST counter: `int(state.step)` every step would
            # force a device round-trip per step
            if log_every and steps % log_every == 0:
                print(
                    f"[{log_tag}] step={base_step + steps} "
                    f"loss={float(metrics['loss']):.4f}"
                )
    return state, metrics


def batched_count_eval(eval_fn, params, x, y, batch: int, group: int):
    """Run a (params, x, y) -> (correct_sum, loss_sum) eval over the set in
    ``group``-divisible batches (truncating the remainder). Returns
    (correct, loss_sum, n_examples_used)."""
    batch = (min(batch, len(x)) // group) * group or group
    n = (len(x) // batch) * batch
    if n == 0:
        raise ValueError("eval set smaller than one global batch")
    correct = 0
    loss_sum = 0.0
    for i in range(0, n, batch):
        c, l = eval_fn(params, x[i : i + batch], y[i : i + batch])
        correct += int(c)
        loss_sum += float(l)
    return correct, loss_sum, n


def bound_cpu_dispatch(topo, tree) -> None:
    """Serialize step dispatch on the virtual CPU mesh (no-op elsewhere).

    XLA:CPU's cross-module collective rendezvous deadlocks when several
    executions are in flight over the forced host-platform devices: async
    dispatch pipelines step k+1 while k runs, participants from different
    runs tangle on the shared pool, and one of N never arrives — the runtime
    then either hangs or aborts the process (rendezvous.cc "Exiting to
    ensure a consistent program state"). Observed on a 1-core host: an
    8-device psum loop died ~2 of 3 runs; with one execution in flight it
    passed every time. Real accelerator platforms pipeline correctly and
    stay fully async.
    """
    if topo.platform == "cpu" and topo.num_devices > 1:
        jax.block_until_ready(tree)


class RoundTrainer:
    """Shared machinery for τ-round trainers (EASGD, Downpour).

    Subclasses set, in __init__: ``topo``, ``tau``, ``_round`` (jitted round
    step taking (state, x(W,τ,B,...), y(W,τ,B,...))), ``_eval`` (jitted
    (params, x, y) -> summed-correct, or None when model-less), and implement
    ``center_params(state)``.
    """

    topo: Any
    tau: int
    _round: Callable
    _eval: Optional[Callable]

    _log_tag = "round"

    def center_params(self, state):
        raise NotImplementedError

    def round_batches(self, x_round: np.ndarray, y_round: np.ndarray):
        """Reshape τ stacked global batches (τ, W·B, ...) → (W, τ, B, ...)."""
        tau, w = self.tau, self.topo.num_workers
        if x_round.shape[0] != tau:
            raise ValueError(
                f"need {tau} stacked batches, got {x_round.shape[0]}"
            )
        b = check_global_batch(x_round.shape[1], w)
        xr = x_round.reshape(tau, w, b, *x_round.shape[2:]).swapaxes(0, 1)
        yr = y_round.reshape(tau, w, b, *y_round.shape[2:]).swapaxes(0, 1)
        return xr, yr

    def step(self, state, x_round, y_round):
        """One exchange round: τ local steps + the collective. Inputs are τ
        stacked global batches, shape (τ, W·B, ...)."""
        xr, yr = self.round_batches(np.asarray(x_round), np.asarray(y_round))
        state, metrics = self._round(state, xr, yr)
        bound_cpu_dispatch(self.topo, metrics)
        return state, metrics

    def rounds_per_epoch(self, batches) -> int:
        return batches.steps_per_epoch() // self.tau

    def fit(
        self,
        batches,
        state,
        epochs: int = 1,
        log_every: int = 0,
        start_epoch: int = 0,
        skip_rounds: int = 0,
        on_round=None,
        prefetch: int = 2,
    ):
        """Epoch loop grouping minibatches into τ-rounds. Per epoch, a
        trailing group smaller than τ is dropped (SPMD rounds have a fixed
        shape — and *per-epoch* dropping keeps the round↔epoch arithmetic
        exact for checkpoint/resume); raises if that leaves zero full rounds.

        Resume: ``start_epoch``/``skip_rounds`` re-enter the deterministic
        data schedule mid-stream — epoch ``e`` always reuses the same
        permutation (``Batches`` seeds by epoch index), and the first
        ``skip_rounds`` round-groups of ``start_epoch`` are consumed without
        training. ``on_round(rounds_done, state, metrics)`` fires after every
        trained round.

        ``prefetch``: round-groups staged onto the mesh ahead of the running
        step (``device_put`` is async, so transfer overlaps compute); 0 =
        stage synchronously (each staged group holds its full HBM footprint,
        so large-input configs may need 0). Skipped resume rounds are never
        staged.

        One iteration is tiled by four host spans (``utils/profiling.span``),
        each carrying ``unit``, the number of the round it works for:
        ``mpit.fit.group`` (stacking τ batches into a round-group),
        ``mpit.fit.stage`` (its ``device_put``, ``prefetch`` rounds early),
        ``mpit.fit.dispatch`` and ``mpit.fit.callback``."""
        if self.rounds_per_epoch(batches) == 0:
            raise ValueError(
                f"epoch of {batches.steps_per_epoch()} step(s) < "
                f"tau={self.tau}: no full rounds"
            )
        metrics = None
        rounds = 0
        grouped = 0
        dropped = 0

        def round_groups(e, to_skip):
            nonlocal dropped, grouped
            buf_x, buf_y = [], []
            for x, y in batches.epoch(e):
                buf_x.append(x)
                buf_y.append(y)
                if len(buf_x) < self.tau:
                    continue
                if to_skip > 0:
                    to_skip -= 1
                else:
                    grouped += 1
                    with span("mpit.fit.group", unit=grouped):
                        group = self.round_batches(
                            np.stack(buf_x), np.stack(buf_y)
                        )
                    yield group
                buf_x, buf_y = [], []
            dropped += len(buf_x)

        sharding = self.topo.worker_sharding()
        for e in range(start_epoch, epochs):
            to_skip = skip_rounds if e == start_epoch else 0
            for xr, yr in prefetch_to_device(
                round_groups(e, to_skip), sharding, depth=prefetch,
                first_unit=rounds + 1,
            ):
                with span("mpit.fit.dispatch", unit=rounds + 1):
                    state, metrics = self._round(state, xr, yr)
                    bound_cpu_dispatch(self.topo, metrics)
                if rounds == 0:
                    # after the dispatch, so the device is at work meanwhile;
                    # the state that came out is what the next call takes
                    remember_unit(self._round, state, xr, yr)
                rounds += 1
                if on_round is not None:
                    with span("mpit.fit.callback", unit=rounds):
                        on_round(rounds, state, metrics)
                if log_every and rounds % log_every == 0:
                    print(
                        f"[{self._log_tag}] round={rounds} "
                        f"loss={float(metrics['loss']):.4f}"
                    )
        if dropped:
            print(
                f"[{self._log_tag}] dropped {dropped} trailing batch(es) "
                f"across epochs (< tau={self.tau})"
            )
        return state, metrics

    def evaluate(self, state, x, y, batch: int = 1024) -> float:
        """Accuracy of the CENTER variable (the consensus model — what the
        reference's pserver held and reported)."""
        if self._eval is None:
            raise ValueError(
                "evaluate() requires a model; this trainer was built with "
                "model=None (loss-only math mode)"
            )
        w = self.topo.num_workers
        batch = (min(batch, len(x)) // w) * w or w
        n = (len(x) // batch) * batch
        if n == 0:
            raise ValueError(
                f"eval set of {len(x)} smaller than one per-worker sample "
                f"each across {w} workers"
            )
        correct = 0
        center = self.center_params(state)
        for i in range(0, n, batch):
            correct += int(
                self._eval(center, x[i : i + batch], y[i : i + batch])
            )
        return correct / n


def build_count_loss_eval(model, topo) -> Callable:
    """Jitted shard_map eval over the worker axis returning global
    (correct-count sum, loss sum) — the ONE copy shared by the
    replicated-param DP trainers (sync and ZeRO)."""
    import optax
    from jax.sharding import PartitionSpec as P

    axis = topo.worker_axis

    def eval_step(params, x, y):
        logits = model.apply({"params": params}, x)
        correct = jnp.sum(jnp.argmax(logits, -1) == y)
        loss_sum = optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).sum()
        return jax.lax.psum(correct, axis), jax.lax.psum(loss_sum, axis)

    return jax.jit(
        jax.shard_map(
            eval_step,
            mesh=topo.mesh,
            in_specs=(P(), P(axis), P(axis)),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )


def build_center_eval(model, topo) -> Optional[Callable]:
    """Jitted shard_map eval returning the summed correct-count across the
    worker axis, or None when model-less."""
    if model is None:
        return None
    from jax.sharding import PartitionSpec as P

    axis = topo.worker_axis

    def eval_step(params, x, y):
        logits = model.apply({"params": params}, x)
        correct = jnp.sum(jnp.argmax(logits, -1) == y)
        return jax.lax.psum(correct, axis)

    return jax.jit(
        jax.shard_map(
            eval_step,
            mesh=topo.mesh,
            in_specs=(P(), P(axis), P(axis)),
            out_specs=P(),
            check_vma=False,
        )
    )
