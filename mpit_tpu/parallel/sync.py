"""Synchronous allreduce data parallelism.

Reference parity (SURVEY.md §3(d), BASELINE.json:8): per step each worker
computes a gradient on its batch shard, ``mpiT.Allreduce(grad, SUM)`` then
``grad /= size``, and a replicated optimizer applies the averaged gradient.

TPU-native design: one jit-compiled ``shard_map`` step over the worker mesh
axis — the batch is sharded on the leading axis, params/optimizer state are
replicated, and the gradient average is a single ``lax.pmean`` that XLA lowers
to an ICI all-reduce fused into the step (no host round trip per step, unlike
the reference's per-step MPI call from the Lua loop).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from mpit_tpu.comm.topology import topology as _current_topology
from mpit_tpu.comm.topology import Topology
from mpit_tpu.parallel import common
from mpit_tpu.utils.profiling import scope, span


class DataParallelTrainer:
    """Sync allreduce DP trainer for a flax model.

    Usage::

        topo = mpit_tpu.init()
        trainer = DataParallelTrainer(model, optax.sgd(0.1), topo)
        state = trainer.init_state(jax.random.key(0), sample_batch_x)
        state, metrics = trainer.step(state, x_global, y_global)
    """

    def __init__(
        self,
        model,
        optimizer: optax.GradientTransformation,
        topo: Optional[Topology] = None,
        loss_fn: Optional[Callable] = None,
        donate_state: bool = True,
        accum_steps: int = 1,
        jit_init: bool = False,
    ):
        """``jit_init``: make the state in one jitted program, born
        replicated, instead of op by op and then placed: for a model
        whose eager ``init`` is a forward pass too large to run that way.

        ``accum_steps``: gradient accumulation — each step's local
        batch is processed as that many sequential slices (``lax.scan``)
        whose gradients average before the one optimizer update. The
        math is EXACTLY the full-batch step (equal slice sizes, mean
        losses, and no model here carries batch statistics — GroupNorm/
        LayerNorm only), so it trades step latency for peak activation
        memory: effective batch B needs only B/accum_steps of forward
        state in HBM at once."""
        self.model = model
        self.optimizer = optimizer
        self.topo = topo if topo is not None else _current_topology()
        self.loss_fn = (
            loss_fn
            if loss_fn is not None
            else common.default_loss_fn(model.apply)
        )
        self.accum_steps = accum = int(accum_steps)
        self.donate_state = donate_state
        self.jit_init = jit_init

        axis = self.topo.worker_axis
        mesh = self.topo.mesh
        # a model may offer (params, x, y) -> (loss, counters) beside its
        # apply (expert layers' routing counts): the step's metrics then
        # carry the counters
        counting = (
            getattr(model, "loss_with_counters", None)
            if loss_fn is None else None
        )
        local_vg = common.accumulated_value_and_grad(
            counting or self.loss_fn, accum, has_aux=counting is not None
        )
        # the step's own value-and-gradient, for what compares it with a
        # reference (benchmark/drivers/train_lm.py)
        self._local_vg = local_vg

        def train_step(state: common.TrainState, x, y):
            loss, grads = local_vg(state.params, x, y)
            counters = {}
            if counting is not None:
                loss, counters = loss
            # the one collective of the step: grad average over workers
            with scope("grad_exchange"):
                grads = jax.lax.pmean(grads, axis)
                loss = jax.lax.pmean(loss, axis)
                counters = jax.lax.pmean(counters, axis)
            with scope("optimizer"):
                updates, opt_state = self.optimizer.update(
                    grads, state.opt_state, state.params
                )
                params = optax.apply_updates(state.params, updates)
            return (
                common.TrainState(
                    params=params, opt_state=opt_state, step=state.step + 1
                ),
                {"loss": loss, **counters},
            )

        self._step = jax.jit(
            jax.shard_map(
                train_step,
                mesh=mesh,
                in_specs=(P(), P(axis), P(axis)),
                out_specs=(P(), P()),
                check_vma=False,
            ),
            donate_argnums=(0,) if donate_state else (),
        )

        self._eval = common.build_count_loss_eval(model, self.topo)

    def init_state(self, rng, sample_x) -> common.TrainState:
        """Initialize replicated state. ``sample_x`` is a *per-worker* shaped
        batch (leading dim = per-worker batch); only shapes matter."""
        with span("mpit.setup.init_state"):
            create = lambda key, x: common.TrainState.create(
                self.model.init(key, x)["params"], self.optimizer
            )
            if self.jit_init:
                return common.placed_state(
                    create, self.topo.replicated_sharding(),
                    rng, jnp.asarray(sample_x),
                )
            state = create(rng, jnp.asarray(sample_x))
            # waited for, so that the span reads set-up done, not dispatched
            return jax.block_until_ready(
                jax.device_put(state, self.topo.replicated_sharding())
            )

    def _check(self, x) -> None:
        common.check_accum_batch(
            len(x), self.topo.num_workers, self.accum_steps
        )

    def step(self, state, x_global, y_global):
        """One sync-DP step on a global batch (leading dim divisible by W,
        per-worker shard divisible by accum_steps)."""
        self._check(x_global)
        state, metrics = self._step(state, x_global, y_global)
        common.bound_cpu_dispatch(self.topo, metrics)
        return state, metrics

    def evaluate(self, state, x, y, batch: int = 1024):
        """Full-dataset eval; returns (accuracy, mean_loss)."""
        correct, loss_sum, n = common.batched_count_eval(
            self._eval, state.params, x, y, batch, self.topo.num_workers
        )
        return correct / n, loss_sum / n

    def fit(
        self,
        batches,
        state,
        epochs: int = 1,
        log_every: int = 0,
        start_epoch: int = 0,
        skip_steps: int = 0,
        on_step=None,
        prefetch: int = 2,
    ):
        """Epoch loop over a :class:`mpit_tpu.data.Batches` — the shared
        :func:`common.synced_fit_loop` with the sync-DP sharding/check.
        Returns (state, last_metrics)."""
        return common.synced_fit_loop(
            self.topo, self._step, batches, state,
            sharding=self.topo.worker_sharding(),
            check=self._check,
            log_tag="sync-dp",
            epochs=epochs, log_every=log_every, start_epoch=start_epoch,
            skip_steps=skip_steps, on_step=on_step, prefetch=prefetch,
        )
