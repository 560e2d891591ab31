"""ZeRO-1 data parallelism: optimizer state sharded 1/W per device.

The memory pillar plain sync DP lacks: ``DataParallelTrainer`` replicates
optimizer state on every device, so Adam costs 2× params per chip no
matter how many chips there are. Here the flat parameter vector is cut
into W contiguous chunks and each device owns ONE chunk's optimizer
state (Rajbhandari et al., ZeRO stage 1 — arXiv:1910.02054):

- forward/backward run exactly as in sync DP (params replicated);
- the gradient average and sharding happen in one ``lax.psum_scatter``
  per step (half of the bandwidth-optimal allreduce, so the step moves
  no more bytes than plain DP's ``pmean``). Under gradient accumulation
  the scatter moves inside the fold — one per slice, same aggregate
  bytes, accum× the collective count — so the PERSISTENT gradient
  state is a 1/W chunk instead of a full param-sized pytree (the
  ZeRO-2 composition; each slice's backward still transiently builds
  one param-sized gradient);
- the optimizer updates only the local chunk (state leaves live sharded
  ``P(axis)`` — 1/W of Adam's mu/nu per device);
- ``lax.all_gather`` reassembles the updated flat vector (the other
  half of the allreduce) and the pytree is re-ravelled.

For ELEMENTWISE optimizers the chunked update equals the full-vector
update exactly — pinned against plain sync DP in tests — and the same
behavioral probe that protects the MoE trainer
(:func:`common.assert_elementwise_optimizer`) rejects cross-leaf
transforms here, where a per-chunk global-norm would silently differ
per device. Flat buffers reuse ``utils/params.flatten_params``
(≡ the reference's ``getParameters()`` view, SURVEY.md §2 comp. 4).
"""

from __future__ import annotations

import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from mpit_tpu import quant as _quant
from mpit_tpu.comm.collectives import quantized_psum_scatter
from mpit_tpu.comm.topology import topology as _current_topology
from mpit_tpu.comm.topology import Topology
from mpit_tpu.parallel import common
from mpit_tpu.utils.params import flatten_params


def dp_quant_from_env(env=None) -> str:
    """``MPIT_DP_QUANT`` (off|bf16|int8; default off) — the ZeRO
    gradient reduce-scatter's quantization mode."""
    env = os.environ if env is None else env
    mode = env.get("MPIT_DP_QUANT") or "off"
    if mode not in _quant.QUANT_MODES:
        raise ValueError(
            f"MPIT_DP_QUANT={mode!r}: expected one of {_quant.QUANT_MODES}"
        )
    return mode


class ZeroDataParallelTrainer:
    """Sync allreduce DP with ZeRO-1 sharded optimizer state.

    Usage (identical surface to :class:`DataParallelTrainer`)::

        topo = mpit_tpu.init()
        trainer = ZeroDataParallelTrainer(model, optax.adam(1e-3), topo)
        state = trainer.init_state(jax.random.key(0), sample_batch_x)
        state, metrics = trainer.step(state, x_global, y_global)

    ``state.opt_state`` leaves of parameter size live sharded over the
    worker axis; everything else matches plain sync DP.
    """

    def __init__(
        self,
        model,
        optimizer: optax.GradientTransformation,
        topo: Optional[Topology] = None,
        loss_fn: Optional[Callable] = None,
        donate_state: bool = True,
        accum_steps: int = 1,
        clip_norm: Optional[float] = None,
        quant: Optional[str] = None,
    ):
        """``accum_steps``: gradient accumulation, composable with the
        state sharding — both memory knobs together (activations / accum,
        optimizer state / W). ``clip_norm``: global-norm gradient
        clipping done mesh-correctly on the gradient chunks
        (:func:`common.clip_by_global_norm_in_mesh` — the psum over
        chunk sum-of-squares IS the full-vector norm, so this equals
        ``optax.clip_by_global_norm`` on unsharded sync DP exactly; the
        chain form itself is rejected by the elementwise probe below).
        ``quant`` (default: the ``MPIT_DP_QUANT`` knob): run the
        gradient reduce-scatter through
        :func:`comm.collectives.quantized_psum_scatter` — 1- or 2-byte
        codes on the wire, f32 accumulate. STATELESS (no error feedback
        — the persistent state here is deliberately 1/W-sized, and a
        full-width residual would undo that); the rounding is one
        bounded step per scatter, and the dynamics plane is the
        convergence guardrail (docs/WIRE.md)."""
        self.model = model
        self.optimizer = optimizer
        common.assert_elementwise_optimizer(
            optimizer, "ZeroDataParallelTrainer"
        )
        self.clip_norm = common.check_clip_norm(clip_norm)
        self.quant = dp_quant_from_env() if quant is None else quant
        if self.quant not in _quant.QUANT_MODES:
            raise ValueError(
                f"quant={self.quant!r}: expected one of {_quant.QUANT_MODES}"
            )
        self.topo = topo if topo is not None else _current_topology()
        self.loss_fn = (
            loss_fn
            if loss_fn is not None
            else common.default_loss_fn(model.apply)
        )
        self.accum_steps = accum = common.check_accum_steps(accum_steps)
        axis = self.topo.worker_axis
        mesh = self.topo.mesh
        w = self.topo.num_workers
        self._axis, self._mesh, self._w = axis, mesh, w
        self._donate = donate_state
        self._step = None  # built in init_state (needs the flat size)
        self._eval = common.build_count_loss_eval(model, self.topo)

    def _opt_spec(self, opt_state, padded: int):
        """P(axis) for flat parameter-sized leaves, replicated rest."""
        return jax.tree.map(
            lambda a: P(self._axis)
            if getattr(a, "shape", ()) == (padded,)
            else P(),
            opt_state,
        )

    def _build(self, params_template):
        axis, w = self._axis, self._w
        flat0, spec = flatten_params(params_template)
        n = flat0.size
        padded = -(-n // w) * w
        chunk = padded // w

        # optimizer state is born SHARDED: structure from eval_shape,
        # then a jit with out_shardings computes each leaf directly into
        # its 1/W placement — the full mu/nu never exist on one device
        # (materializing them first would OOM exactly the models ZeRO
        # exists for)
        abstract = jax.eval_shape(
            self.optimizer.init,
            jax.ShapeDtypeStruct((padded,), flat0.dtype),
        )
        opt_spec = self._opt_spec(abstract, padded)
        opt_shardings = jax.tree.map(
            lambda s: NamedSharding(self._mesh, s), opt_spec,
            is_leaf=lambda v: isinstance(v, P),
        )
        opt_state0 = jax.jit(
            lambda: self.optimizer.init(
                jnp.zeros((padded,), flat0.dtype)
            ),
            out_shardings=opt_shardings,
        )()
        state_spec = common.TrainState(
            params=jax.tree.map(lambda _: P(), params_template),
            opt_state=opt_spec,
            step=P(),
        )

        accum = self.accum_steps
        quant_mode = self.quant

        def _scatter(flat_g):
            # mode "off" IS lax.psum_scatter(tiled=True) — the raw path
            # byte-identical to the pre-quant trainer
            return quantized_psum_scatter(
                flat_g, axis_name=axis, mode=quant_mode
            ) / w

        def scattered_grad(params, x, y):
            """Mean-gradient CHUNK for this device.

            accum=1: one grad, one psum_scatter — half of the
            bandwidth-optimal allreduce, no extra bytes vs pmean.
            accum>1: the scatter moves INSIDE the accumulation fold
            (ZeRO-2 composed with accumulation): each slice's gradient
            is reduced-scattered immediately and only the (chunk,)
            accumulator persists across slices — the persistent gradient
            state shrinks from a full param-sized pytree to 1/W of one
            (each slice's backward still materializes one transient
            param-sized gradient), at the cost of one collective per
            slice instead of one per step. Mean of scattered slices ==
            scattered full-batch mean, exactly.
            """
            vg = jax.value_and_grad(self.loss_fn)
            if accum == 1:
                loss, grads = vg(params, x, y)
                flat_g, _ = flatten_params(grads)
                flat_g = jnp.pad(flat_g, (0, padded - n))
                return loss, _scatter(flat_g)
            xs = x.reshape(accum, x.shape[0] // accum, *x.shape[1:])
            ys = y.reshape(accum, y.shape[0] // accum, *y.shape[1:])

            def fold(carry, xy):
                loss_acc, shard_acc = carry
                l, g = vg(params, *xy)
                flat_g, _ = flatten_params(g)
                flat_g = jnp.pad(flat_g, (0, padded - n))
                gs = _scatter(flat_g)
                return (loss_acc + l, shard_acc + gs), None

            (loss, shard), _ = lax.scan(
                fold,
                (jnp.float32(0.0), jnp.zeros((chunk,), flat0.dtype)),
                (xs, ys),
            )
            return loss / accum, shard / accum

        clip_norm = self.clip_norm

        def train_step(state: common.TrainState, x, y):
            loss, g_shard = scattered_grad(state.params, x, y)
            if clip_norm is not None:
                # every device holds a disjoint chunk of the ONE flat
                # mean gradient (padding is zeros), so psum of chunk
                # sums-of-squares is exactly the full-vector norm
                g_shard, _ = common.clip_by_global_norm_in_mesh(
                    g_shard, clip_norm, axis
                )
            flat_p, _ = flatten_params(state.params)
            flat_p = jnp.pad(flat_p, (0, padded - n))
            rank = lax.axis_index(axis)
            p_shard = lax.dynamic_slice(flat_p, (rank * chunk,), (chunk,))
            updates, opt_state = self.optimizer.update(
                g_shard, state.opt_state, p_shard
            )
            new_shard = optax.apply_updates(p_shard, updates)
            # the other half of the allreduce: reassemble the params
            flat_new = lax.all_gather(new_shard, axis, tiled=True)
            params = spec.unravel(flat_new[:n])
            return (
                common.TrainState(
                    params=params, opt_state=opt_state,
                    step=state.step + 1,
                ),
                {"loss": lax.pmean(loss, axis)},
            )

        self._step = jax.jit(
            jax.shard_map(
                train_step,
                mesh=self._mesh,
                in_specs=(state_spec, P(axis), P(axis)),
                out_specs=(state_spec, P()),
                check_vma=False,
            ),
            donate_argnums=(0,) if self._donate else (),
        )
        return opt_state0, opt_spec

    def init_state(self, rng, sample_x) -> common.TrainState:
        """Replicated params; optimizer state born in its 1/W shards
        (never whole on any device — see :meth:`_build`)."""
        variables = self.model.init(rng, jnp.asarray(sample_x))
        params = variables["params"]
        opt_state0, _ = self._build(params)
        replicated = NamedSharding(self._mesh, P())
        return common.TrainState(
            params=jax.device_put(
                params, jax.tree.map(lambda _: replicated, params)
            ),
            opt_state=opt_state0,  # already placed by _build
            step=jax.device_put(jnp.zeros((), jnp.int32), replicated),
        )

    def step(self, state, x_global, y_global):
        """One ZeRO-1 step on a global batch (divisible by W; per-worker
        shard divisible by accum_steps)."""
        common.check_accum_batch(
            len(x_global), self._w, self.accum_steps
        )
        if self._step is None:
            _ = self._build(state.params)
        state, metrics = self._step(state, x_global, y_global)
        common.bound_cpu_dispatch(self.topo, metrics)
        return state, metrics

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
        """Epoch loop — the shared :func:`common.synced_fit_loop`."""
        if self._step is None:
            _ = self._build(state.params)
        w, accum = self._w, self.accum_steps
        return common.synced_fit_loop(
            self.topo, self._step, batches, state,
            sharding=self.topo.worker_sharding(),
            check=lambda x: common.check_accum_batch(len(x), w, accum),
            log_tag="zero-dp",
            epochs=epochs, log_every=log_every, start_epoch=start_epoch,
            skip_steps=skip_steps, on_step=on_step, prefetch=prefetch,
        )

    def evaluate(self, state, x, y, batch: int = 1024):
        """Full-dataset eval; returns (accuracy, mean_loss)."""
        correct, loss_sum, n = common.batched_count_eval(
            self._eval, state.params, x, y, batch, self._w
        )
        return correct / n, loss_sum / n
