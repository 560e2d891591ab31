"""EASGD / EAMSGD trainer, collective formulation.

Reference parity: goptim.easgd + pclient/pserver push-pull (SURVEY.md §2
comps. 3-5, §3(b)-(c)). The reference ran one *server process* holding the
center variable and clients that exchanged with it every τ steps over tagged
MPI messages. On TPU that protocol is re-expressed as a symmetric collective
round (SURVEY.md §5, backend item (i)): every worker keeps its own params,
the center is replicated state, and every τ local steps one fused psum
implements the server's entire recv-dispatch loop. The asynchrony the MPI
version got from message interleaving is preserved where it matters
mathematically — clients explore independently between rounds — while the
exchange itself rides ICI inside one jit step (no host, no per-message
round trips). For protocol-level asynchrony (stale pulls), see the
host-async mode in ``mpit_tpu.parallel.pserver``.

Layout: per-worker state is stored with a leading worker axis W sharded over
the mesh ("stacked" layout); inside shard_map each worker sees its slice.
A round step consumes (W, τ, B, ...) batches and runs τ local steps under
``lax.scan`` — so a whole communication period is ONE XLA computation.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from mpit_tpu.comm.topology import topology as _current_topology
from mpit_tpu import goptim
from mpit_tpu.comm.topology import Topology
from mpit_tpu.parallel import common
from mpit_tpu.utils.profiling import scope, span


@flax.struct.dataclass
class EASGDState:
    """worker_params/worker_opt have leading worker axis (sharded over dp);
    center is replicated."""

    worker_params: Any
    worker_opt: Any
    center: Any
    round: jax.Array  # replicated scalar: completed exchange rounds


def _stack(tree: Any, w: int) -> Any:
    return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (w, *a.shape)), tree)


def _take0(tree: Any) -> Any:
    return jax.tree.map(lambda a: a[0], tree)


def _put0(tree: Any) -> Any:
    return jax.tree.map(lambda a: a[None], tree)


class EASGDTrainer(common.RoundTrainer):
    """Elastic-averaging SGD over the worker mesh axis.

    Args:
      model: flax module (or None when a custom ``loss_fn`` over raw params
        is supplied together with ``init_params`` — used by the math tests).
      optimizer: the *local* optimizer (EAMSGD = pass momentum here).
      alpha: elastic coupling strength. The paper's stability bound for the
        symmetric round is 0 < α < 1/W for the center move; default follows
        the paper's β/W rule.
      tau: communication period (local steps per exchange round).
      exchange_dtype: compress the exchange collective to this dtype (e.g.
        ``jnp.bfloat16`` halves the bytes the psum moves over ICI/DCN; see
        ``goptim.summed_client_diffs``). None = exact full-precision.
    """

    def __init__(
        self,
        model,
        optimizer: optax.GradientTransformation,
        topo: Optional[Topology] = None,
        loss_fn: Optional[Callable] = None,
        alpha: Optional[float] = None,
        tau: int = 4,
        donate_state: bool = True,
        use_pallas: bool = False,
        exchange_dtype: Any = None,
    ):
        self.model = model
        self.optimizer = optimizer
        self.use_pallas = bool(use_pallas)
        self.exchange_dtype = exchange_dtype
        self.topo = topo if topo is not None else _current_topology()
        self.tau = int(tau)
        w = self.topo.num_workers
        # β = 0.9 rule from the EASGD paper: α = β / W keeps the center move
        # a convex combination.
        self.alpha = float(alpha) if alpha is not None else 0.9 / w
        self.loss_fn = (
            loss_fn
            if loss_fn is not None
            else common.default_loss_fn(model.apply)
        )
        axis = self.topo.worker_axis
        mesh = self.topo.mesh

        def round_step(state: EASGDState, x, y):
            # per-shard: worker_* enter with leading dim 1
            params = _take0(state.worker_params)
            opt = _take0(state.worker_opt)

            def local_step(carry, batch):
                p, o = carry
                bx, by = batch
                loss, g = jax.value_and_grad(self.loss_fn)(p, bx, by)
                with scope("optimizer"):
                    updates, o = self.optimizer.update(g, o, p)
                    p = optax.apply_updates(p, updates)
                return (p, o), loss

            (params, opt), losses = jax.lax.scan(
                local_step, (params, opt), (x[0], y[0])
            )
            with scope("elastic"):
                params, center = goptim.easgd_round(
                    params, state.center, self.alpha, axis,
                    use_pallas=self.use_pallas,
                    compress_dtype=self.exchange_dtype,
                )
            return (
                EASGDState(
                    worker_params=_put0(params),
                    worker_opt=_put0(opt),
                    center=center,
                    round=state.round + 1,
                ),
                {"loss": jnp.mean(jax.lax.pmean(losses, axis))},
            )

        state_specs = EASGDState(
            worker_params=P(axis),
            worker_opt=P(axis),
            center=P(),
            round=P(),
        )
        self._round = jax.jit(
            jax.shard_map(
                round_step,
                mesh=mesh,
                in_specs=(state_specs, P(axis), P(axis)),
                out_specs=(state_specs, P()),
                check_vma=False,
            ),
            donate_argnums=(0,) if donate_state else (),
        )

        self._eval = common.build_center_eval(model, self.topo)
        self._log_tag = "easgd"

    # -- state ------------------------------------------------------------

    def init_state(self, rng, sample_x=None, params: Any = None) -> EASGDState:
        """All workers and the center start from identical params (the
        reference broadcast the initial model the same way, via rank-0
        construction + bcast). One jitted program makes the whole state,
        each leaf born with its sharding; given ``params``, it takes them
        as its argument and ``model.init`` is skipped."""
        with span("mpit.setup.init_state"):
            w = self.topo.num_workers

            def make(params):
                return EASGDState(
                    worker_params=_stack(params, w),
                    worker_opt=_stack(self.optimizer.init(params), w),
                    center=params,
                    round=jnp.zeros((), jnp.int32),
                )

            shardings = EASGDState(
                worker_params=self.topo.worker_sharding(),
                worker_opt=self.topo.worker_sharding(),
                center=self.topo.replicated_sharding(),
                round=self.topo.replicated_sharding(),
            )
            if params is not None:
                return common.placed_state(make, shardings, params)
            # the barrier draws each leaf once, into one buffer that the
            # center and the stacked workers read: left to fuse, XLA draws
            # GPT-2's two embedding tables twice (the workers' copy in
            # another layout) and the TPU compile takes twice as long
            return common.placed_state(
                lambda key, x: make(jax.lax.optimization_barrier(
                    self.model.init(key, x)["params"]
                )),
                shardings, rng, jnp.asarray(sample_x),
            )

    def center_params(self, state: EASGDState):
        return state.center
