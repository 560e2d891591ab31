"""The plain reference a cell's first unit is checked against.

A single-worker loop written out step by step: ``jax.value_and_grad`` of the
trainer's own loss, the same optax optimizer, ``tau`` steps on each worker's
own batches from the same initial parameters, then the EASGD paper's
symmetric elastic move in ``jax.numpy``. No ``shard_map``, no ``lax.scan``,
no collective, no donation: what it shares with the program is the model and
the loss, so it proves the wiring around them (which batch reaches which
worker, how many steps a round holds, the optimizer's state, ``alpha``, the
exchange) and not the model's own arithmetic, whose reference is a later
``model_config`` PR's.

Tolerances (beside the check, as the contract asks). Both sides compute the
same loss in the same dtype, so they differ only by how XLA fuses the scanned
and sharded program against the plain one: seen on the chip, losses agreed to
seven digits and moves to 2e-5 (refused run, 2026-09-27). ``LOSS_RTOL`` 2e-3
and ``MOVE_RTOL`` 2e-2 leave bfloat16 reduction order its room and still fail
every wiring fault: one step too few or too many moves a warm-up-scheduled
AdamW round by 40% or more, a wrong ``alpha`` or worker count scales the
center's move by a whole factor, and another worker's batch changes the loss
in the second digit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax

LOSS_RTOL = 2e-3
MOVE_RTOL = 2e-2


@jax.jit
def tree_distance(a, b) -> jax.Array:
    """L2 norm of ``a - b`` over every leaf, accumulated in float32."""
    sq = jax.tree.map(
        lambda p, q: jnp.sum(
            jnp.square(p.astype(jnp.float32) - q.astype(jnp.float32))
        ),
        a, b,
    )
    return jnp.sqrt(sum(jax.tree.leaves(sq)))


def first_unit(loss_fn, optimizer, params, worker_batches, alpha=None):
    """Run the reference for one unit on the default device.

    ``worker_batches[w]`` is worker ``w``'s list of ``(x, y)`` host batches:
    ``tau`` of them under EASGD, one under sync (where ``alpha`` is None and
    there is one "worker" holding the whole global batch). Returns the loss
    the trainer should report for the unit and the L2 norm of the move of
    the center (EASGD) or of the parameters (sync).
    """

    @jax.jit
    def local_step(p, o, x, y):
        loss, g = jax.value_and_grad(loss_fn)(p, x, y)
        updates, o = optimizer.update(g, o, p)
        return optax.apply_updates(p, updates), o, loss

    losses, pull = [], None  # pull: sum_i (x_i - c), a worker at a time
    for batches in worker_batches:
        p, o = params, optimizer.init(params)
        for x, y in batches:
            p, o, loss = local_step(p, o, jnp.asarray(x), jnp.asarray(y))
            losses.append(loss)
        diff = jax.tree.map(jnp.subtract, p, params)
        pull = diff if pull is None else jax.tree.map(jnp.add, pull, diff)
    loss = float(np.mean([float(l) for l in losses]))
    if alpha is None:
        return loss, float(tree_distance(p, params))
    # EASGD, symmetric round, both from the values before the move:
    #   x_i <- x_i - a (x_i - c);  c <- c + a * sum_i (x_i - c)
    # only the center's move is compared (a worker's is the same formula on
    # its own difference)
    center = jax.tree.map(lambda c, d: c + alpha * d, params, pull)
    return loss, float(tree_distance(center, params))


def agree(measured: float, reference: float, rtol: float) -> bool:
    return bool(
        np.isfinite(measured)
        and abs(measured - reference) <= rtol * max(abs(reference), 1e-30)
    )
