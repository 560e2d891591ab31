"""Tensor-parallel training via GSPMD sharding annotations.

Beyond-parity extension, and the OTHER TPU-native parallelism style: where
the shard_map trainers spell out every collective, this trainer only
annotates WHERE tensors live — Megatron-style column/row shardings on the
transformer's projection matrices over a ``tp`` mesh axis — and lets XLA's
SPMD partitioner insert the all-reduces (the scaling-book recipe: pick a
mesh, annotate shardings, let the compiler do the rest).

Sharding rules (the Megatron pairing, one all-reduce per block half):

- qkv projection (``Dense_0``): column-sharded ``P(None, "tp")`` — heads
  split across tp, attention computes per-shard with no communication;
- attention output (``Dense_1``): row-sharded ``P("tp", None)`` — XLA
  inserts the psum that merges head shards;
- MLP up (``Dense_2``): column-sharded, bias sharded with it;
- MLP down (``Dense_3``): row-sharded — second psum;
- embeddings, positions, LayerNorms: replicated.

Batch shards over the ``dp`` axis; gradients reduce over dp because the
loss mean spans the global batch (the partitioner derives this too — no
hand-written pmean anywhere in this file).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from mpit_tpu.comm.topology import topology as _current_topology
from mpit_tpu.comm.topology import Topology
from mpit_tpu.parallel import common

# (path-suffix substring, leaf name) -> PartitionSpec for the transformer's
# params; first match wins, default replicated. Momentum/optimizer leaves
# reuse the same rules because their tree paths end with the same param
# path (the rules only look at the trailing components).
_TP_RULES = (
    ("Dense_0", "kernel", P(None, "tp")),
    ("Dense_1", "kernel", P("tp", None)),
    ("Dense_2", "kernel", P(None, "tp")),
    ("Dense_2", "bias", P("tp")),
    ("Dense_3", "kernel", P("tp", None)),
    ("Dense_3", "bias", P()),
)


def _path_keys(path) -> list:
    keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
    return [k for k in keys if isinstance(k, str)]


def _spec_for_path(path) -> "tuple[P, Optional[int]]":
    """(spec, index of the matching rule) — (P(), None) when unmatched."""
    keys = _path_keys(path)
    for i, (module_name, leaf, spec) in enumerate(_TP_RULES):
        # exact segment equality: substring matching would let Dense_10
        # silently take Dense_1's row sharding
        if leaf in keys[-1:] and any(k == module_name for k in keys[:-1]):
            return spec, i
    return P(), None


def _is_block_dense_kernel(keys: list) -> bool:
    """A Dense kernel inside a transformer Block — the leaves tensor
    parallelism exists to shard. One of these matching NO rule means the
    model drifted from the rule table (renamed/added Dense), and
    silently replicating it would quietly lose tp — hard-fail instead."""
    return (
        keys[-1:] == ["kernel"]
        and any(k.startswith("Block") for k in keys[:-1])
        and any("Dense" in k for k in keys[:-1])
    )


def tp_state_specs(state):
    """PartitionSpec pytree for a TrainState under the Megatron rules.

    Strict by construction: every Dense kernel inside a Block must match
    a rule, and every rule must match at least one leaf — renaming or
    adding a layer raises here instead of silently falling back to
    replicated (losing tensor parallelism with no error). Shared by the
    2-D tp trainer and the composed dp×tp×sp trainer.
    """
    matched: set = set()
    unmatched: list = []

    def assign(path, _):
        spec, idx = _spec_for_path(path)
        if idx is not None:
            matched.add(idx)
        else:
            keys = _path_keys(path)
            if _is_block_dense_kernel(keys):
                unmatched.append("/".join(keys))
        return spec

    tree = jax.tree_util.tree_map_with_path(assign, state)
    if unmatched:
        raise ValueError(
            "tensor-parallel rules cover Dense_0..Dense_3 inside each "
            f"Block, but these Dense kernels matched no rule: "
            f"{sorted(set(unmatched))}. The model's block structure "
            "drifted from _TP_RULES — update the rule table rather "
            "than silently replicating these weights."
        )
    missing = set(range(len(_TP_RULES))) - matched
    if missing:
        raise ValueError(
            "tensor-parallel rules matched no parameter at all for: "
            f"{[_TP_RULES[i][:2] for i in sorted(missing)]} — the "
            "model's layer names drifted from _TP_RULES; fix the "
            "table or the model."
        )
    return tree


def check_tp_divisibility(model, tp: int) -> None:
    """d_model / num_heads / d_ff must all split across the tp axis."""
    if getattr(model, "arch", None) is not None:
        raise ValueError(
            "tensor-parallel rules are written for the GPT-2 block "
            "(Dense_0..Dense_3); a model described by arch (wq, wk, wv, "
            "wo, SwiGLU and expert weights) has none yet"
        )
    d_model = getattr(model, "d_model", tp)
    for field, need in (
        ("d_model", d_model),
        ("num_heads", getattr(model, "num_heads", tp)),
        ("d_ff", getattr(model, "d_ff", 0) or 4 * d_model),
    ):
        if need % tp:
            raise ValueError(f"{field}={need} not divisible by tp={tp}")


class TensorParallelTrainer:
    """dp × tp training for :class:`TransformerLM` (dense-attention mode).

    Usage::

        topo = mpit_tpu.init(axis_names=("dp", "tp"), mesh_shape=(2, 4))
        model = TransformerLM(vocab_size=V)        # seq_axis=None: the
        trainer = TensorParallelTrainer(model, optax.sgd(0.1), topo)
        state = trainer.init_state(jax.random.key(0), x[:2])
        state, metrics = trainer.step(state, x_global, y_global)

    The step function contains NO collectives — they come from the
    sharding annotations alone. Requires ``d_model % tp == 0``,
    ``num_heads % tp == 0`` and ``d_ff % tp == 0``.

    Cross-leaf optimizers (``clip_by_global_norm`` etc.) are SAFE here,
    unlike in the shard_map MoE trainer: ``optimizer.update`` runs under
    jit on globally-sharded gradients, so the partitioner inserts the
    cross-device collectives the global norm needs — every replica sees
    the same scalar.
    """

    def __init__(
        self,
        model,
        optimizer: optax.GradientTransformation,
        topo: Optional[Topology] = None,
        loss_fn: Optional[Callable] = None,
        donate_state: bool = True,
    ):
        self.model = model
        self.optimizer = optimizer
        self.topo = topo if topo is not None else _current_topology()
        mesh = self.topo.mesh
        if len(mesh.axis_names) < 2 or mesh.axis_names[1] != "tp":
            raise ValueError(
                "TensorParallelTrainer needs a mesh whose second axis is "
                "'tp', e.g. mpit_tpu.init(axis_names=('dp','tp'), "
                f"mesh_shape=(B, T)); got axes {mesh.axis_names}"
            )
        if getattr(model, "seq_axis", None) is not None:
            raise ValueError(
                "tensor parallelism uses the dense-attention model "
                "(seq_axis=None); ring attention shards the sequence, "
                "not the weights"
            )
        if getattr(model, "moe_experts", 0):
            raise ValueError(
                "TensorParallelTrainer has no sharding rules for MoE "
                "expert weights (moe_* leaves would silently stay "
                "replicated, losing expert parallelism); use "
                "MoEParallelTrainer for moe_experts > 0"
            )
        check_tp_divisibility(model, int(mesh.shape["tp"]))
        self.batch_axis = mesh.axis_names[0]
        self.loss_fn = (
            loss_fn
            if loss_fn is not None
            else common.default_loss_fn(model.apply)
        )

        def train_step(state: common.TrainState, x, y):
            loss, grads = jax.value_and_grad(self.loss_fn)(state.params, x, y)
            updates, opt_state = self.optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
            return (
                common.TrainState(
                    params=params, opt_state=opt_state, step=state.step + 1
                ),
                {"loss": loss},
            )

        # no in_shardings: jit honors the committed shardings of its
        # arguments (init_state/data_sharding place them), and the
        # partitioner propagates from there
        self._step = jax.jit(
            train_step, donate_argnums=(0,) if donate_state else ()
        )

        def eval_step(params, x, y):
            logits = self.model.apply({"params": params}, x)
            correct = jnp.sum(jnp.argmax(logits, -1) == y)
            loss_sum = optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).sum()
            return correct, loss_sum

        self._eval = jax.jit(eval_step)

    @property
    def tp_size(self) -> int:
        return int(self.topo.mesh.shape["tp"])

    def state_sharding(self, state):
        """NamedSharding pytree for a TrainState under the Megatron rules
        (strict — see :func:`tp_state_specs`)."""
        mesh = self.topo.mesh
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s), tp_state_specs(state),
            is_leaf=lambda v: isinstance(v, P),
        )

    def data_sharding(self) -> NamedSharding:
        """(B, T) token batches shard over dp, sequence replicated."""
        return NamedSharding(self.topo.mesh, P(self.batch_axis, None))

    def init_state(self, rng, sample_x) -> common.TrainState:
        """Replicated init, then leaves committed to their tp shardings
        (XLA re-lays the weights once here, never per step)."""
        variables = self.model.init(rng, jnp.asarray(sample_x))
        state = common.TrainState.create(variables["params"], self.optimizer)
        return jax.device_put(state, self.state_sharding(state))

    def step(self, state, x_global, y_global):
        """One tp-sharded step on a global (B, T) batch."""
        if len(x_global) % int(self.topo.mesh.shape[self.batch_axis]):
            raise ValueError(
                f"global batch {len(x_global)} not divisible by "
                f"dp={self.topo.mesh.shape[self.batch_axis]}"
            )
        sharding = self.data_sharding()
        x = jax.device_put(jnp.asarray(x_global), sharding)
        y = jax.device_put(jnp.asarray(y_global), sharding)
        state, metrics = self._step(state, x, y)
        common.bound_cpu_dispatch(self.topo, metrics)
        return state, metrics

    def evaluate(self, state, x, y, batch: int = 512):
        """Token-level accuracy and mean loss over a (N, T) eval set."""
        group = int(self.topo.mesh.shape[self.batch_axis])
        correct, loss_sum, n = common.batched_count_eval(
            self._eval, state.params, x, y, batch, group
        )
        tokens = n * x.shape[1]
        return correct / tokens, loss_sum / tokens
