"""Controls of the comparison that decides ``correct`` in the cell
``zaya1_sync_1chip_8k``, on the chip, at the cell's widths and batch: faults
pushed through the driver's own ``decide`` and limits, each of which has to
come out as not correct (its loss may pass).

- ``float8``: the reference itself with both operands of every product
  outside the router rounded to float8 (e4m3), the nearest precision below
  the configuration's bfloat16, in the place of the system.
- ``no_conv``: the step program's own value-and-gradient function
  (``trainer._local_vg``) with CCA's two convolutions skipped (``z = u``:
  ``no_conv`` below, patched in where ``ops/cca.conv_mix`` stands).
- ``no_value_shift``: the same function with the value shift skipped (both
  halves of every value head from the token itself: ``no_value_shift``
  below, in the place of ``ops/cca.value_shift``).
- ``no_eda``: the same function with the router's depth averaging forced
  off (every layer's ``router_eda`` leaf set to 0 in the system's
  parameters; the reference keeps the seeded values).

Each fault is judged as the driver judges the system: its own expert choices
on the batch (one forward pass of the faulty program) are given to the float32
reference, which returns its loss, its gradient and its own choices; then the
loss, the gradient's error by leaf group, the error of the two-step move the
job's optimizer makes of that gradient, the worst layer's routing mismatch,
and ``decide``. The system itself comes first. One seed an argument:

    python3 scripts/zaya_controls.py 3200000089  # one chip

Prints one JSON line a fault. ``--tiny`` runs the cell's rehearsal sizes, to
try the script on the CPU.
"""

import importlib
import json
import os
import sys

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import mpit_tpu  # noqa: E402
from benchmark.drivers import train_lm, train_lm_ref  # noqa: E402
from benchmark.lib import traffic  # noqa: E402
from mpit_tpu import run as program  # noqa: E402
from mpit_tpu.ops import cca as cca_ops  # noqa: E402
from mpit_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402
from mpit_tpu.utils.config import TrainConfig  # noqa: E402

CELL = "zaya1_sync_1chip_8k"


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def no_conv(u, taps0, taps1):
    """``conv_mix``'s signature with both convolutions skipped."""
    return u.astype(jnp.float32)


def no_value_shift(v_now, v_back, kv_heads):
    """``value_shift``'s signature with the second half of each value head
    taken from the token itself, not the one before."""
    b, t, _ = v_now.shape
    heads = lambda a: a.reshape(b, t, kv_heads, -1)
    return jnp.concatenate([heads(v_now), heads(v_back)], axis=-1)


def main(seed: int, tiny: bool) -> None:
    enable_compile_cache()
    job = load("benchmark", "workloads", f"{CELL}.json")
    config = load("benchmark", "configs", f"{job['config']}.json")
    compare = config["comparison"]
    reference = importlib.import_module(
        f"benchmark.lib.{compare['reference']}")
    train = {**config["train_config"], **job["train_config"]}
    vocab, arch = config["vocab_size"], train_lm.arch_of(config)
    limits = {**train_lm.LIMITS, **compare.get("limits", {})}
    if tiny:  # the rehearsal's sizes, to try the script on the CPU
        job = job["rehearsal"]
        train, vocab = {**train, **job["train_config"]}, job["vocab_size"]
        arch = train.pop("arch")
        limits.update(job.get("limits", {}))
    per_chip = job["per_chip_batch"]
    cfg = TrainConfig(**train, arch=arch, global_batch=per_chip)
    topo = mpit_tpu.init()
    model = program._build_model(cfg, {"vocab_size": vocab},
                                 worker_axis=topo.worker_axis)
    opt = program.build_optimizer(cfg, job["total_updates"])
    trainer = program.build_trainer(cfg, model, opt, topo)
    x, y = traffic.make(seed, {"kind": "tokens", "pool": 8, "epoch_repeats": 1},
                        seq_len=cfg.seq_len, vocab_size=vocab)
    bx, by = jnp.asarray(x.pool[:per_chip]), jnp.asarray(y.pool[:per_chip])
    layers, k = arch["num_hidden_layers"], arch["num_experts_per_tok"]
    share = {"experts_held": arch[compare["experts_held_key"]],
             "expert_offset": arch.get("expert_offset", 0)}

    params = jax.jit(lambda key, t: model.init(key, t)["params"])(
        jax.random.key(seed % (2**31 - 1)), bx)
    start = jax.device_get(params)
    group = train_lm_ref.grouping(compare["leaf_groups"])

    def mismatch(choices, own):
        return max(float(1.0 - jnp.mean(jnp.any(
            c.reshape(-1, k, 1) == o.reshape(-1, 1, k), axis=-1)))
            for c, o in zip(choices, own))

    def judge(name, loss, grads, ref_loss, ref_grads, differ):
        grad_err, _ = train_lm_ref.gradient_errors(grads, ref_grads, group)
        moved = jax.jit(lambda p0, g: train_lm.two_steps(opt, p0, g))
        end = jax.tree.map(
            lambda p0, g: jax.device_get(moved(jnp.asarray(p0), jnp.asarray(g))),
            start, grads)
        move_err, move_norm, _ = train_lm.move_check(opt, start, ref_grads, end)
        read = {
            "same_start": True, "first_losses": [float(loss)] * 2,
            "reference_loss": float(ref_loss),
            "grad_rel_err_by_group": grad_err, "move_rel_err": move_err,
            "move_norm": move_norm, "routing_mismatch": differ,
            "rows_dropped": 0.0, "rows_held": [], "rows_expected": 0.0,
            "losses_not_finite": 0, "compiled_in_window": 0, "loss_fell": True,
        }
        checks = train_lm.decide(read, limits, False)
        print(json.dumps({
            "fault": name, "seed": seed, "correct": all(checks.values()),
            "failed_checks": sorted(c for c, ok in checks.items() if not ok),
            "loss": float(loss),
            "loss_rel_err": abs(float(loss) - float(ref_loss)) / float(ref_loss),
            "grad_rel_err_by_group": grad_err, "move_rel_err": move_err,
            "move_norm": move_norm, "routing_mismatch": differ,
            "reference_loss": float(ref_loss), "limits": limits}), flush=True)

    def system(name, sys_params):
        """The step program's own loss and gradient with whatever stands in
        ``ops/cca`` now, its choices, and the reference given them."""
        choices = train_lm_ref.system_choices(model, sys_params, bx, layers)
        ref_loss, ref_grads, own = reference.loss_and_grad_by_layer(
            params, bx, by, arch, **share, choices=choices, to_host=True)
        # a fresh jit: a fault patched into ops/cca is traced in
        (loss, _), grads = jax.jit(
            lambda *a: trainer._local_vg(*a))(sys_params, bx, by)
        judge(name, loss, jax.device_get(grads), ref_loss, ref_grads,
              mismatch(choices, own))
        return choices, ref_loss, ref_grads

    # the system itself first: the reading the faults stand beside
    choices, ref_loss, ref_grads = system("none", params)
    low_loss, low_grads, _ = reference.loss_and_grad_by_layer(
        params, bx, by, arch, **share, choices=choices,
        operand_dtype=jnp.float8_e4m3fn, to_host=True)
    judge("float8", low_loss, low_grads, ref_loss, ref_grads, 0.0)
    del low_grads, ref_grads

    for name, attr, fault in (("no_conv", "conv_mix", no_conv),
                              ("no_value_shift", "value_shift", no_value_shift)):
        whole = getattr(cca_ops, attr)
        setattr(cca_ops, attr, fault)
        try:
            system(name, params)
        finally:
            setattr(cca_ops, attr, whole)

    no_eda = {name: ({**block, "router_eda": jnp.zeros_like(
        block["router_eda"])} if name.startswith("Block_") else block)
        for name, block in params.items()}
    system("no_eda", no_eda)


if __name__ == "__main__":
    main(int(sys.argv[1]), tiny="--tiny" in sys.argv)
