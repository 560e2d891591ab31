"""Controls of the comparison that decides ``correct`` in the cell
``nemotron3_nano_sync_1chip_8k``, on the chip, at the cell's widths and batch:
three faults pushed through the driver's own ``decide`` and limits, each of
which has to come out as not correct (its loss may pass).

- ``float8``: the reference itself with both operands of every product
  rounded to float8 (e4m3), the nearest precision below the configuration's
  bfloat16, in the place of the system.
- ``half_sequence``: the step program's own value-and-gradient function
  (``trainer._local_vg``) given only the first half of the tokens, as a
  step that left half of them out would compute.
- ``state_not_carried``: the same function with the Mamba-2 scan's states left
  where they are made (``ops/ssd.ssd(carry_state=False)``: every chunk of 128
  starts from a zero state; it takes the ``jax.numpy`` form, the kernels carry
  their state in scratch), the fault a chunked scan is most likely to have.

For each: the loss, the gradient's error by leaf group and the error of the
two-step move the job's optimizer makes of that gradient, all against the
float32 reference, then ``decide``. One seed an argument:

    chiprun -- python3 scripts/nemotron_controls.py 3200000033

Prints one JSON line a fault (PERF.md section 6 holds PR 32's readings).
``--tiny`` runs the cell's rehearsal sizes, to try the script on the CPU.
"""

import functools
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
from mpit_tpu.ops import ssd as ssd_ops  # noqa: E402
from mpit_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402
from mpit_tpu.utils.config import TrainConfig  # noqa: E402

CELL = "nemotron3_nano_sync_1chip_8k"


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


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

    params = jax.jit(lambda k, t: model.init(k, t)["params"])(
        jax.random.key(seed % (2**31 - 1)), bx)
    share = dict(
        experts_held=arch[compare["experts_held_key"]],
        expert_offset=arch.get("expert_offset", 0), to_host=True,
        choices=train_lm_ref.system_choices(
            model, params, bx, arch["num_hidden_layers"]))
    ref_loss, ref_grads, _ = reference.loss_and_grad_by_layer(
        params, bx, by, arch, **share)
    start = jax.device_get(params)
    group = train_lm_ref.grouping(compare["leaf_groups"])

    def fault(name, loss, grads):
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
            "move_norm": move_norm, "routing_mismatch": 0.0,
            "rows_dropped": 0.0, "rows_held": [], "rows_expected": 0.0,
            "losses_not_finite": 0, "compiled_in_window": 0, "loss_fell": True,
        }
        checks = train_lm.decide(read, limits, False)
        print(json.dumps({
            "fault": name, "seed": seed, "correct": all(checks.values()),
            "failed_checks": sorted(k for k, ok in checks.items() if not ok),
            "loss": float(loss),
            "loss_rel_err": abs(float(loss) - float(ref_loss)) / float(ref_loss),
            "grad_rel_err_by_group": grad_err, "move_rel_err": move_err,
            "move_norm": move_norm, "reference_loss": float(ref_loss),
            "limits": limits}), flush=True)

    low_loss, low_grads, _ = reference.loss_and_grad_by_layer(
        params, bx, by, arch, operand_dtype=jnp.float8_e4m3fn, **share)
    fault("float8", low_loss, low_grads)
    del low_grads

    half = cfg.seq_len // 2
    (half_loss, _), half_grads = jax.jit(trainer._local_vg)(
        params, bx[:, :half], by[:, :half])
    fault("half_sequence", half_loss, jax.device_get(half_grads))
    del half_grads

    whole = ssd_ops.ssd
    ssd_ops.ssd = functools.partial(whole, carry_state=False)
    try:  # a fresh jit: the fault is traced in
        (cut_loss, _), cut_grads = jax.jit(
            lambda *a: trainer._local_vg(*a))(params, bx, by)
    finally:
        ssd_ops.ssd = whole
    fault("state_not_carried", cut_loss, jax.device_get(cut_grads))


if __name__ == "__main__":
    main(int(sys.argv[1]), tiny="--tiny" in sys.argv)
