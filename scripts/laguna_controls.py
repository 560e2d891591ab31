"""Controls of the comparison that decides ``correct`` in the cell
``laguna_s21_sync_1chip_8k``, on the chip, at the cell's widths and batch:
two faults pushed through the driver's own ``decide`` and limits, each of
which has to come out as not correct.

- ``float8``: the reference itself with both operands of every product
  rounded to float8 (e4m3), the nearest precision below the configuration's
  bfloat16, in the place of the system.
- ``half_sequence``: the step program's own value-and-gradient function
  (``trainer._local_vg``) given only the first half of the tokens, as a
  step that left half of them out would compute.

For each: the loss, the gradient's error by leaf group and the error of
the two-step move the job's optimizer makes of that gradient, all against
the float32 reference, then ``decide``. One seed an argument:

    chiprun -- python3 scripts/laguna_controls.py 2500000033

Prints one JSON line a fault (PERF.md section 6 holds PR 27's readings).
"""

import json
import os
import sys

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import mpit_tpu  # noqa: E402
from benchmark.drivers import train_lm  # noqa: E402
from benchmark.lib import reference_laguna_s as reference, traffic  # noqa: E402
from mpit_tpu import run as program  # noqa: E402
from mpit_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402
from mpit_tpu.utils.config import TrainConfig  # noqa: E402

CELL = "laguna_s21_sync_1chip_8k"


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def main(seed: int, tiny: bool) -> None:
    enable_compile_cache()
    config = load("benchmark", "configs", "laguna-s-2.1.json")
    job = load("benchmark", "workloads", f"{CELL}.json")
    train = {**config["train_config"], **job["train_config"]}
    vocab, arch = config["vocab_size"], train_lm.arch_of(config)
    if tiny:  # the rehearsal's sizes, to try the script on the CPU
        job = job["rehearsal"]
        train, vocab = {**train, **job["train_config"]}, job["vocab_size"]
        arch = train.pop("arch")
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
    _, sown = jax.jit(lambda p, t: model.apply(
        {"params": p}, t, mutable=["routing"]))(params, bx)
    choices = [sown["routing"][f"Block_{l}"]["experts"][0]
               if f"Block_{l}" in sown["routing"] else None
               for l in range(arch["num_hidden_layers"])]
    share = dict(experts_held=arch["num_experts"],
                 expert_offset=arch.get("expert_offset", 0), choices=choices,
                 to_host=True)
    ref_loss, ref_grads, _ = reference.loss_and_grad_by_layer(
        params, bx, by, arch, **share)
    start = jax.device_get(params)

    def fault(name, loss, grads):
        grad_err, _ = train_lm.gradient_errors(grads, ref_grads)
        moved = jax.jit(lambda p0, g: train_lm.two_steps(opt, p0, g))
        end = jax.tree.map(
            lambda p0, g: jax.device_get(moved(jnp.asarray(p0), jnp.asarray(g))),
            start, grads)
        move_err, move_norm, _ = train_lm.move_check(opt, start, ref_grads, end)
        limits = {**train_lm.LIMITS, **job.get("limits", {})}
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
            "loss_rel_err": abs(float(loss) - float(ref_loss)) / float(ref_loss),
            "grad_rel_err_by_group": grad_err, "move_rel_err": move_err,
            "limits": limits}), flush=True)

    low_loss, low_grads, _ = reference.loss_and_grad_by_layer(
        params, bx, by, arch, operand_dtype=jnp.float8_e4m3fn, **share)
    fault("float8", low_loss, low_grads)
    del low_grads

    half = cfg.seq_len // 2
    (half_loss, _), half_grads = jax.jit(trainer._local_vg)(
        params, bx[:, :half], by[:, :half])
    fault("half_sequence", half_loss, jax.device_get(half_grads))


if __name__ == "__main__":
    main(int(sys.argv[1]), tiny="--tiny" in sys.argv)
