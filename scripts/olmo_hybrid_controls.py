"""Controls of the comparison that decides ``correct`` in the cell
``olmo_hybrid_sync_1chip_8k``, on the chip, at the cell's widths and batch:
three faults pushed through the driver's own ``decide`` and limits, each of
which has to come out as not correct (its loss may pass).

- ``float8``: the reference itself with both operands of every product
  rounded to float8 (e4m3), the nearest precision below the configuration's
  bfloat16, in the place of the system.
- ``state_not_carried``: the step program's own value-and-gradient function
  (``trainer._local_vg``) with the gated delta rule's states left where they
  are made (``state_not_carried`` below: every chunk of 64 taken as a
  sequence of its own, so each starts from a zero state), the fault a chunked
  scan is most likely to have.
- ``no_delta_term``: the same function with the read of the state left out of
  the update, ``S_t = S' + beta v k^T`` (``gated_linear_attention`` below, the
  same chunks and decays without the triangular solve): plain gated linear
  attention under the model's name.

For each: the loss, the gradient's error by leaf group and the error of the
two-step move the job's optimizer makes of that gradient, all against the
float32 reference, then ``decide``. One seed an argument:

    chiprun -- python3 scripts/olmo_hybrid_controls.py 3200000033

Prints one JSON line a fault (PERF.md section 6 holds PR 34's readings).
``--tiny`` runs the cell's rehearsal sizes, to try the script on the CPU.
"""

import importlib
import json
import os
import sys

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

import mpit_tpu  # noqa: E402
from benchmark.drivers import train_lm, train_lm_ref  # noqa: E402
from benchmark.lib import traffic  # noqa: E402
from mpit_tpu import run as program  # noqa: E402
from mpit_tpu.ops import gated_delta as delta_ops  # noqa: E402
from mpit_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402
from mpit_tpu.utils.config import TrainConfig  # noqa: E402

CELL = "olmo_hybrid_sync_1chip_8k"
WHOLE = delta_ops.gated_delta  # the op, while a fault stands in its place


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def state_not_carried(q, k, v, g, beta, *, chunk):
    """``gated_delta`` with every chunk a sequence of its own: the chunks go
    through the op itself as ``B T / C`` sequences of ``C`` steps, each from
    an empty state. Both faults are patched in from here; the op has no knob
    for either."""
    bsz, t = q.shape[:2]
    if t % chunk:
        raise ValueError(f"{t} steps are no whole chunks of {chunk}")
    alone = lambda a: a.reshape(bsz * t // chunk, chunk, *a.shape[2:])
    o, low = WHOLE(
        *(alone(a) for a in (q, k, v, g, beta)), chunk=chunk)
    return o.reshape(bsz, t, *o.shape[2:]), low


def gated_linear_attention(q, k, v, g, beta, *, chunk):
    """``gated_delta``'s signature for ``S_t = exp(g_t) S_{t-1} + beta_t v_t
    k_t^T``, ``o_t = S_t q_t``: the same chunks, decays and carried state, no
    read of the state in the update and so no solve."""
    f32, dtype = jnp.float32, v.dtype
    bsz, t, h, dk = q.shape
    c, nc = chunk, -(-t // chunk)
    pad = nc * c - t
    g, beta = g.astype(f32), beta.astype(f32)
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    mm = lambda spec, a, b: jnp.einsum(
        spec, a.astype(dtype), b.astype(dtype), preferred_element_type=f32)
    cut = lambda a: jnp.moveaxis(a.reshape(bsz, nc, c, h, *a.shape[3:]), 2, 3)
    q, k, v, g, beta = (cut(a) for a in (q, k, v, g, beta))
    gamma = jnp.cumsum(g, axis=-1)
    last = gamma[..., -1]
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where(
        i >= j, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    written = (v.astype(f32) * beta[..., None]).astype(dtype)  # beta v
    k_end = k.astype(f32) * jnp.exp(last[..., None] - gamma)[..., None]
    made = mm("zchid,zchie->zchde", k_end, written)

    def step(state, at):
        made_c, log_decay = at
        return jnp.exp(log_decay)[..., None, None] * state + made_c, state

    _, entering = lax.scan(
        step, jnp.zeros(made.shape[:1] + made.shape[2:], f32),
        (jnp.moveaxis(made, 1, 0), jnp.moveaxis(last, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)
    o = (mm("zchid,zchde->zchie",
            q.astype(f32) * jnp.exp(gamma)[..., None], entering)
         + mm("zchij,zchjd->zchid", mm("zchid,zchjd->zchij", q, k) * decay,
              written))
    o = jnp.moveaxis(o, 3, 2).reshape(bsz, nc * c, h, -1)[:, :t].astype(dtype)
    return o, lax.stop_gradient(jnp.min(last))


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
    ref_loss, ref_grads, _ = reference.loss_and_grad_by_layer(
        params, bx, by, arch, to_host=True)
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

    # the system itself first: the reading the faults stand beside
    (loss, _), grads = jax.jit(trainer._local_vg)(params, bx, by)
    fault("none", loss, jax.device_get(grads))
    del grads

    low_loss, low_grads, _ = reference.loss_and_grad_by_layer(
        params, bx, by, arch, operand_dtype=jnp.float8_e4m3fn, to_host=True)
    fault("float8", low_loss, low_grads)
    del low_grads

    for name, faulty in (
            ("state_not_carried", state_not_carried),
            ("no_delta_term", gated_linear_attention)):
        delta_ops.gated_delta = faulty
        try:  # a fresh jit: the fault is traced in
            (loss, _), grads = jax.jit(
                lambda *a: trainer._local_vg(*a))(params, bx, by)
        finally:
            delta_ops.gated_delta = WHOLE
        fault(name, loss, jax.device_get(grads))
        del grads


if __name__ == "__main__":
    main(int(sys.argv[1]), tiny="--tiny" in sys.argv)
