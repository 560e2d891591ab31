"""One layer's gated delta rule alone on the chip, at the shape the cell
``olmo_hybrid_sync_1chip_8k`` runs (8,192 tokens, 30 heads, keys of 96, values
of 192, chunks of 64, bfloat16 operands): ``ops/gated_delta.gated_delta``
forward, and forward with every input's gradient, once for each variant:

- ``kernel``: the Pallas kernel pair (``use_pallas=True``), what the program
  runs on a TPU; ``--heads 3,5,6,10`` times it once for each number of heads a
  grid step (``ops/gated_delta._HEADS``) and ``--blocks 8,16,32,64`` once for
  each size of the diagonal blocks the in-kernel solve takes by forward
  substitution before it goes on by halves (``ops/gated_delta._BLOCK``: 64 is
  substitution alone, 63 dependent steps and no product);
- ``kernel_none``: the kernels with the identity in the inverse's place (a
  wrong result: what they cost without any solve);
- the ``jax.numpy`` form (``use_pallas=False``) with each way of taking the
  chunk's unit lower triangular inverse: ``halves``
  (``ops/gated_delta.unit_lower_inverse``, the specification), ``powers``
  (the product form ``(I - A)(I + A^2)(I + A^4)...``, six factors for a chunk
  of 64), ``solve`` (``jax.scipy.linalg.solve_triangular`` against the
  identity), ``none`` (the identity in the inverse's place: a wrong result,
  what the scan and the products cost without any inverse).

    chiprun -- python3 scripts/gated_delta_sweep.py

Prints one JSON line a variant: milliseconds (the median of ``--repeats``
calls after a warm-up) and the error of the output and of the gradients
(the worst of the five) against the recurrence taken step by step in float32
(``models/reference_olmo_hybrid.recurrence``) from the same rounded inputs,
at random keys and at correlated ones (cosine 0.5 between steps, ``beta``
1.5 to 2: where the product form fails). ``--only kernel,halves`` keeps to
some variants; ``--trace`` adds each variant's longest device operations
(one profiled call of forward and gradients: the kernels and what XLA lays
out round them); ``--tiny`` tries the script on the CPU at a small shape,
where its times mean nothing.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mpit_tpu.models import reference_olmo_hybrid as reference  # noqa: E402
from mpit_tpu.ops import gated_delta as delta_ops  # noqa: E402
from scripts import ssd_sweep  # noqa: E402

_HIGHEST = jax.lax.Precision.HIGHEST


def by_powers(lower):
    c = lower.shape[-1]
    eye = jnp.eye(c, dtype=jnp.float32)
    inv, power, span = eye - lower, lower, 2
    while span < c:
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inv = jnp.matmul(inv, eye + power, precision=_HIGHEST)
        span *= 2
    return inv


def by_solve(lower):
    c = lower.shape[-1]
    eye = jnp.broadcast_to(jnp.eye(c, dtype=jnp.float32), lower.shape)
    return jax.scipy.linalg.solve_triangular(
        eye + lower, eye, lower=True, unit_diagonal=True)


def no_inverse(lower):
    return jnp.broadcast_to(jnp.eye(lower.shape[-1], dtype=jnp.float32),
                            lower.shape) + 0.0 * lower


INVERSES = {"halves": delta_ops.unit_lower_inverse, "powers": by_powers,
            "solve": by_solve, "none": no_inverse}


def make_inputs(t, h, dk, dv, correlated):
    """The mixer's inputs as the model makes them (SiLU'd, normalised,
    bfloat16); ``correlated``: every key shares a component with every other
    (cosine 0.5) and ``beta`` lies in 1.5 to 2."""
    keys = jax.random.split(jax.random.key(int(correlated)), 7)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    bf16 = jnp.bfloat16
    k = unit(jax.nn.silu(jax.random.normal(keys[1], (1, t, h, dk))))
    if correlated:
        k = unit(k + unit(jax.random.normal(keys[6], (1, 1, h, dk))))
    q = unit(jax.nn.silu(jax.random.normal(keys[0], (1, t, h, dk)))) * dk ** -0.5
    v = jax.nn.silu(jax.random.normal(keys[2], (1, t, h, dv)))
    g = -8.0 * jax.nn.softplus(jax.random.normal(keys[3], (1, t, h)) - 4.0)
    beta = 2.0 * jax.nn.sigmoid(
        jax.random.normal(keys[4], (1, t, h)) + (2.0 if correlated else 0.0))
    weights = jax.random.normal(keys[5], (1, t, h, dv)).astype(bf16)
    return (q.astype(bf16), k.astype(bf16), v.astype(bf16), g, beta), weights


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--repeats", type=int, default=10)
    parser.add_argument("--heads", default="",
                        help="heads a grid step to try, by commas")
    parser.add_argument("--blocks", default="",
                        help="sizes of the solve's diagonal blocks to try")
    parser.add_argument("--only", default="", help="variants, by commas")
    parser.add_argument("--trace", action="store_true")
    args = parser.parse_args()
    if jax.devices()[0].platform == "cpu" and not args.tiny:
        sys.exit("no accelerator (--tiny tries the script on the CPU)")
    t, h, dk, dv, chunk = (128, 2, 16, 32, 16) if args.tiny else (
        8192, 30, 96, 192, 64)
    f32 = jnp.float32

    def functions(op):
        forward = jax.jit(lambda *a: op(*a)[0])
        return forward, lambda weights: jax.jit(jax.grad(
            lambda *a: jnp.sum(op(*a)[0].astype(f32) * weights.astype(f32)),
            argnums=range(5)))

    def timed(fn, *inputs):
        jax.block_until_ready(fn(*inputs))  # compiles
        seconds = []
        for _ in range(args.repeats):
            start = time.perf_counter()
            jax.block_until_ready(fn(*inputs))
            seconds.append(time.perf_counter() - start)
        return 1e3 * statistics.median(seconds)

    apart = lambda got, want: float(
        jnp.linalg.norm(got.astype(f32) - want) / jnp.linalg.norm(want))
    sets = {}
    for name, correlated in (("random", False), ("correlated", True)):
        inputs, weights = make_inputs(t, h, dk, dv, correlated)
        exact = lambda *a: (reference.recurrence(
            *(x.astype(f32) for x in a), blocks=True), None)
        forward, both = functions(exact)
        sets[name] = (inputs, weights, forward(*inputs),
                      both(weights)(*inputs))

    def report(name, op, **more):
        forward, both = functions(op)
        line = {"variant": name, **more,
                "device": jax.devices()[0].device_kind,
                "shape": [t, h, dk, dv, chunk]}
        for case, (inputs, weights, want, want_grads) in sets.items():
            gradients = both(weights)
            out, grads = forward(*inputs), gradients(*inputs)
            if case == "random":
                line["forward_ms"] = timed(forward, *inputs)
                line["forward_and_gradients_ms"] = timed(gradients, *inputs)
                if args.trace:  # one profiled call, the device's operations
                    line["top_ops"] = ssd_sweep.top_ops(
                        lambda _: gradients(*inputs), None, 1, top=12)
            line[f"error_{case}"] = apart(out, want)
            line[f"gradient_error_{case}"] = max(
                apart(a, b) for a, b in zip(grads, want_grads))
            line[f"finite_{case}"] = bool(jnp.all(jnp.isfinite(
                out.astype(f32)))) and all(
                    bool(jnp.all(jnp.isfinite(a.astype(f32)))) for a in grads)
        print(json.dumps(line), flush=True)

    only = set(filter(None, args.only.split(",")))
    wanted = lambda name: not only or name in only
    numbers = lambda text, default: [
        int(n) for n in text.split(",") if n] or [default]
    kernels = lambda *a: delta_ops.gated_delta(
        *a, chunk=chunk, use_pallas=True)
    was = delta_ops._HEADS, delta_ops._BLOCK, delta_ops._inverses_in_vmem
    try:  # the kernels' jits read all three as they trace
        for heads in numbers(args.heads, was[0]) if wanted("kernel") else ():
            for block in numbers(args.blocks, was[1]):
                delta_ops._HEADS, delta_ops._BLOCK = heads, block
                jax.clear_caches()
                report("kernel", kernels, block=block,
                       heads_a_step=delta_ops._heads_a_step(h))
        delta_ops._HEADS, delta_ops._BLOCK = was[:2]
        if wanted("kernel_none"):
            delta_ops._inverses_in_vmem = lambda lowers: [
                jnp.eye(a.shape[0], dtype=f32) + 0.0 * a for a in lowers]
            jax.clear_caches()
            report("kernel_none", kernels)
    finally:
        (delta_ops._HEADS, delta_ops._BLOCK,
         delta_ops._inverses_in_vmem) = was
        jax.clear_caches()
    for name, inverse in INVERSES.items():
        if not wanted(name):
            continue
        delta_ops.unit_lower_inverse = inverse
        try:  # fresh jits: the inverse is traced in
            report(name, lambda *a: delta_ops.gated_delta(
                *a, chunk=chunk, use_pallas=False))
        finally:
            delta_ops.unit_lower_inverse = INVERSES["halves"]


if __name__ == "__main__":
    main()
