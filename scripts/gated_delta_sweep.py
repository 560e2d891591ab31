"""One layer's gated delta rule alone on the chip, at the shape the cell
``olmo_hybrid_sync_1chip_8k`` runs (8,192 tokens, 30 heads, keys of 96, values
of 192, chunks of 64, bfloat16 operands): ``ops/gated_delta.gated_delta``
forward, and forward with every input's gradient, once for each way of taking
the chunk's unit lower triangular inverse:

- ``halves``: ``ops/gated_delta.unit_lower_inverse`` as the program has it;
- ``powers``: the product form ``(I - A)(I + A^2)(I + A^4)...``, six factors
  for a chunk of 64;
- ``solve``: ``jax.scipy.linalg.solve_triangular`` against the identity;
- ``none``: the identity in the inverse's place (a wrong result: what the step
  costs without any inverse, so the scan's and the products' share shows).

    chiprun -- python3 scripts/gated_delta_sweep.py

Prints one JSON line a variant: milliseconds (the median of ``--repeats``
calls after a warm-up) and the output's error against ``halves``. ``--tiny``
tries the script on the CPU at a small shape, where its times mean nothing.
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

from mpit_tpu.ops import gated_delta as delta_ops  # noqa: E402

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


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--repeats", type=int, default=10)
    args = parser.parse_args()
    if jax.devices()[0].platform == "cpu" and not args.tiny:
        sys.exit("no accelerator (--tiny tries the script on the CPU)")
    t, h, dk, dv, chunk = (256, 2, 16, 32, 16) if args.tiny else (
        8192, 30, 96, 192, 64)
    keys = jax.random.split(jax.random.key(0), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    bf16 = jnp.bfloat16
    q = (unit(jax.nn.silu(jax.random.normal(keys[0], (1, t, h, dk))))
         * dk ** -0.5).astype(bf16)
    k = unit(jax.nn.silu(jax.random.normal(keys[1], (1, t, h, dk)))).astype(bf16)
    v = jax.nn.silu(jax.random.normal(keys[2], (1, t, h, dv))).astype(bf16)
    g = -8.0 * jax.nn.softplus(jax.random.normal(keys[3], (1, t, h)) - 4.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (1, t, h)))
    weights = jax.random.normal(keys[5], (1, t, h, dv)).astype(bf16)

    def timed(fn, *inputs):
        jax.block_until_ready(fn(*inputs))  # compiles
        seconds = []
        for _ in range(args.repeats):
            start = time.perf_counter()
            jax.block_until_ready(fn(*inputs))
            seconds.append(time.perf_counter() - start)
        return 1e3 * statistics.median(seconds)

    want = None
    for name, inverse in INVERSES.items():
        delta_ops.unit_lower_inverse = inverse
        try:  # fresh jits: the inverse is traced in
            forward = jax.jit(lambda *a: delta_ops.gated_delta(
                *a, chunk=chunk)[0])
            both = jax.jit(jax.grad(
                lambda *a: jnp.sum(delta_ops.gated_delta(
                    *a, chunk=chunk)[0].astype(jnp.float32)
                    * weights.astype(jnp.float32)), argnums=range(5)))
            out = forward(q, k, v, g, beta).astype(jnp.float32)
            want = out if want is None else want
            print(json.dumps({
                "inverse": name, "device": jax.devices()[0].device_kind,
                "shape": [t, h, dk, dv, chunk],
                "forward_ms": timed(forward, q, k, v, g, beta),
                "forward_and_gradients_ms": timed(both, q, k, v, g, beta),
                "error_against_halves": float(
                    jnp.linalg.norm(out - want) / jnp.linalg.norm(want)),
                "finite": bool(jnp.all(jnp.isfinite(out))),
            }), flush=True)
        finally:
            delta_ops.unit_lower_inverse = INVERSES["halves"]


if __name__ == "__main__":
    main()
