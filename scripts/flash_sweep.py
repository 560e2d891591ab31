#!/usr/bin/env python3
"""Time the flash-attention kernels over tile sizes on the chip.

    python3 scripts/flash_sweep.py [--b 8 --t 1024 --h 12 --d 64] [--blocks 128,256,512,1024]
    python3 scripts/flash_sweep.py --b 1 --t 8192 --h 72 --kv-heads 8 --d 128 --window 512

For every ``block_q x block_k`` it times the forward, dQ and dK/dV kernels
alone (on the kernels' own ``(B·H, T, D)`` layout) and the whole
``jax.grad`` through ``flash_attention`` (layout transposes included),
beside ``dense_attention``'s. A kernel takes well under a millisecond, less
than a dispatch can cost, so ``--iters`` calls run inside ONE jitted
``fori_loop``, each fed the one before's output, and the host clock around
``block_until_ready`` is divided by ``--iters``. A tile the chip's compiler
refuses is reported as such. ``--kv-heads`` gives K and V fewer (grouped)
heads, ``--window`` a sliding window; dense attention is left out where its
float32 scores would pass 4 GB. Refuses to run without a TPU: a CPU time is
not a device time. PERF.md section 6 (PR 26, PR 27) holds the tables this
printed.
"""

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from mpit_tpu.ops.ring_attention import dense_attention

fa = importlib.import_module("mpit_tpu.ops.flash_attention")


def ms(step, first, iters, repeats=3):
    """Milliseconds a call of ``step`` (array -> array of the same shape
    and dtype): ``iters`` dependent calls in one program, the best of
    ``repeats`` timings after a warm-up."""
    loop = jax.jit(lambda x: jax.lax.fori_loop(
        0, iters, lambda _, carry: step(carry), x))
    jax.block_until_ready(loop(first))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(loop(first))
        best = min(best, time.perf_counter() - t0)
    return best / iters * 1e3


def grad_of(attend, k, v, ct):
    """q -> dq through ``attend`` (dk and dv are computed and kept live)."""
    def loss(q, k_, v_):
        return (attend(q, k_, v_).astype(jnp.float32) * ct).sum()

    def step(q):
        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return dq + (0 * (dk + dv).sum()).astype(dq.dtype)
    return step


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--b", type=int, default=8)
    p.add_argument("--t", type=int, default=1024)
    p.add_argument("--h", type=int, default=12)
    p.add_argument("--d", type=int, default=64)
    p.add_argument("--kv-heads", type=int, default=0, help="0 = --h")
    p.add_argument("--window", type=int, default=0, help="0 = none")
    p.add_argument("--blocks", default="128,256,512,1024")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--out", default="")
    args = p.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("no TPU: the sweep times the compiled kernels", file=sys.stderr)
        return 3

    b, t, h, d = args.b, args.t, args.h, args.d
    h_kv, window = args.kv_heads or h, args.window or None
    keys = jax.random.split(jax.random.key(0), 4)
    q, k, v, ct = (jax.random.normal(kk, (b, t, heads, d), jnp.bfloat16)
                   for kk, heads in zip(keys, (h, h_kv, h_kv, h)))
    q2, k2, v2, do2 = (fa._to2d(a) for a in (q, k, v, ct))
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    emit({"device": jax.devices()[0].device_kind, "shape": [b, t, h, d],
          "kv_heads": h_kv, "window": window,
          "dtype": "bfloat16", "causal": True, "iters": args.iters,
          "chosen": fa.choose_blocks(t, d, jnp.bfloat16, window)})
    plain = window is None and h_kv == h
    dense = lambda a, b_, c: (
        dense_attention(a, b_, c, causal=True) if plain
        else fa.masked_dense_attention(a, b_, c, window))
    chosen = lambda a, b_, c: fa.flash_attention(
        a, b_, c, causal=True, window=window)
    impls = [("flash, chosen tiles", chosen)]
    if b * h * t * t * 4 <= 4e9:
        impls.insert(0, ("dense", dense))
    for impl, attend in impls:
        emit({"impl": impl,
              "fwd_ms": ms(lambda a: attend(a, k, v), q, args.iters),
              "grad_ms": ms(grad_of(attend, k, v, ct), q, args.iters)})

    sides = [int(s) for s in args.blocks.split(",")]
    for bq in sides:
        for bk in sides:
            row = {"block_q": bq, "block_k": bk}
            # causal, the tile, compiled, the window
            tiles = (True, bq, bk, False, window)
            try:
                fwd = lambda a: fa._fwd_call(a, k2, v2, *tiles)
                row["fwd_ms"] = ms(lambda a: fwd(a)[0], q2, args.iters)
                out2, lse = jax.jit(fwd)(q2)
                lse = lse[..., 0]
                dd = jnp.sum(do2.astype(jnp.float32)
                             * out2.astype(jnp.float32), -1)
                row["dq_ms"] = ms(lambda a: fa._dq_call(
                    q2, k2, v2, a, lse, dd, *tiles), do2, args.iters)
                # fed through K: dK has K's shape under grouped heads too
                row["dkv_ms"] = ms(lambda a: fa._dkv_call(
                    q2, a, v2, do2, lse, dd, *tiles)[0], k2, args.iters)
                row["kernels_ms"] = (row["fwd_ms"] + row["dq_ms"]
                                     + row["dkv_ms"])
                row["grad_ms"] = ms(grad_of(
                    lambda a, b_, c: fa.flash_attention(
                        a, b_, c, causal=True, block_q=bq, block_k=bk,
                        window=window),
                    k, v, ct), q, args.iters)
            except Exception as e:  # the compiler's refusal is a result
                row["refused"] = str(e).splitlines()[0][:200]
            emit(row)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
