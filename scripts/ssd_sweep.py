#!/usr/bin/env python3
"""Time one layer's Mamba-2 recurrence on the chip: ``jax.numpy`` form
against the Pallas kernels.

    python3 scripts/ssd_sweep.py [--b 1 --t 8192 --heads 64 --head-dim 64 --groups 8 --state 128 --chunk 128]
    python3 scripts/ssd_sweep.py --tiny        # control flow, on the CPU

``ops/ssd.ssd`` alone at the shape of ``nemotron3_nano_sync_1chip_8k``
(bfloat16 operands, ``A`` = -1..-64, steps near 0.1: a chunk's log-decay
far under -88), ``use_pallas=False`` against ``use_pallas=True``: the
forward, and the forward with the gradient of every input, in ms and as a
share of ``benchmark/lib/ssm_kernels.least_seconds`` (the recurrence's own
work from shapes). Then the two kernels alone on their own layout
(``_fwd_call``, ``_bwd_call``), which leaves out the layouts round them
(``cum`` as columns and rows, ``D`` spread over channels). It also prints
how far the compiled kernels' output and gradients are from the
``jax.numpy`` form's. A call takes a
few ms, so ``--iters`` calls run inside ONE jitted ``fori_loop``, each fed
the one before's output, and the host clock round ``block_until_ready`` is
divided by ``--iters``. Refuses to time without a TPU: a CPU time is not a
device time. PERF.md section 6 (PR 33) holds the table this printed.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmark.lib import ssm_kernels
from mpit_tpu.ops import ssd as ssd_ops


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


def top_ops(loop, first, iters, top=24):
    """Self time of the device operations of one profiled run of ``loop``,
    ms an iteration, longest first."""
    import tempfile

    from benchmark.lib import trace_reduce

    jax.block_until_ready(loop(first))
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            jax.block_until_ready(loop(first))
        planes = trace_reduce.load(trace_reduce.newest_xplane(trace_dir))
    ops = next((lines["XLA Ops"] for name, lines in sorted(planes.items())
                if name.startswith(trace_reduce.DEVICE_PLANE)
                and "XLA Ops" in lines), ())  # none on the CPU
    selfs, _ = trace_reduce.self_times(ops)
    by_name = {}
    for name, self_ns, _ in selfs:
        by_name[name] = by_name.get(name, 0.0) + self_ns / iters / 1e6
    return sorted(by_name.items(), key=lambda kv: -kv[1])[:top]


def inputs(b, t, heads, head_dim, groups, state, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    bf = jnp.bfloat16
    return (
        jax.random.normal(ks[0], (b, t, heads, head_dim), bf),
        jax.nn.softplus(jax.random.normal(ks[1], (b, t, heads)) - 2.0),
        -jnp.arange(1, heads + 1, dtype=jnp.float32),
        (jax.random.normal(ks[2], (b, t, groups, state)) * 0.3).astype(bf),
        (jax.random.normal(ks[3], (b, t, groups, state)) * 0.3).astype(bf),
        jnp.ones((heads,), jnp.float32),
    ), jax.random.normal(ks[4], (b, t, heads, head_dim), bf)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--b", type=int, default=1)
    p.add_argument("--t", type=int, default=8192)
    p.add_argument("--heads", type=int, default=64)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--groups", type=int, default=8)
    p.add_argument("--state", type=int, default=128)
    p.add_argument("--chunk", type=int, default=128)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--tiny", action="store_true",
                   help="T 256, 4 heads, 2 groups, one iteration, any device")
    p.add_argument("--kernels-only", action="store_true",
                   help="leave the jax.numpy form (and the comparison) out")
    p.add_argument("--trace", action="store_true",
                   help="also profile the kernels' gradient program and "
                   "print its longest device operations")
    p.add_argument("--out", default="")
    args = p.parse_args()
    device = jax.devices()[0]
    if args.tiny:
        args.t, args.heads, args.groups, args.iters = 256, 4, 2, 1
    elif device.platform != "tpu":
        print("no TPU: the sweep times the compiled kernels", file=sys.stderr)
        return 3

    shape = {"batch": args.b, "t": args.t, "heads": args.heads,
             "head_dim": args.head_dim, "groups": args.groups,
             "state": args.state, "itemsize": 2}
    (x, dt, a, b, c, d), ct = inputs(*list(shape.values())[:6])
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    least = {k: 1e3 * ssm_kernels.least_seconds(k, shape, device.device_kind)
             for k in ("fwd", "bwd")} if device.platform == "tpu" else {}
    emit({"device": device.device_kind, "shape": shape, "chunk": args.chunk,
          "iters": args.iters, "least_ms": least})

    def share(row):
        if least:
            row["fwd_share_pct"] = 100 * least["fwd"] / row["fwd_ms"]
            row["grad_share_pct"] = (100 * (least["fwd"] + least["bwd"])
                                     / row["grad_ms"])
        return row

    results = {}
    for impl, use in (("jax.numpy", False), ("kernels", True)):
        if args.kernels_only and not use:
            continue
        scan = lambda x_, *rest: ssd_ops.ssd(
            x_, *rest, chunk=args.chunk, use_pallas=use)[0]
        if not args.kernels_only:
            results[impl] = jax.jit(lambda *ins: (scan(*ins), *jax.grad(
                lambda *ins_: (scan(*ins_).astype(jnp.float32) * ct).sum(),
                argnums=tuple(range(6)))(*ins)))(x, dt, a, b, c, d)

        def grad_step(x_):
            # every input's gradient computed and kept live
            grads = jax.grad(lambda *ins: (
                scan(*ins).astype(jnp.float32) * ct).sum(),
                argnums=tuple(range(6)))(x_, dt, a, b, c, d)
            rest = sum(g.astype(jnp.float32).sum() for g in grads[1:])
            return grads[0] + (0 * rest).astype(x_.dtype)

        emit(share({"impl": impl,
                    "fwd_ms": ms(lambda x_: scan(x_, dt, a, b, c, d), x,
                                 args.iters),
                    "grad_ms": ms(grad_step, x, args.iters)}))

    # as compiled for this device: the kernels' output and gradients against
    # the jax.numpy form's, |difference| / |jax.numpy|
    norm = lambda v: float(jnp.linalg.norm(v.astype(jnp.float32)))
    if not args.kernels_only:
        emit({"kernels_against_jax_numpy": {
            name: norm(got.astype(jnp.float32) - want.astype(jnp.float32))
            / norm(want)
            for name, got, want in zip(
                ("y", "dx", "ddt", "da", "db", "dc", "dd"),
                results["kernels"], results["jax.numpy"])}})

    # the kernels alone, on their own layout
    g, r = args.groups, args.heads // args.groups
    nc = args.t // args.chunk
    dtc = jnp.moveaxis(dt.reshape(args.b, nc, args.chunk, g, r), 2, 3)
    cumc = jnp.cumsum(dtc * a.reshape(g, 1, r), axis=3)
    flat = lambda v: v.reshape(args.b, args.t, -1)
    ins = (dtc, cumc, jnp.swapaxes(cumc, 3, 4), flat(b), flat(c),
           jnp.repeat(d, args.head_dim)[None])
    interpret = device.platform != "tpu"
    _, entering = ssd_ops._fwd_call(flat(x), *ins, r, interpret)
    emit({"kernel": "ssd_fwd", "ms": ms(
        lambda x_: ssd_ops._fwd_call(x_, *ins, r, interpret)[0], flat(x),
        args.iters)})
    emit({"kernel": "ssd_bwd", "ms": ms(
        lambda dy: ssd_ops._bwd_call(
            flat(x), *ins, entering, dy, r, interpret)[0], flat(ct),
        args.iters)})
    if args.trace:
        emit({"top_ops": top_ops(jax.jit(lambda x_: jax.lax.fori_loop(
            0, args.iters, lambda _, v: grad_step(v), x_)), x, args.iters)})
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
