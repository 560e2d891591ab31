#!/usr/bin/env python3
"""Time the held-experts layer over chunk sizes and loads on the chip.

    python3 scripts/moe_held_sweep.py [--chunks 0,1280,2560,5120] [--loads 0.75,1,1.3,1.6,6]
    python3 scripts/moe_held_sweep.py --tiny        # control flow, on the CPU

One layer of ``ops/moe.moe_ffn_held`` at the Laguna cell's shape (8,192
tokens of 3,072, top-10 of 256 experts, 8 held of width 1,024, row bound
20,480, bfloat16 on float32 parameters, routing weights constant backward),
under ``jax.checkpoint`` and ``value_and_grad`` as the block's remat runs
it. ``--chunks`` replaces ``moe.chunk_rows``' answer for the sweep alone
(0 = one pass over the whole bound, the layer before PR 31); ``--loads``
shifts the held experts' logits until they draw about that multiple of
the uniform rows. A call takes milliseconds, so the host clock
around ``--iters`` queued calls, the last waited for, is divided by
``--iters``. Refuses to time without a TPU: a CPU time is not a device
time. PERF.md section 6 (PR 31) holds the table this printed.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from mpit_tpu.ops import moe

ROUTED, HELD, TOP_K = 256, 8, 10


def inputs(tokens, d, width, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda k, *shape: jax.random.normal(k, shape) / shape[-2] ** 0.5
    params = {"router": normal(ks[0], d, ROUTED),
              "w_gate": normal(ks[1], HELD, d, width),
              "w_up": normal(ks[2], HELD, d, width),
              "w_down": normal(ks[3], HELD, width, d)}
    y = jax.random.normal(ks[4], (tokens, d)).at[:, -1].set(1.0)
    return params, y.astype(jnp.bfloat16)  # the last feature is a bias's


def loaded(params, y, load):
    """``params`` with the held experts' logits shifted (``y``'s last
    feature is 1) until they draw about ``load`` times the uniform rows."""
    uniform = y.shape[0] * TOP_K * HELD / ROUTED
    lo, hi = -8.0, 8.0
    for _ in range(24):
        shift = (lo + hi) / 2
        router = params["router"].at[-1, :HELD].add(shift)
        _, experts, _ = moe.route_top_k(y, router, TOP_K)
        if float((experts < HELD).sum()) < load * uniform:
            lo = shift
        else:
            hi = shift
    return dict(params, router=router)


def layer_loss(row_bound):
    """``(params, y) -> (loss, counters)`` through one remat'd layer."""
    def layer(params, y):
        out, counters, _ = moe.moe_ffn_held(
            params, y, top_k=TOP_K, row_bound=row_bound, scale=2.5,
            routing_grad=False)
        return y + out, counters

    def loss(params, y):
        out, counters = jax.checkpoint(layer)(params, y)
        return jnp.sum(out.astype(jnp.float32) ** 2), counters
    return loss


def top_ops(call, top):
    """``call()`` once under the profiler: its ``top`` longest operations
    on the first device, ``[name, calls, self ms]``."""
    import tempfile

    from benchmark.lib import program_spans, trace_reduce

    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            call()
        planes = trace_reduce.load(trace_reduce.newest_xplane(tmp))
    ops = next(lines["XLA Ops"] for name, lines in sorted(planes.items())
               if name.startswith(trace_reduce.DEVICE_PLANE))
    total = {}
    for name, self_ns, _ in trace_reduce.self_times(ops)[0]:
        key = program_spans.instruction(name)
        calls, ns = total.get(key, (0, 0.0))
        total[key] = (calls + 1, ns + self_ns)
    longest = sorted(total.items(), key=lambda kv: -kv[1][1])[:top]
    return [[k, calls, ns / 1e6] for k, (calls, ns) in longest]


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chunks", default="0,1280,2560,5120")
    p.add_argument("--loads", default="0.75,1,1.3,1.6,6")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--top", type=int, default=0,
                   help="trace every case and print its N longest operations")
    args = p.parse_args()
    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu and not args.tiny:
        print("no TPU: the sweep times the compiled layer", file=sys.stderr)
        return 3
    tokens, d, width = (256, 64, 32) if args.tiny else (8192, 3072, 1024)
    uniform = moe.chunk_rows(tokens, TOP_K, HELD, ROUTED) // 2
    row_bound = 8 * uniform
    scale = uniform / 2560
    base, y = inputs(tokens, d, width)
    cases = [(load, loaded(base, y, load))
             for load in map(float, args.loads.split(","))]
    rule = moe.chunk_rows
    for chunk in (int(int(c) * scale) or row_bound
                  for c in args.chunks.split(",")):
        moe.chunk_rows = lambda *a: chunk
        try:
            t0 = time.perf_counter()
            step = jax.jit(jax.value_and_grad(
                layer_loss(row_bound), argnums=(0, 1), has_aux=True)
            ).lower(base, y).compile()
            compile_s = time.perf_counter() - t0
        finally:
            moe.chunk_rows = rule
        if args.top:  # the names in the table are this text's instructions
            os.makedirs("chiprun_out", exist_ok=True)
            with open(f"chiprun_out/moe_held_{chunk}.hlo.txt", "w") as f:
                f.write(step.as_text())
        for load, params in cases:
            (_, counters), _ = jax.block_until_ready(step(params, y))
            t0 = time.perf_counter()
            for _ in range(args.iters):  # one program at a time, in order
                last = step(params, y)
            jax.block_until_ready(last)
            took = (time.perf_counter() - t0) / args.iters * 1e3
            if args.top and on_tpu:
                print(json.dumps(top_ops(
                    lambda: jax.block_until_ready(step(params, y)),
                    args.top)), flush=True)
            print(json.dumps({
                "chunk": chunk, "load": load,
                "rows_held": float(counters["rows_held"]),
                "rows_walked": float(counters["rows_walked"]),
                "rows_dropped": float(counters["rows_dropped"]),
                "ms" if on_tpu else "cpu_ms_not_a_device_time": took,
                "compile_s": compile_s}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
