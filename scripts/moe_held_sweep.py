#!/usr/bin/env python3
"""Time the held-experts layer, its experts or one grouped product on the chip.

    python3 scripts/moe_held_sweep.py --shape laguna|nemotron|zaya \\
        [--parts layer,experts,product] [--impls ragged,kernel] \\
        [--row-tiles 0,128,256,512] [--chunks 0,1280,2560,5120] \\
        [--loads 0.75,1,1.3]
    python3 scripts/moe_held_sweep.py --tiny        # control flow, on the CPU

``--shape`` takes one expert cell's layer: its tokens, hidden size, expert
width, experts held and routed, top-k, family and row bound (``SHAPES``),
bfloat16 on float32 parameters, routing weights constant backward. Each
case runs under ``jax.checkpoint`` and ``value_and_grad``, as the block's
remat runs it:

- ``layer``: one whole ``ops/moe.moe_ffn_held`` (routing, dispatch, the
  experts, the scatter-add);
- ``experts``: ``moe._expert_rows`` alone over a buffer sorted by expert
  (the grouped products with their activation, the row weights);
- ``product``: one grouped product over that buffer (an expert of one
  weight, registered here for the sweep alone).

``--impls``: ``ragged`` = ``lax.ragged_dot`` with its masks, ``kernel`` =
``ops/grouped``'s kernels; the script chooses by patching what
``moe_ffn_held`` observes (``grouped.pallas_supported``), so the layer
takes no new argument. ``--row-tiles`` replaces ``grouped.choose_tiles``'
row tile for the kernel's cases (0 = the chooser's own). ``--chunks``
replaces ``moe.chunk_rows``' answer in the ``layer`` cases (0 = one pass
over the whole bound, the layer before the chunked walk; empty = the
rule's).
``--loads`` shifts the held experts' logits until they draw about that
multiple of the uniform rows. A call takes milliseconds, so the host clock
around ``--iters`` queued calls, the last waited for, is divided by
``--iters``. Refuses to time without a TPU: a CPU time is not a device
time. PERF.md section 6 holds the tables this printed.
"""

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from mpit_tpu.ops import grouped, moe

#: the expert cells' layers: tokens, hidden size, expert width, experts
#: held and routed, top-k, family, sigmoid scores (a bias on the choice),
#: row bound (``benchmark/configs/``)
SHAPES = {
    "laguna": dict(tokens=8192, d=3072, width=1024, held=8, routed=256,
                   top_k=10, expert="swiglu", sigmoid=False, bound=20480),
    "nemotron": dict(tokens=8192, d=2688, width=1856, held=8, routed=128,
                     top_k=6, expert="relu2", sigmoid=True, bound=24576),
    "zaya": dict(tokens=8192, d=2048, width=2048, held=8, routed=16,
                 top_k=1, expert="swiglu", sigmoid=False, bound=8192),
    "tiny": dict(tokens=256, d=128, width=128, held=4, routed=8, top_k=2,
                 expert="swiglu", sigmoid=False, bound=512),
}
#: one product alone, as an expert of one weight
moe.EXPERTS["one"] = (("w_one",), lambda product, rows, w_one: product(
    rows, w_one))


def inputs(shape, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    d, width, held = shape["d"], shape["width"], shape["held"]
    normal = lambda k, *s: jax.random.normal(k, s) / s[-2] ** 0.5
    names = moe.EXPERTS[shape["expert"]][0]
    params = {"router": normal(ks[0], d, shape["routed"])}
    for i, name in enumerate(names):
        last = i == len(names) - 1
        params[name] = normal(ks[1 + i], held, *((width, d) if last
                                                  else (d, width)))
    if shape["sigmoid"]:
        params["bias"] = jnp.zeros((shape["routed"],))
    y = jax.random.normal(ks[4], (shape["tokens"], d)).at[:, -1].set(1.0)
    return params, y.astype(jnp.bfloat16)  # the last feature is a bias's


def route(shape, params, y):
    return moe.route_top_k(y, params["router"], shape["top_k"],
                           bias=params.get("bias"))


def loaded(shape, params, y, load):
    """``params`` with the held experts' logits shifted (``y``'s last
    feature is 1) until they draw about ``load`` times the uniform rows."""
    held = shape["held"]
    uniform = shape["tokens"] * shape["top_k"] * held / shape["routed"]
    lo, hi = -8.0, 8.0
    for _ in range(24):
        shift = (lo + hi) / 2
        router = params["router"].at[-1, :held].add(shift)
        _, experts, _ = route(shape, dict(params, router=router), y)
        if float((experts < held).sum()) < load * uniform:
            lo = shift
        else:
            hi = shift
    return dict(params, router=router)


def layer_case(shape, params, y):
    """``(params, y) -> (loss, counters)`` through one remat'd layer."""
    def layer(params, y):
        out, counters, _ = moe.moe_ffn_held(
            params, y, top_k=shape["top_k"], row_bound=shape["bound"],
            scale=2.5, routing_grad=False, expert=shape["expert"])
        return y + out, counters

    def loss(params, y):
        out, counters = jax.checkpoint(layer)(params, y)
        return jnp.sum(out.astype(jnp.float32) ** 2), counters
    return loss, (params, y)


def buffer_case(shape, params, y, expert, kernel):
    """``_expert_rows`` over the rows ``params``' routing sends to the
    experts held, sorted by expert into one chunk: ``(weights, rows) ->
    (loss, counters)`` and its arguments."""
    held, dt = shape["held"], jnp.bfloat16
    chunk = moe.chunk_rows(shape["tokens"], shape["top_k"], held,
                           shape["routed"])
    rows = min(chunk, shape["bound"], shape["tokens"] * shape["top_k"])
    _, experts, _ = route(shape, params, y)
    counts = jnp.bincount(experts.reshape(-1), length=shape["routed"])[:held]
    ends = jnp.minimum(jnp.cumsum(counts), rows)
    buf = jax.random.normal(jax.random.PRNGKey(1), (rows, shape["d"]), dt)
    row_weight = jnp.where(jnp.arange(rows) < ends[-1], 0.5, 0.0)
    names = moe.EXPERTS[shape["expert"]][0]
    weights = [params[n].astype(dt) for n in names]
    width = weights[-1].shape[1]
    pad = -width % moe._WIDTH_TILE if width > moe._WIDTH_TILE else 0
    if pad:  # as moe_ffn_held pads the width
        weights = [jnp.pad(w, ((0, 0), (0, 0), (0, pad)))
                   for w in weights[:-1]] + [
            jnp.pad(weights[-1], ((0, 0), (0, pad), (0, 0)))]
    if expert == "one":  # the first product: into the width
        weights = weights[:1]
    per_group = chunk // 2 // held
    if not (kernel and grouped.takes(grouped.pallas_supported(), rows,
                                     shape["d"], width + pad, per_group)):
        per_group = None
    tm = per_group and grouped.choose_tiles(
        per_group, shape["d"], width + pad, dt)[0][0]
    sizes = moe._group_sizes(ends, 0, rows)[1]
    counters = {"rows_held": counts.sum().astype(jnp.float32),
                "rows_walked": jnp.float32(rows),
                "rows_tiled": (grouped.rows_tiled(sizes, tm) if tm
                               else rows) * 1.0}

    def loss(weights, rows_in):
        out = jax.checkpoint(lambda w, r: moe._expert_rows(
            expert, r, w, row_weight, ends, 0, per_group))(weights, rows_in)
        return jnp.sum(out ** 2), counters
    return loss, (weights, buf)


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
    p.add_argument("--shape", default="laguna", choices=sorted(SHAPES))
    p.add_argument("--parts", default="layer,experts,product")
    p.add_argument("--impls", default="ragged,kernel")
    p.add_argument("--row-tiles", default="0")
    p.add_argument("--chunks", default="")
    p.add_argument("--loads", default="0.75,1,1.3")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--top", type=int, default=0,
                   help="trace every case and print its N longest operations")
    p.add_argument("--hlo-dir", default="",
                   help="write each case's compiled text there (the names "
                        "--top prints are its instructions)")
    args = p.parse_args()
    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu and not args.tiny:
        print("no TPU: the sweep times the compiled layer", file=sys.stderr)
        return 3
    name = "tiny" if args.tiny else args.shape
    shape = SHAPES[name]
    seen = {}  # a buffer case's results by (part, load), for the check
    base, y = inputs(shape)
    loads = [(load, loaded(shape, base, y, load))
             for load in map(float, args.loads.split(","))]
    chunks = [int(c) for c in args.chunks.split(",") if c] or [None]
    row_tiles = [int(t) for t in args.row_tiles.split(",")]
    cases = [(part, impl, tm, chunk)
             for part in args.parts.split(",")
             for impl in args.impls.split(",")
             for tm in (row_tiles if impl == "kernel" else [0])
             for chunk in (chunks if part == "layer" else [None])]
    for part, impl, tm, chunk in cases:
        with chosen(impl == "kernel", tm, chunk, shape["bound"]):
            for load, params in loads:
                run_case(args, name, shape, part, impl, tm, chunk, load,
                         params, y, on_tpu, seen)
    return 0


@contextlib.contextmanager
def chosen(kernel, tm, chunk, bound):
    """What ``moe_ffn_held`` observes, patched for one case: whether the
    kernels may run (``grouped.pallas_supported``), their row tile (0 = the
    chooser's) and the chunk (None = the rule's, 0 = the whole bound)."""
    saved = grouped.pallas_supported, grouped._row_tile, moe.chunk_rows
    grouped.pallas_supported = lambda: kernel
    if tm:
        grouped._row_tile = lambda rows_per_group: tm
    if chunk is not None:
        moe.chunk_rows = lambda *a: chunk or bound
    try:
        yield
    finally:
        grouped.pallas_supported, grouped._row_tile, moe.chunk_rows = saved


def run_case(args, name, shape, part, impl, tm, chunk, load, params, y,
             on_tpu, seen):
    """Compile and time one case; print one JSON line. A buffer case also
    prints, against the first case of its part and load (``ragged`` where
    it runs first), the largest difference of its loss and gradients over
    the largest magnitude of the first's (``rel_diff``)."""
    if part == "layer":
        loss, operands = layer_case(shape, params, y)
    else:
        loss, operands = buffer_case(
            shape, params, y, shape["expert"] if part == "experts" else "one",
            impl == "kernel")
    t0 = time.perf_counter()
    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
                   ).lower(*operands).compile()
    compile_s = time.perf_counter() - t0
    text = step.as_text()
    if args.hlo_dir:
        os.makedirs(args.hlo_dir, exist_ok=True)
        with open(os.path.join(args.hlo_dir,
                               f"moe_{name}_{part}_{impl}_{tm}.hlo.txt"),
                  "w") as f:
            f.write(text)
    (value, counters), grads = jax.block_until_ready(step(*operands))
    rel_diff = None
    if part != "layer":
        got = [value] + jax.tree.leaves(grads)
        first = seen.setdefault((part, load), got)
        f32 = lambda a: a.astype(jnp.float32)
        rel_diff = max(float(jnp.max(jnp.abs(f32(a) - f32(b))))
                       / max(float(jnp.max(jnp.abs(f32(b)))), 1e-30)
                       for a, b in zip(got, first))
    t0 = time.perf_counter()
    for _ in range(args.iters):  # one program at a time, in order
        last = step(*operands)
    jax.block_until_ready(last)
    took = (time.perf_counter() - t0) / args.iters * 1e3
    if args.top and on_tpu:
        print(json.dumps(top_ops(
            lambda: jax.block_until_ready(step(*operands)), args.top)),
            flush=True)
    print(json.dumps({
        "shape": name, "part": part, "impl": impl, "row_tile": tm,
        "chunk": chunk, "load": load,
        "kernels": text.count("tpu_custom_call"), "rel_diff": rel_diff,
        **{k: float(v) for k, v in counters.items()
           if k.startswith("rows_")},
        "ms" if on_tpu else "cpu_ms_not_a_device_time": took,
        "compile_s": compile_s}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
