"""chip_smoke.py — does the system still start, compile and finish on the chip?

    python chip_smoke.py                # on a TPU host: every visible chip
    python chip_smoke.py --cpu-wiring   # tiny sizes on CPU: control flow only

One process drives the main path once, through the entry points a user
calls, on every chip ``jax.devices()`` shows (one v5e chip, or the four of
one host):

  A  sync trainer     run(resnet50-sync): ResNet-50 at 224 px, 32 samples a
                      chip, 4 steps + eval
  B  EASGD            run(mnist-easgd): LeNet, 1024 samples a chip, tau=4,
                      3 fused rounds + eval
  C  literal PS       run(mnist-ps): 2 pclient + 1 pserver threads over the
                      tagged transport, 40 local steps (exchange every 4)
  D  serving          models.Server at GPT-2-small width (6 layers): 10 greedy
                      requests through 8 slots, rows compared with solo
                      generate_fast
  E  pallas kernels   flash attention forward + grad and the fused elastic
                      update, compiled (not interpreted), against XLA; at
                      GPT-2-small's shape the kernel's and XLA's errors
                      against float32 at the highest precision

Weights are random from a seed and the data is the loaders' seeded synthetic
sets (no download, no git, no network). Each leg checks its own output —
finite losses, token counts, parity with the reference path, where the
arrays live — and any failure ends the run with a traceback and a non-zero
exit: no leg is wrapped in a try/except.

Without a TPU the default command exits 3 before any leg runs. ``--cpu-wiring``
is a mode, not a fallback: tiny shapes, pallas in interpret mode, the output
marked as wiring only, and the pass marker (the final JSON line) never printed.

Wall and compile times printed here are set-up facts about a cold or cached
start, not performance metrics (PERF.md).
"""

import argparse
import dataclasses
import json
import sys
import time


class _CompileMeter:
    """Sums what jax itself reports about compilation: seconds in the
    backend compiler (or, on a persistent-cache hit, in the cache read),
    programs compiled, persistent-cache hits, and the slowest program
    since the last ``take_slowest`` (client threads compile concurrently,
    so a leg's summed seconds can exceed its wall time)."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self._slowest = ("-", 0.0)
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.programs += 1
            if seconds > self._slowest[1]:
                self._slowest = (fun_name, seconds)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.programs, self.cache_hits

    def take_slowest(self):
        slowest, self._slowest = self._slowest, ("-", 0.0)
        return slowest


def _memory(devices):
    """Per-device allocator counters, where the backend reports them."""
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({
            k: stats.get(k)
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
        })
    return out


def _assert_every_chip_worked(results, devices, leg):
    """The run's final state has shards (or replicas) on every device, and
    every device's allocator has held bytes."""
    n = len(devices)
    assert results["workers"] == n, (leg, results["workers"], n)
    assert results["state_min_devices"] == n, (
        f"leg {leg}: some leaf of the trainer state lives on only "
        f"{results['state_min_devices']} of {n} devices"
    )
    mem = _memory(devices)
    if all(m["peak_bytes_in_use"] is not None for m in mem):
        idle = [str(d) for d, m in zip(devices, mem)
                if not m["peak_bytes_in_use"]]
        assert not idle, f"leg {leg}: devices never held memory: {idle}"
    return mem


def leg_a(tiny, devices):
    import numpy as np

    from mpit_tpu.run import run
    from mpit_tpu.utils.config import TrainConfig

    per_chip, image = (2, 32) if tiny else (32, 224)
    gb = per_chip * len(devices)
    steps = 4
    cfg = dataclasses.replace(
        TrainConfig().apply_preset("resnet50-sync"),
        global_batch=gb, train_size=gb * steps, epochs=1, image_size=image,
    )
    r = run(cfg)
    assert r["platform"] == devices[0].platform, r["platform"]
    assert r["trained_units"] == steps, r["trained_units"]
    assert np.isfinite(r["final_loss"]) and np.isfinite(r["eval_loss"]), r
    assert 0.0 <= r["accuracy"] <= 1.0, r["accuracy"]
    mem = _assert_every_chip_worked(r, devices, "A")
    return {
        "model": "resnet50", "image_size": image, "per_chip_batch": per_chip,
        "steps": r["trained_units"], "final_loss": round(r["final_loss"], 4),
        "eval_loss": round(r["eval_loss"], 4), "memory": mem,
    }


def leg_b(tiny, devices):
    import numpy as np

    from mpit_tpu.run import run
    from mpit_tpu.utils.config import TrainConfig

    per_chip, tau, rounds = (16 if tiny else 1024), 4, 3
    gb = per_chip * len(devices)
    cfg = dataclasses.replace(
        TrainConfig().apply_preset("mnist-easgd"),
        global_batch=gb, tau=tau, train_size=gb * tau * rounds, epochs=1,
    )
    r = run(cfg)
    assert r["platform"] == devices[0].platform, r["platform"]
    assert r["trained_units"] == rounds, r["trained_units"]
    assert np.isfinite(r["final_loss"]), r["final_loss"]
    assert 0.0 <= r["accuracy"] <= 1.0, r["accuracy"]
    _assert_every_chip_worked(r, devices, "B")
    return {
        "model": "lenet", "per_chip_batch": per_chip, "tau": tau,
        "rounds": r["trained_units"], "final_loss": round(r["final_loss"], 4),
    }


def leg_c(tiny, devices):
    import numpy as np

    from mpit_tpu.run import run
    from mpit_tpu.utils.config import TrainConfig

    steps = 8 if tiny else 40
    cfg = dataclasses.replace(
        TrainConfig().apply_preset("mnist-ps"),
        steps=steps, train_size=2048,
        **({"global_batch": 16} if tiny else {}),
    )
    r = run(cfg)
    assert r["platform"] == devices[0].platform, r["platform"]
    assert np.isfinite(r["final_loss"]), r["final_loss"]
    assert r["dead_clients"] == [], r["dead_clients"]
    exchanges = steps // cfg.tau
    pushes = sum(c["push_easgd"] for c in r["server_counts"])
    assert pushes == cfg.clients * exchanges, (pushes, r["server_counts"])
    # each client thread computed on a device of the platform under test
    assert len(r["client_devices"]) == cfg.clients
    kinds = {str(d) for d in devices}
    assert set(r["client_devices"]) <= kinds, (r["client_devices"], kinds)
    return {
        "transport": r["transport"], "client_devices": r["client_devices"],
        "clients": cfg.clients, "servers": cfg.servers, "steps": steps,
        "pushes": pushes, "final_loss": round(r["final_loss"], 4),
    }


def _first_divergence(model, params, prompt, served, solo):
    """Where two greedy continuations of one prompt part ways, and how
    close the two candidate tokens' logits were there (dense forward over
    the shared prefix, read as f32)."""
    import jax.numpy as jnp
    import numpy as np

    pos = next(i for i, (a, b) in enumerate(zip(served, solo)) if a != b)
    prefix = jnp.asarray([solo[:pos]], jnp.int32)
    logits = np.asarray(
        model.apply({"params": params}, prefix)[0, -1], np.float32
    )
    return {
        "position": pos, "generated_index": pos - len(prompt),
        "served_token": int(served[pos]), "solo_token": int(solo[pos]),
        "logit_margin": float(abs(logits[served[pos]] - logits[solo[pos]])),
        "max_abs_logit": float(np.max(np.abs(logits))),
    }


def _serve_parity(dims, compute_dtype, eps, reqs, slots, segment,
                  check_rows, device):
    """Drain ``reqs`` through a Server; compare ``check_rows`` of them with
    the solo generate_fast call.

    The batched segment program and the solo program are different XLA
    programs and may tile their reductions differently, so greedy rows are
    required to be token-identical *up to the first near-tie*: where a row
    departs from its solo decode, the two tokens' logits must lie within
    ``2 * eps * max|logit|`` of each other — rounding at the multiply
    precision ``eps``, fixed by the dtype before the run. A departure with
    a real margin is a scheduling bug and fails."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpit_tpu.models import Server, generate_fast
    from mpit_tpu.models.transformer import TransformerLM

    model = TransformerLM(**dims, compute_dtype=compute_dtype)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    # leg D stays on one device: jax's default, the first one
    assert jax.tree.leaves(params)[0].devices() == {device}
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, dims["vocab_size"], p).tolist()
               for p, _ in reqs]
    srv = Server(model, params, max_batch=slots, segment=segment)
    rids = [srv.submit(q, mn) for q, (_, mn) in zip(prompts, reqs)]
    out = srv.drain()
    srv.close()
    for rid, q, (_, mn) in zip(rids, prompts, reqs):
        toks = out[rid]
        assert len(toks) == len(q) + mn, (rid, len(toks), len(q), mn)
        assert toks[: len(q)] == q, f"request {rid}: prompt not echoed"
        assert all(0 <= t < dims["vocab_size"] for t in toks), rid
    identical, near_ties = 0, []
    for i in check_rows:
        solo = generate_fast(model, params, prompts[i], reqs[i][1])
        assert len(solo) == len(out[rids[i]])
        if out[rids[i]] == solo:
            identical += 1
            continue
        d = _first_divergence(model, params, prompts[i], out[rids[i]], solo)
        tol = 2 * eps * d["max_abs_logit"]
        assert d["logit_margin"] <= tol, (
            f"request {i}: served row departs from solo generate_fast with "
            f"a logit margin {d['logit_margin']:.3g} > {tol:.3g}: {d}"
        )
        near_ties.append({"request": i, "tol": round(tol, 6), **d})
    return {
        "requests": len(reqs), "segments": srv.segments_run,
        "generated_tokens": sum(mn for _, mn in reqs),
        "rows_compared": len(check_rows), "rows_identical": identical,
        "near_tie_departures": near_ties,
    }


def leg_d(tiny, devices):
    import jax
    import jax.numpy as jnp

    if tiny:
        dims = dict(vocab_size=101, num_layers=2, d_model=32, num_heads=4,
                    max_len=64)
        reqs = [(6 + (i * 3) % 10, 8 + (i * 5) % 12) for i in range(4)]
        slots, segment, rows = 2, 8, (0, 3)  # 3 is a late admission
    else:
        # the width bench.py's serving modes use (GPT-2-small block, 6 deep)
        dims = dict(vocab_size=10_000, num_layers=6, d_model=768,
                    num_heads=12, max_len=512)
        # prompts 32..128, budgets 64..128; two more requests than slots,
        # so two are admitted into slots that earlier requests retired from
        reqs = [(32 + (i * 13) % 97, 64 + (i * 29) % 65) for i in range(10)]
        slots, segment, rows = 8, 64, (0, 3, 6, 9)
    # the dtype users serve in: the MXU multiplies in bf16 (eps 2^-8)
    served = _serve_parity(dims, jnp.bfloat16, 2.0 ** -8, reqs, slots,
                           segment, rows, devices[0])
    # the scheduler check proper: float32 at full multiply precision, where
    # rounding cannot reorder an argmax — rows must match token for token
    # (a near-tie allowance of 2^-23 relative is exactness in practice)
    with jax.default_matmul_precision("highest"):
        exact = _serve_parity(dims, jnp.float32, 2.0 ** -23, reqs, slots,
                              segment, rows, devices[0])
    return {"bfloat16": served, "float32_highest": exact}


def leg_e(tiny, devices):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpit_tpu.ops.elastic import elastic_update, pallas_interpret
    from mpit_tpu.ops.flash_attention import flash_attention
    from mpit_tpu.ops.ring_attention import dense_attention

    compiled = devices[0].platform == "tpu"
    assert pallas_interpret() == (not compiled)

    def assert_mosaic(fn, *args):
        """The lowered program carries a Mosaic custom call — the kernel
        was compiled for the chip, not interpreted op by op."""
        if compiled:
            text = jax.jit(fn).lower(*args).as_text()
            assert "tpu_custom_call" in text, "no Mosaic kernel in lowering"

    # what ptb-transformer-large hands the attention: B=2 T=512 H=12 D=64
    b, t, h, d = (1, 64, 2, 16) if tiny else (2, 512, 12, 64)
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.bfloat16)
        for _ in range(3)
    )
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, use_pallas=True
    )
    dense = lambda q, k, v: dense_attention(q, k, v, causal=True)
    assert_mosaic(flash, q, k, v)
    f32 = lambda a: np.asarray(a, np.float32)
    out, ref = flash(q, k, v), dense(q, k, v)
    assert out.shape == ref.shape == (b, t, h, d)
    fwd_err = float(np.max(np.abs(f32(out) - f32(ref))))
    np.testing.assert_allclose(f32(out), f32(ref), rtol=2e-2, atol=2e-2)

    w = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    loss = lambda fn: lambda q, k, v: jnp.sum(
        fn(q, k, v).astype(jnp.float32) * w
    )
    assert_mosaic(jax.grad(loss(flash), argnums=(0, 1, 2)), q, k, v)
    g_flash = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    bwd_err = 0.0
    for gf, gd in zip(g_flash, g_dense):
        assert np.isfinite(f32(gf)).all()
        bwd_err = max(bwd_err, float(np.max(np.abs(f32(gf) - f32(gd)))))
        # bf16 gradients of a 512-long softmax: the two paths round
        # differently, so compare at bf16 resolution of the largest entry
        np.testing.assert_allclose(
            f32(gf), f32(gd), rtol=5e-2,
            atol=5e-2 * float(np.max(np.abs(f32(gd)))),
        )

    # the shape gpt2s_easgd_1chip_flash runs (GPT-2-small, batch 8): both
    # branches of attn_impl against dense attention on float32 inputs at
    # the highest matmul precision. The kernel's speed is not bought with
    # precision: its error may be at most 1.5 times the xla branch's.
    cell = (1, 128, 2, 16) if tiny else (8, 1024, 12, 64)
    q, k, v = (
        jnp.asarray(rng.standard_normal(cell), jnp.bfloat16) for _ in range(3)
    )
    w = jnp.asarray(rng.standard_normal(cell), jnp.float32)
    out_and_grads = lambda fn: jax.jit(lambda q, k, v: (
        fn(q, k, v), *jax.grad(loss(fn), argnums=(0, 1, 2))(q, k, v)
    ))
    with jax.default_matmul_precision("highest"):
        exact = [f32(a) for a in out_and_grads(dense)(
            *(a.astype(jnp.float32) for a in (q, k, v))
        )]
    errors = {}
    for branch, fn in (("flash", flash), ("xla", dense)):
        got = [f32(a) for a in out_and_grads(fn)(q, k, v)]
        errors[branch] = {
            name: {
                "max_abs": float(np.max(np.abs(g - e))),
                "rel": float(np.linalg.norm(g - e) / np.linalg.norm(e)),
            }
            for name, g, e in zip(("out", "dq", "dk", "dv"), got, exact)
        }
    for name, kernel_err in errors["flash"].items():
        for kind, err in kernel_err.items():
            assert err <= 1.5 * errors["xla"][name][kind], (
                f"flash {name} {kind} error {err:.3g} is over 1.5 times the "
                f"xla branch's {errors['xla'][name][kind]:.3g}"
            )
    rounded = lambda e: {
        name: {kind: float(f"{x:.3g}") for kind, x in by.items()}
        for name, by in e.items()
    }

    n = 3_000 if tiny else 1_000_003  # not a multiple of the block: pads
    x, c, dd = (
        jnp.asarray(rng.standard_normal(n), jnp.float32) for _ in range(3)
    )
    kernel = lambda x, c, dd: elastic_update(x, c, dd, 0.3, use_pallas=True)
    assert_mosaic(kernel, x, c, dd)
    got = kernel(x, c, dd)
    want = elastic_update(x, c, dd, 0.3, use_pallas=False)
    for a, e in zip(got, want):
        np.testing.assert_allclose(f32(a), f32(e), rtol=1e-6, atol=1e-6)
    assert got[0].devices() == {devices[0]}
    return {
        "compiled": compiled, "flash_shape": [b, t, h, d],
        "flash_fwd_max_abs_err": round(fwd_err, 5),
        "flash_bwd_max_abs_err": round(bwd_err, 5), "elastic_n": n,
        "cell_shape": list(cell),
        "cell_flash_vs_f32_highest": rounded(errors["flash"]),
        "cell_xla_vs_f32_highest": rounded(errors["xla"]),
    }


LEGS = (("A", leg_a), ("B", leg_b), ("C", leg_c), ("D", leg_d), ("E", leg_e))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-wiring", action="store_true",
        help="tiny sizes on the CPU platform to check control flow before "
        "spending chip time; never prints the pass marker",
    )
    ns = ap.parse_args(argv)

    import jax
    import jaxlib

    from mpit_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(
        f"chip_smoke: platform: {device['platform']}  device_kind: "
        f"{device['kind']!r}  devices: {device['count']}  jax {jax.__version__}"
        f"  jaxlib {jaxlib.__version__}  compile cache: {cache_dir}",
        flush=True,
    )
    print("chip_smoke: data = the loaders' seeded synthetic sets; weights = "
          "random from a seed; no network, no git", flush=True)
    if ns.cpu_wiring:
        if device["platform"] != "cpu":
            print("chip_smoke: --cpu-wiring needs JAX_PLATFORMS=cpu",
                  file=sys.stderr)
            return 2
        print("chip_smoke: WIRING ONLY — tiny shapes on cpu, pallas "
              "interpreted; nothing below is a device result", flush=True)
    elif device["platform"] != "tpu":
        print(
            f"chip_smoke: no TPU (jax.devices()[0].platform == "
            f"{device['platform']!r}); refusing to run — use --cpu-wiring "
            "for a control-flow check on cpu",
            file=sys.stderr,
        )
        return 3

    meter = _CompileMeter()
    t_all = time.perf_counter()
    for name, leg in LEGS:
        c0, p0, h0 = meter.snapshot()
        t0 = time.perf_counter()
        detail = leg(ns.cpu_wiring, devices)
        wall = time.perf_counter() - t0
        c1, p1, h1 = meter.snapshot()
        slow_name, slow_s = meter.take_slowest()
        print(
            f"chip_smoke: leg {name} ok  wall {wall:7.2f} s  compile "
            f"{c1 - c0:7.2f} s ({p1 - p0} programs, {h1 - h0} from cache; "
            f"slowest {slow_name} {slow_s:.2f} s)  " + json.dumps(detail),
            flush=True,
        )
    print(
        f"chip_smoke: all legs ok  wall {time.perf_counter() - t_all:.2f} s"
        f"  compile {meter.seconds:.2f} s ({meter.programs} programs, "
        f"{meter.cache_hits} from cache)",
        flush=True,
    )
    if ns.cpu_wiring:
        print("chip_smoke: wiring run complete (no pass marker on cpu)")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
