#!/usr/bin/env python3
"""Write the lowered text of a benchmark cell's unit program, to compare two
trees to the byte without the chip.

    python3 scripts/lowered_step.py ROOT CELL OUT

The trainer's own jitted callable (a sync step, an EASGD round) of ``CELL`` as
``benchmark/drivers`` build it from the tree at ``ROOT``, at the cell's full
size, traced on abstract arguments (nothing is allocated or compiled) and
lowered for the TPU platform with the choices the chip makes: the Pallas
kernels where ``pallas_supported()`` takes them, compiled and not
interpreted. Prints the text's length, its SHA-256 and the number of kernel
calls. The text is lowered without source locations
(``jax_traceback_in_locations_limit`` 0): a kernel's body would otherwise
carry the path and line number of every frame that reached it, so that an
edit which moves line numbers in ``models/transformer.py`` or
``models/arch.py`` and nothing else changed six kernel bodies by a few bytes
each (seen in PR 34). Run ONE copy of this script on both trees:

    python3 scripts/lowered_step.py <parent's tree> CELL a.txt
    python3 scripts/lowered_step.py <this tree>     CELL b.txt && cmp a.txt b.txt

A cell whose model is described by a configuration (any driver but
``train:run``) is built as its driver builds it, with ``arch_of(config)``.

A text that is the same says the program is; it is no chip run.
"""

import dataclasses
import hashlib
import importlib
import json
import os
import sys


def main(root: str, cell: str, out: str) -> int:
    sys.path.insert(0, root)
    os.chdir(root)
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_traceback_in_locations_limit", 0)
    import mpit_tpu
    from benchmark.drivers import train as train_driver
    from mpit_tpu import run as program
    from mpit_tpu.parallel import common, easgd

    # as the chip chooses
    for name in ("flash_attention", "ssd", "gated_delta", "grouped"):
        try:
            ops = importlib.import_module(f"mpit_tpu.ops.{name}")
        except ImportError:
            continue
        if hasattr(ops, "pallas_supported"):
            ops.pallas_supported = lambda: True
            ops.pallas_interpret = lambda: False

    load = lambda path: json.load(open(path))
    manifest = load("BENCHMARK.json")
    chips = next(w for w in manifest["workloads"] if w["name"] == cell)
    config = load(next(c for c in manifest["configs"]
                       if c["name"] == chips["config"])["file"])
    cfg, job, sizes = train_driver.build_config({
        "workload": load(f"benchmark/workloads/{cell}.json"),
        "config": config, "rehearsal": False, "chips": 1})
    if cfg.arch is None and job.get("driver", "train:run") != "train:run":
        from benchmark.drivers import train_lm  # a described model's driver

        cfg = dataclasses.replace(cfg, arch=train_lm.arch_of(config))
    topo = mpit_tpu.init(num_workers=1)
    model = program._build_model(cfg, sizes, worker_axis=topo.worker_axis)
    opt = program.build_optimizer(cfg, job["total_updates"])
    trainer = program.build_trainer(cfg, model, opt, topo)
    x = jax.ShapeDtypeStruct((job["per_chip_batch"], cfg.seq_len), jnp.int32)
    params_of = lambda key, tokens: model.init(key, tokens)["params"]
    if hasattr(trainer, "_step"):
        unit = trainer._step
        state = jax.eval_shape(lambda k, t: common.TrainState.create(
            params_of(k, t), opt), jax.random.key(0), x)
    else:  # an EASGD round: tau batches, one worker
        unit = trainer._round

        def one_worker(key, tokens):
            params = params_of(key, tokens)
            stack = lambda tree: jax.tree.map(lambda a: a[None], tree)
            return easgd.EASGDState(
                worker_params=stack(params), worker_opt=stack(opt.init(params)),
                center=params, round=jnp.zeros((), jnp.int32))

        state = jax.eval_shape(one_worker, jax.random.key(0), x)
        x = jax.ShapeDtypeStruct((1, cfg.tau) + x.shape, jnp.int32)
    text = unit.trace(state, x, x).lower(lowering_platforms=("tpu",)).as_text()
    with open(out, "w") as f:
        f.write(text)
    print(json.dumps({"cell": cell, "bytes": len(text),
                      "sha256": hashlib.sha256(text.encode()).hexdigest(),
                      "kernel_calls": text.count("tpu_custom_call")}))
    return 0


if __name__ == "__main__":
    if len(sys.argv) != 4:
        sys.exit(__doc__)
    sys.exit(main(*sys.argv[1:]))
