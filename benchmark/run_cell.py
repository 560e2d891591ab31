#!/usr/bin/env python3
"""The benchmark's one command: run one cell of ``BENCHMARK.json``.

    python3 benchmark/run_cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It finds everything by the names in ``BENCHMARK.json``: the cell's job in
``benchmark/workloads/<cell>.json``, its configuration in the file the
manifest names, its driver (``benchmark/drivers/``, training unless the job
says otherwise) and, in a traced run, each per-layer metric's reader
(``benchmark/layer_metrics/<name>.json`` -> ``benchmark/readers/``). It knows
no cell, configuration or metric by name. Earlier lines of its output are
details (``{"detail": ..., "value": ...}``); the last line is the result.

Without an accelerator it exits non-zero and prints no result, unless a
rehearsal is asked for with ``--rehearsal``: then it runs the job's tiny
``rehearsal`` sizes on whatever jax finds, and the result names that device.
"""

import argparse
import importlib
import json
import os
import sys
import time

T0 = time.perf_counter()  # set-up counts from here: imports, backend, all of it
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, ".bench_out")  # git-ignored; traces go here


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def named(entries, name, what):
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise SystemExit(f"{what} {name!r}: {len(found)} entries in BENCHMARK.json")
    return found[0]


def cell_metrics(manifest, group, cell):
    """The manifest's metrics of ``group`` that ``cell`` reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def resolve(spec, package):
    """``"<module>:<function>"`` under ``benchmark/<package>/``."""
    module, _, function = spec.partition(":")
    return getattr(importlib.import_module(f"benchmark.{package}.{module}"),
                   function)


def detail(what, value):
    print(json.dumps({"detail": what, "value": value}, default=repr), flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearsal", action="store_true",
                        help="run the job's tiny sizes on any device (CPU)")
    args = parser.parse_args(argv)

    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = named(manifest["workloads"], args.workload, "workload")
    workload = load_json(HERE, "workloads", f"{cell['name']}.json")
    config = load_json(ROOT, named(manifest["configs"], cell["config"],
                                   "config")["file"])

    sys.path.insert(0, ROOT)  # the program, and ``benchmark`` as a package
    import jax

    from mpit_tpu.utils.compile_cache import enable_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu" and not args.rehearsal:
        print("no accelerator: jax found only the CPU (a CPU rehearsal of "
              "the tiny sizes is asked for with --rehearsal)", file=sys.stderr)
        return 3
    if len(devices) != cell["chips"]:
        print(f"cell {cell['name']!r} needs {cell['chips']} chip(s); jax "
              f"found {len(devices)}", file=sys.stderr)
        return 3

    from benchmark.lib.timing import CompileMeter

    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    detail("start", {"workload": cell["name"], "seed": args.seed,
                     "seconds": args.seconds, "trace": args.trace,
                     "rehearsal": args.rehearsal, "compile_cache": cache_dir,
                     "jax": jax.__version__})

    driver = resolve(workload.get("driver", "train:run"), "drivers")
    result = driver({
        "args": args, "t0": T0, "chips": cell["chips"], "workload": workload,
        "config": config, "rehearsal": args.rehearsal, "meter": meter,
        "detail": detail, "out_dir": os.path.join(OUT_DIR, cell["name"]),
    })

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(manifest, group, cell["name"]):
        if args.trace:
            own = load_json(HERE, "layer_metrics", f"{m['name']}.json")
            value = resolve(own["reader"], "readers")(result["run"])
            if value is None:  # nothing to read in this run: left out
                continue
        elif m["name"] == "setup_s":
            value = result["setup_s"]
        else:
            value = result["end_to_end"][m["name"]]
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    trace = result["run"].get("trace")
    if args.trace and trace:
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        line["breakdown"] = trace["breakdown"]
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
