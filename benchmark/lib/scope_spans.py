"""Device time UNDER a scope, wherever in the path it stands, for the scopes a
described model's mixer adds, read from this process's own trace: one
reduction beside ``program_spans``' and ``lm_spans``', which count an event
once, under its last scope.

A family of scopes is a module of ``benchmark/lib/`` that names them in
``SCOPES`` and its detail line in ``SPANS``, beside the shapes of the kernels
the step runs (``kernel_shapes``, which the configuration's ``comparison``
block names) and the least time they need (``least_seconds_unit``):
``ssm_kernels`` for the Mamba-2 mixer (``models/transformer.Block.
_ssm_mixer``), ``delta_kernels`` for the gated-delta-rule mixer
(``Block._linear_attention_mixer``). A further mixer brings its own module
and readers and edits nothing here.

It reuses ``program_spans``' loader, its choice of the trace, its map from
instruction to ``op_name`` and ``trace_reduce``'s self times. Time under a
scope holds the scopes inside it: ``ssm`` (the whole mixer: norm, both
projections, residual) holds ``ssm_conv``, ``ssd`` and ``ssm_gate``. At a
commit without a family's scopes everything here returns None and the metric
is left out of the line.
"""

import functools
import importlib
import json

from benchmark.lib import program_spans, trace_reduce


def family(name: str):
    """``benchmark/lib/<name>.py``: its ``SCOPES``, ``SPANS`` and
    ``least_seconds_unit``."""
    return importlib.import_module(f"benchmark.lib.{name}")


def scopes_of(op_name: str, scopes) -> set:
    """Every one of ``scopes`` among the path components of ``op_name``."""
    found = set()
    for part in op_name.split("/"):
        inner = program_spans._WRAPPED.match(part)
        if inner and inner.group(1) in scopes:
            found.add(inner.group(1))
    return found


def reduce(lines: dict, op_names: dict, scopes) -> dict:
    """One device plane (``XLA Modules`` and ``XLA Ops``): self time a unit
    under each of ``scopes``. A unit is one run of the program that took most
    of the traced time, as in ``program_spans``."""
    modules, ops = lines["XLA Modules"], lines["XLA Ops"]
    units = program_spans.reduce_scopes(lines, op_names)["units"]
    lo = min(s for _, s, _ in modules)
    hi = max(s + d for _, s, d in modules)
    selfs, _ = trace_reduce.self_times([ev for ev in ops if lo <= ev[1] < hi])
    under = {}
    for name, self_ns, _ in selfs:
        key = program_spans.instruction(name)
        for scope in scopes_of(op_names.get(key, ""), scopes):
            under[scope] = under.get(scope, 0.0) + self_ns
    in_program = set().union(
        *(scopes_of(n, scopes) for n in op_names.values())) if op_names else ()
    return {
        "units": units,
        "under_ms_unit": {s: ns / units / 1e6 for s, ns in sorted(under.items())},
        "scopes_in_program": sorted(in_program),
    }


@functools.lru_cache(maxsize=1)
def first_plane():
    """This process's trace on its first device and the unit program's map
    from instruction to ``op_name``, loaded once for every family; None
    without a device plane or the program's text."""
    path = program_spans.own_xplane()
    if path is None:
        return None
    try:
        from mpit_tpu.utils import profiling

        text = profiling.unit_program_text()
    except (ImportError, AttributeError):
        text = None
    planes = program_spans.load(path)
    devices = [n for n in planes if n.startswith(trace_reduce.DEVICE_PLANE)
               and "XLA Modules" in planes[n] and "XLA Ops" in planes[n]]
    if not devices or not text:
        return None
    first = min(devices, key=lambda n: int(n[len(trace_reduce.DEVICE_PLANE):]))
    return planes[first], program_spans.op_names_of(text)


@functools.lru_cache(maxsize=None)
def traced(name: str):
    """``reduce`` of ``first_plane()`` under the family's ``SCOPES``, once for
    all its readers, with its ``SPANS`` detail line; None without a device
    plane."""
    loaded = first_plane()
    if loaded is None:
        return None
    kind = family(name)
    out = reduce(*loaded, kind.SCOPES)
    print(json.dumps({"detail": kind.SPANS, "value": out}), flush=True)
    return out


def under_ms_unit(run: dict, name: str, scope: str):
    """Device self time a unit under ``scope`` of family ``name``; None
    without a device trace or where the program does not set the scope."""
    if not run.get("trace"):
        return None
    out = traced(name)
    if out is None or scope not in out["scopes_in_program"]:
        return None
    return out["under_ms_unit"].get(scope, 0.0)


def roofline_pct(run: dict, name: str, kernel: str, scope: str):
    """The least time the chip could take for the step's ``kernel`` (its
    shape ``run["kernels"][kernel]``, the family's ``least_seconds_unit``)
    over the device time under ``scope``. None where the run names no such
    shape or the trace holds no time under the scope."""
    shape = (run.get("kernels") or {}).get(kernel)
    took_ms = under_ms_unit(run, name, scope) if shape else None
    if not took_ms:
        return None
    return 100.0 * 1e3 * family(name).least_seconds_unit(
        shape, run["device_kind"]) / took_ms
