"""The scopes a gated-delta-rule mixer adds
(``models/transformer.Block._linear_attention_mixer``), read from this
process's own trace: ``ssm_spans``' reduction for another set of scopes
(``ssm_spans`` is an accepted file with a closed tuple of them).

It reuses ``program_spans``' loader, its choice of the trace, its map from
instruction to ``op_name`` and ``trace_reduce``'s self times. Time is counted
UNDER a scope, wherever in the path it stands, so ``linattn`` (the whole
mixer: projections, the block's norm, residual) holds ``linattn_conv``,
``delta_rule`` and ``linattn_gate``, which lie inside it. At a commit without
these scopes everything here returns None and the metric is left out of the
line.
"""

import functools
import json

from benchmark.lib import delta_kernels, program_spans, trace_reduce

SCOPES = ("linattn", "linattn_conv", "delta_rule", "linattn_gate")


def scopes_of(op_name: str) -> set:
    """Every one of ``SCOPES`` among the path components of ``op_name``."""
    found = set()
    for part in op_name.split("/"):
        inner = program_spans._WRAPPED.match(part)
        if inner and inner.group(1) in SCOPES:
            found.add(inner.group(1))
    return found


def reduce(lines: dict, op_names: dict) -> dict:
    """One device plane (``XLA Modules`` and ``XLA Ops``): self time a unit
    under each scope. A unit is one run of the program that took most of the
    traced time, as in ``program_spans``."""
    modules, ops = lines["XLA Modules"], lines["XLA Ops"]
    units = program_spans.reduce_scopes(lines, op_names)["units"]
    lo = min(s for _, s, _ in modules)
    hi = max(s + d for _, s, d in modules)
    selfs, _ = trace_reduce.self_times([ev for ev in ops if lo <= ev[1] < hi])
    under = {}
    for name, self_ns, _ in selfs:
        key = program_spans.instruction(name)
        for scope in scopes_of(op_names.get(key, "")):
            under[scope] = under.get(scope, 0.0) + self_ns
    return {
        "units": units,
        "under_ms_unit": {s: ns / units / 1e6 for s, ns in sorted(under.items())},
        "scopes_in_program": sorted(
            set().union(*map(scopes_of, op_names.values())) if op_names else ()),
    }


@functools.lru_cache(maxsize=1)
def traced():
    """``reduce`` of this process's trace on its first device, once for all
    readers, with the ``delta_spans`` detail line; None without a device
    plane."""
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
    out = reduce(planes[first], program_spans.op_names_of(text))
    print(json.dumps({"detail": "delta_spans", "value": out}), flush=True)
    return out


def under_ms_unit(run: dict, scope: str):
    """Device self time a unit under ``scope``; None without a device trace
    or where the program does not set it."""
    if not run.get("trace"):
        return None
    out = traced()
    if out is None or scope not in out["scopes_in_program"]:
        return None
    return out["under_ms_unit"].get(scope, 0.0)


def delta_roofline_pct(run: dict):
    """The least time the chip could take for the step's recurrences
    (``delta_kernels.least_seconds_unit``) over the device time under
    ``delta_rule``. None where the run names no recurrence's shape or the
    trace holds no time under the scope."""
    shape = (run.get("kernels") or {}).get("delta")
    took_ms = under_ms_unit(run, "delta_rule") if shape else None
    if not took_ms:
        return None
    return 100.0 * 1e3 * delta_kernels.least_seconds_unit(
        shape, run["device_kind"]) / took_ms
