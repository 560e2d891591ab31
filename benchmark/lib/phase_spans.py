"""Device time by phase (forward, recomputation, backward, update), by
provenance and by what a fusion holds besides its own scope, read from this
process's own trace: one more reduction beside ``program_spans``', keyed not
by a tuple of scope names kept here but by the program's own table,
``profiling.unit_scope_table()`` (``mpit_tpu/utils/profiling.py``: every
instruction's scope ``path``, ``layer``, ``phase``, ``opcode``, ``how`` it
got them and, for a fusion, what it ``holds`` and has ``fused``).

It reuses ``program_spans``' loader, its choice of the trace and of the unit
(one run of the program that took most of the traced time, device 0) and
``trace_reduce``'s self times, so a ``while`` does not count its body and
the phases add up to ``program_spans``' ``busy_ms_unit``. At a commit whose
program has no table, in a run without a device plane and in a cell whose
program has no such phase, every function here returns None and the metric
is left out of the line.
"""

import functools
import json
import time

from benchmark.lib import program_spans, trace_reduce

PHASES = ("forward", "recompute", "backward", "update", "mixed", "unnamed")
UNSCOPED = "unscoped"
#: the compiler's own copies, by opcode
COPIES = ("copy", "copy-start", "copy-done")
#: the opcodes that only move bytes the program already had: a fusion whose
#: fused computation holds nothing else is a layout change, not arithmetic
MOVEMENT = frozenset({
    "parameter", "copy", "transpose", "bitcast", "reshape", "tuple",
    "get-tuple-element", "slice", "dynamic-slice", "dynamic-update-slice",
    "concatenate", "pad", "constant"})
#: an event whose instruction the table does not hold
_UNKNOWN = {"path": [], "layer": None, "phase": "unnamed", "opcode": "",
            "how": "none"}


def program_table():
    """``(table, scopes)`` of the unit the fit loop ran, or ``(None, None)``
    where the program has no table or no unit."""
    try:
        from mpit_tpu.utils import profiling

        return profiling.unit_scope_table(), profiling.scopes()
    except (ImportError, AttributeError):
        return None, None


def moves_only(row: dict) -> bool:
    """A copy, or a fusion of nothing but ``MOVEMENT``."""
    if row["opcode"] in COPIES:
        return True
    fused = row.get("fused")
    return row["opcode"] == "fusion" and bool(fused) and set(fused) <= MOVEMENT


def holds_optimizer(row: dict) -> bool:
    """A fusion under another scope that XLA gave an optimizer update too."""
    return (row["path"][-1:] != ["optimizer"]
            and any(scope == "optimizer" for scope, _ in row.get("holds", ())))


def reduce(lines: dict, table: dict, scopes, top: int = 12) -> dict:
    """One device plane (``XLA Modules`` and ``XLA Ops``) against the table:
    self time a unit by phase, by outer and innermost scope and phase, by
    layer and by ``how``; the four metrics; the longest copies and the
    longest fusions that hold another scope."""
    modules, ops = lines["XLA Modules"], lines["XLA Ops"]
    units = program_spans.reduce_scopes(lines, {})["units"]
    lo = min(s for _, s, _ in modules)
    hi = max(s + d for _, s, d in modules)
    selfs, _ = trace_reduce.self_times([ev for ev in ops if lo <= ev[1] < hi])
    by_event = {}  # an instruction runs many times: name it once
    for name, self_ns, _ in selfs:
        key = program_spans.instruction(name)
        by_event[key] = by_event.get(key, 0.0) + self_ns

    def add(sums, key, ns):
        sums[key] = sums.get(key, 0.0) + ns

    phase, how, layer, accepted = {}, {}, {}, {}
    outer, inner = {}, {}
    metrics = dict.fromkeys(("recompute_ms_unit", "layout_copy_ms_unit",
                             "unscoped_ms_unit", "optimizer_fused_ms_unit"), 0.0)
    copies, holding = [], []
    for key, ns in by_event.items():
        row = table.get(key, _UNKNOWN)
        path = row["path"]
        add(phase, row["phase"], ns)
        add(how, row["how"], ns)
        add(layer, row["layer"] or "none", ns)
        add(outer.setdefault(path[0] if path else UNSCOPED, {}), row["phase"], ns)
        add(inner.setdefault(path[-1] if path else UNSCOPED, {}), row["phase"], ns)
        if row["how"] == "own":  # what the accepted scope metrics read
            last = next((s for s in reversed(path)
                         if s in program_spans.SCOPES), None)
            if last:
                add(accepted, last, ns)
        if row["phase"] == "recompute":
            metrics["recompute_ms_unit"] += ns
        if not path:
            metrics["unscoped_ms_unit"] += ns
        if moves_only(row):
            metrics["layout_copy_ms_unit"] += ns
            copies.append((ns, key, row))
        if row.get("holds"):
            holding.append((ns, key, row))
            if holds_optimizer(row):
                metrics["optimizer_fused_ms_unit"] += ns
    per_unit = lambda ns: ns / units / 1e6
    scaled = lambda sums: {k: per_unit(v) for k, v in sorted(sums.items())}
    longest = lambda found: sorted(found, key=lambda f: -f[0])[:top]
    return {
        "units": units,
        **{name: per_unit(ns) for name, ns in metrics.items()},
        "phase_ms_unit": {p: per_unit(phase.get(p, 0.0)) for p in PHASES},
        "busy_ms_unit": per_unit(sum(by_event.values())),
        "outer_ms_unit": {k: scaled(v) for k, v in sorted(outer.items())},
        "inner_ms_unit": {k: scaled(v) for k, v in sorted(inner.items())},
        "layer_ms_unit": scaled(layer),
        "how_ms_unit": scaled(how),
        "own_accepted_ms_unit": scaled(accepted),
        "copies_top": [[key, row["opcode"], row["how"], "/".join(row["path"]),
                        row["phase"], per_unit(ns)]
                       for ns, key, row in longest(copies)],
        "holding_top": [[key, "/".join(row["path"]), row["phase"],
                         row["holds"], per_unit(ns)]
                        for ns, key, row in longest(holding)],
        "phases_in_program": sorted({row["phase"] for row in table.values()}),
        "scopes": list(scopes),
    }


@functools.lru_cache(maxsize=1)
def traced():
    """``reduce`` of this process's trace on its first device, once for all
    readers, with the ``phase_spans`` detail line; None without a table or
    a device plane."""
    path = program_spans.own_xplane()
    if path is None:
        return None
    started = time.perf_counter()
    table, scopes = program_table()
    if not table:
        return None
    tabled = time.perf_counter()
    planes = program_spans.load(path)
    devices = [n for n in planes if n.startswith(trace_reduce.DEVICE_PLANE)
               and "XLA Modules" in planes[n] and "XLA Ops" in planes[n]]
    if not devices:
        return None
    first = min(devices, key=lambda n: int(n[len(trace_reduce.DEVICE_PLANE):]))
    out = reduce(planes[first], table, scopes)
    # what this reduction cost the traced run, on the host's clock: the
    # table (the unit's compile too, where no reader before this one paid
    # it), then the trace's second load and the sums
    out["cost_s"] = {"table": tabled - started,
                     "load_and_reduce": time.perf_counter() - tabled,
                     "instructions": len(table)}
    print(json.dumps({"detail": "phase_spans", "value": out}), flush=True)
    return out


def metric(run: dict, name: str):
    """One of the four metrics, ms a unit; None without a device trace, and
    where the program has nothing of the kind: no instruction in the phase
    ``recompute`` (no remat), no scope ``optimizer``."""
    if not run.get("trace"):
        return None
    out = traced()
    if out is None:
        return None
    if name == "recompute_ms_unit" and "recompute" not in out["phases_in_program"]:
        return None
    if name == "optimizer_fused_ms_unit" and "optimizer" not in out["scopes"]:
        return None
    return out[name]
