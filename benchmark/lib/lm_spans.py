"""The scopes and kernels a model described by ``TrainConfig.arch`` adds,
read from this process's own trace: one more reduction beside
``program_spans``', which is an accepted file with a closed tuple of scopes.

It reuses that file's loader, its choice of the trace, its map from
instruction to ``op_name`` and ``trace_reduce``'s self times; what is its own
is the list of scope names (``models/transformer.py`` sets them inside
``attention``, ``attn_proj`` and ``mlp``, so the accepted metrics still count
them there) and the kernels' names: a Pallas kernel's custom call is named
after its innermost scope, ``flash_window_fwd.7``. At a commit without these
scopes everything here returns None and the metric is left out of the line.
"""

import functools
import json
import re

from benchmark.lib import lm_kernels, program_spans, trace_reduce

#: innermost first matters nowhere: they do not nest in one another
SCOPES = ("attn_window", "attn_full", "rope", "attn_gate", "moe_router",
          "moe_dispatch", "moe_experts", "moe_shared")
#: kernel family -> the scope (and so the instruction name) of each kernel
KERNELS = {
    "flash_window": {"fwd": "flash_window_fwd", "dq": "flash_window_dq",
                     "dkv": "flash_window_dkv"},
    "flash_causal": {"fwd": "flash_fwd", "dq": "flash_dq", "dkv": "flash_dkv"},
}
#: the TPU compiler's own grouped-product kernels, which ``jax.lax.
#: ragged_dot`` becomes and only the expert layer calls; their custom calls
#: carry no ``op_name`` (seen on the v5e, PR 27), so they are named here
COMPILER_KERNELS = ("ragged-dot",)
_NUMBERED = re.compile(r"\.\d+$")


def scope_of(op_name: str):
    """The last path component of ``op_name`` that is one of ``SCOPES``."""
    for part in reversed(op_name.split("/")):
        inner = program_spans._WRAPPED.match(part)
        if inner and inner.group(1) in SCOPES:
            return inner.group(1)
    return None


def reduce(lines: dict, op_names: dict) -> dict:
    """One device plane (``XLA Modules`` and ``XLA Ops``): self time a unit by
    scope, and each kernel's calls and time a unit. A unit is one run of the
    program that took most of the traced time, as in ``program_spans``."""
    modules, ops = lines["XLA Modules"], lines["XLA Ops"]
    units = program_spans.reduce_scopes(lines, op_names)["units"]
    lo = min(s for _, s, _ in modules)
    hi = max(s + d for _, s, d in modules)
    selfs, _ = trace_reduce.self_times([ev for ev in ops if lo <= ev[1] < hi])
    scope_ns, kernel_ns, kernel_calls = {}, {}, {}
    for name, self_ns, _ in selfs:
        key = program_spans.instruction(name)
        scope = scope_of(op_names.get(key, ""))
        if scope is None and key.startswith(COMPILER_KERNELS):
            scope = "moe_experts"
        if scope is not None:
            scope_ns[scope] = scope_ns.get(scope, 0.0) + self_ns
        kernel = _NUMBERED.sub("", key)
        kernel_ns[kernel] = kernel_ns.get(kernel, 0.0) + self_ns
        kernel_calls[kernel] = kernel_calls.get(kernel, 0) + 1
    named = {k for family in KERNELS.values() for k in family.values()}
    return {
        "units": units,
        "scope_ms_unit": {s: ns / units / 1e6
                          for s, ns in sorted(scope_ns.items())},
        "kernels": {k: {"calls_unit": kernel_calls[k] / units,
                        "ms_unit": kernel_ns[k] / units / 1e6}
                    for k in sorted(named & set(kernel_ns))},
        "scopes_in_program": sorted(
            {s for s in map(scope_of, op_names.values()) if s}),
    }


@functools.lru_cache(maxsize=1)
def traced():
    """``reduce`` of this process's trace on its first device, once for all
    readers, with the ``lm_spans`` detail line; None without a device plane."""
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
    print(json.dumps({"detail": "lm_spans", "value": out}), flush=True)
    return out


def scope_ms_unit(run: dict, *scopes: str):
    """Sum of the scopes' device self time a unit; None without a device
    trace or where the program sets none of ``scopes``."""
    if not run.get("trace"):
        return None
    out = traced()
    if out is None or not set(scopes) & set(out["scopes_in_program"]):
        return None
    return sum(out["scope_ms_unit"].get(s, 0.0) for s in scopes)


def roofline_pct(run: dict, family: str):
    """The family's three kernels together: the least time the chip could
    take for the calls the trace holds (``lm_kernels``), over the time they
    took. None where the run names no such kernel's shape or the trace holds
    no call of it."""
    shape = (run.get("kernels") or {}).get(family)
    if not run.get("trace") or not shape:
        return None
    out = traced()
    if out is None:
        return None
    least_ms = took_ms = 0.0
    for kind, kernel in KERNELS[family].items():
        seen = out["kernels"].get(kernel)
        if seen:
            least_ms += seen["calls_unit"] * 1e3 * lm_kernels.least_seconds(
                kind, shape, run["device_kind"])
            took_ms += seen["ms_unit"]
    return 100.0 * least_ms / took_ms if took_ms else None
