"""What the program names itself, read from inside the benchmark's process:
the host spans of ``mpit_tpu.utils.profiling`` (``mpit.*``: a registry of
host-clock durations, and in a traced run events on the profiler's clock)
and the ``jax.named_scope`` every device event belongs to.

A reader is handed only ``run``, which holds neither, so this library goes to
the sources itself: the registry through ``profiling.snapshot()``, and the
trace by loading this process's own ``.xplane.pb`` a second time (the
driver's reduction, ``trace_reduce.load``, keeps only ``bench.*`` host events
and no scope). Both are missing at a commit before the spans: every function
here then returns None and the metric is left out of the line.

Where the scope of a device event is found (looked at by hand on the v5e,
2026-09-30): not in what ``jax.profiler.ProfileData`` gives. An ``XLA Ops``
event's name is its instruction's text (``%fusion.2960 = (f32[8,1024] ...)
fusion(...)``, no ``metadata=``) and its stats are ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier``. So the map from
instruction to ``op_name`` comes from the program: ``profiling.
unit_program_text()`` compiles the unit the fit loop ran once more, past the
persistent cache (whose entry may carry another revision's names), and its
text has ``metadata={op_name="jit(round_step)/shard_map/while/body/
closed_call/jvp(TransformerLM)/Block_3/attention/..."}`` on every instruction
the trace names.
"""

import functools
import glob
import json
import os
import re
import statistics
import sys

from benchmark.lib import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
#: the ``jax.named_scope`` names the program sets (models/transformer.py,
#: parallel/common.py, easgd.py, sync.py)
SCOPES = ("attention", "attn_proj", "mlp", "head", "loss", "optimizer",
          "elastic", "grad_exchange")
UNSCOPED = "unscoped"
HOST_PREFIXES = ("mpit.", "bench.")
NO_SPAN = "outside mpit.* and bench.* spans"

# ``transpose(jvp(loss))`` -> ``loss``: jax wraps a scope set outside a flax
# module in the transformations it passes through
_WRAPPED = re.compile(r"^(?:\w+\()*([\w.\-]+)\)*$")
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*\bop_name="([^"]*)"', re.M)


# -- the registry: host-clock durations --------------------------------------

def registry() -> dict:
    """``profiling.snapshot()``, or nothing where the program has none."""
    try:
        from mpit_tpu.utils import profiling

        return profiling.snapshot()
    except (ImportError, AttributeError):
        return {}


def median_s(spans: dict, name: str):
    """Median of the span's ring: the two warm-up units, whose first
    dispatch holds the compile, do not move it."""
    last = spans.get(name, {}).get("last_s")
    return statistics.median(last) if last else None


def input_path_ms_unit():
    """Grouping + staging + the batches of one group, medians of each."""
    spans = registry()
    group, stage, batch = (median_s(spans, n) for n in (
        "mpit.fit.group", "mpit.fit.stage", "mpit.input.batch"))
    if group is None or stage is None or batch is None:
        return None
    # a few batches are drawn outside fit (the reference's first unit), so
    # the ratio of the counts is rounded to the batches a group
    per_group = max(round(spans["mpit.input.batch"]["count"]
                          / spans["mpit.fit.group"]["count"]), 1)
    return (group + stage + per_group * batch) * 1e3


def dispatch_host_ms_unit():
    median = median_s(registry(), "mpit.fit.dispatch")
    return None if median is None else median * 1e3


def init_state_s():
    span = registry().get("mpit.setup.init_state")
    return span["total_s"] if span else None


# -- the trace: scopes and named idle time ------------------------------------

def scope_of(op_name: str):
    """The last path component of ``op_name`` that is one of ``SCOPES``."""
    for part in reversed(op_name.split("/")):
        inner = _WRAPPED.match(part)
        if inner and inner.group(1) in SCOPES:
            return inner.group(1)
    return None


def op_names_of(program_text: str) -> dict:
    """``{instruction name: op_name}`` from a compiled program's text."""
    return dict(_INSTRUCTION.findall(program_text))


def instruction(event_name: str) -> str:
    """``%fusion.12 = f32[8] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def load(path: str) -> dict:
    """``trace_reduce``'s plain planes, with the ``mpit.*`` host events too."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(trace_reduce.DEVICE_PLANE)
        if not device and plane.name != trace_reduce.HOST_PLANE:
            continue
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events
                if device or ev.name.startswith(HOST_PREFIXES)
            ]
            if events:
                lines.setdefault(line.name, []).extend(events)
    return planes


def reduce_scopes(lines: dict, op_names: dict, top: int = 12) -> dict:
    """Device self time by scope, a unit, on one device plane that holds
    program runs (``XLA Modules``) and operations (``XLA Ops``). A unit is one
    run of the program that took most of the traced time; self time is
    ``trace_reduce.self_times``', so a ``while`` does not count its body."""
    modules, ops = lines["XLA Modules"], lines["XLA Ops"]
    by_program = {}
    for name, _, dur in modules:
        count, total = by_program.get(name, (0, 0.0))
        by_program[name] = (count + 1, total + dur)
    program = max(by_program, key=lambda n: by_program[n][1])
    units = by_program[program][0]
    lo = min(s for _, s, _ in modules)
    hi = max(s + d for _, s, d in modules)
    selfs, _ = trace_reduce.self_times(
        [ev for ev in ops if lo <= ev[1] < hi])
    by_event = {}  # an instruction runs many times: name it once
    for name, self_ns, _ in selfs:
        by_event[name] = by_event.get(name, 0.0) + self_ns
    by_scope, unscoped, named = {}, {}, []
    for name, self_ns in by_event.items():
        key = instruction(name)
        op_name = op_names.get(key, "")
        scope = scope_of(op_name)
        if scope is None:
            scope = UNSCOPED
            what = op_name or key
            unscoped[what] = unscoped.get(what, 0.0) + self_ns
        by_scope[scope] = by_scope.get(scope, 0.0) + self_ns
        named.append((self_ns, key, scope, op_name))
    per_unit = lambda ns: ns / units / 1e6
    return {
        "program": program,
        "units": units,
        "scope_ms_unit": {k: per_unit(v) for k, v in sorted(by_scope.items())},
        # the op table by name: instruction, scope, the end of its op_name
        "top_ops": [[key, scope, op_name[-80:], per_unit(ns)]
                    for ns, key, scope, op_name in sorted(named, reverse=True)[:top]],
        "unscoped_top": [[what[-80:], per_unit(ns)] for what, ns in sorted(
            unscoped.items(), key=lambda kv: -kv[1])[:top]],
    }


def idle_by_span(gaps, spans) -> dict:
    """``{name: ns}``: every gap cut at the host spans' edges, each piece
    given to the innermost span that covers it (the one that began last)."""
    out = {}
    for g0, g1 in gaps:
        near = [(n, s, s + d) for n, s, d in spans if s < g1 and s + d > g0]
        cuts = sorted({g0, g1} | {t for _, s, e in near for t in (s, e)
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            cover = [(s, -e, n) for n, s, e in near if s <= a and e >= b]
            name = max(cover)[2] if cover else NO_SPAN
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def reduce_idle(worst: dict, spans) -> dict:
    """One device's (``trace_reduce.reduce_device``'s) idle time by covering
    host span, and the share of its traced span that is idle under no
    ``mpit.*`` or ``bench.*`` span."""
    by_span = idle_by_span(worst["gaps"], spans)
    longest = max(worst["gaps"], key=lambda g: g[1] - g[0], default=None)
    seconds = lambda d: {k: v / 1e9 for k, v in
                         sorted(d.items(), key=lambda kv: -kv[1])}
    return {
        "idle_unnamed_pct": 100.0 * by_span.get(NO_SPAN, 0.0) / worst["span_ns"],
        "idle_s_by_span": seconds(by_span),
        "longest_gap": None if longest is None else {
            "seconds": (longest[1] - longest[0]) / 1e9,
            "by_span": seconds(idle_by_span([longest], spans))},
    }


def reduce(planes: dict, op_names: dict):
    """Everything the trace readers and the detail line take, or None where
    the trace holds no device plane: scopes on the first device, idle time
    on the one that idles most."""
    devices = {}
    for name in planes:
        if name.startswith(trace_reduce.DEVICE_PLANE):
            reduced = trace_reduce.reduce_device(planes[name])
            if reduced:
                devices[name] = reduced
    if not devices:
        return None
    first = min(devices, key=lambda n: int(n[len(trace_reduce.DEVICE_PLANE):]))
    worst = max(devices.values(), key=lambda d: 1 - d["busy_ns"] / d["span_ns"])
    scopes = reduce_scopes(planes[first], op_names)
    spans = [ev for line in planes.get(trace_reduce.HOST_PLANE, {}).values()
             for ev in line]
    return {
        **scopes,
        "busy_ms_unit": devices[first]["busy_ns"] / scopes["units"] / 1e6,
        "scopes_in_program": sorted(
            {s for s in map(scope_of, op_names.values()) if s}),
        **reduce_idle(worst, spans),
    }


# -- this process's own trace --------------------------------------------------

def process_started_at() -> float:
    """Unix time at which this process started (``/proc``, to the second)."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/stat") as f:
        boot = next(int(l.split()[1]) for l in f if l.startswith("btime"))
    return boot + ticks / os.sysconf("SC_CLK_TCK")


def own_xplane(argv=None):
    """The newest ``.xplane.pb`` of the cell on the command line (of any
    cell, where none is named), unless it is older than this process."""
    argv = sys.argv if argv is None else argv
    cell = "*"
    for k, word in enumerate(argv):
        if word == "--workload" and k + 1 < len(argv):
            cell = argv[k + 1]
        elif word.startswith("--workload="):
            cell = word.split("=", 1)[1]
    found = glob.glob(os.path.join(
        ROOT, ".bench_out", cell, "trace", "plugins", "profile", "*",
        "*.xplane.pb"))
    if not found:
        return None
    newest = max(found, key=os.path.getmtime)
    # btime is whole seconds: allow the rounding
    if os.path.getmtime(newest) < process_started_at() - 2.0:
        return None
    return newest


@functools.lru_cache(maxsize=1)
def traced():
    """``reduce`` of this process's trace, computed once for all readers, and
    the ``program_spans`` detail line printed with it."""
    path = own_xplane()
    if path is None:
        return None
    try:
        from mpit_tpu.utils import profiling

        text = profiling.unit_program_text()
    except (ImportError, AttributeError):
        text = None
    out = reduce(load(path), op_names_of(text) if text else {})
    if out is not None:
        print(json.dumps({"detail": "program_spans", "value": out}), flush=True)
    return out


def scope_ms_unit(run: dict, *scopes: str):
    """Sum of the scopes' device self time a unit; None without a device
    trace, or where the program sets none of ``scopes``."""
    if not run.get("trace"):
        return None
    out = traced()
    if out is None or not set(scopes) & set(out["scopes_in_program"]):
        return None
    return sum(out["scope_ms_unit"].get(s, 0.0) for s in scopes)


def idle_unnamed_pct(run: dict):
    if not run.get("trace"):
        return None
    out = traced()
    return None if out is None else out["idle_unnamed_pct"]
