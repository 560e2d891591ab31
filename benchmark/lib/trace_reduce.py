"""From a profiler trace to numbers: device busy and idle time, exposed
collective time, the device operations that took most time and what the host
was doing in the longest idle gaps.

The reduction works on plain data, ``{plane: {line: [(name, start_ns,
duration_ns), ...]}}``, so that ``tests/benchmark`` can check it on planes
built by hand; ``load`` makes that from an ``.xplane.pb`` file with
``jax.profiler.ProfileData``.

What a v5e trace holds (looked at by hand, 2026-09-27): a plane
``/device:TPU:<n>`` a chip with the lines ``Steps``, ``XLA Modules`` (one
event a jitted program run), ``XLA Ops`` (every operation, a ``while`` nested
around its body's operations), ``Async XLA Ops`` (one event from a
``-start`` to its ``-done``) and an empty ``TC Overlay``; and a plane
``/host:CPU`` with a line a thread, where ``jax.profiler.TraceAnnotation``
spans appear under their own names. Host and device events share one clock.
"""

import glob
import os

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
COLLECTIVES = (
    "%all-reduce", "%all-gather", "%reduce-scatter", "%collective-permute",
    "%all-to-all",
)
NO_SPAN = "outside bench.* spans"


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str) -> dict:
    """Device planes whole, and of the host plane only ``bench.*`` events
    (the runtime's own host events are many and nothing here reads them)."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        if not device and plane.name != HOST_PLANE:
            continue
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events
                if device or ev.name.startswith("bench.")
            ]
            if events:
                lines.setdefault(line.name, []).extend(events)
    return planes


def union(intervals):
    """Sorted, disjoint ``[start, end]`` lists covering ``intervals``."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(disjoint) -> float:
    return sum(e - s for s, e in disjoint)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def overlap(a, b) -> float:
    """Length of the intersection of two disjoint, sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def self_times(events):
    """``(name, self_ns, is_leaf)`` per event of one line, and the events in
    the same (start) order: an event's self time is its duration minus what
    the events nested directly inside it cover."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out = [[name, dur, True] for name, _, dur in order]
    stack = []  # indices of the events open at this point
    for k, (_, start, dur) in enumerate(order):
        # nested means wholly inside; one that only overlaps is a neighbour
        while stack and start + dur > order[stack[-1]][1] + order[stack[-1]][2]:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= dur
            out[stack[-1]][2] = False
        stack.append(k)
    return [(name, max(ns, 0.0), leaf) for name, ns, leaf in out], order


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVES)


def reduce_device(lines: dict) -> dict:
    """One device plane: span, busy, idle gaps, collectives, op table."""
    modules = lines.get("XLA Modules", [])
    ops = lines.get("XLA Ops", [])
    if not modules or not ops:
        return {}
    lo = min(s for _, s, _ in modules)
    hi = max(s + d for _, s, d in modules)
    busy = union(clip([(s, s + d) for _, s, d in ops], lo, hi))
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = e
    if hi > at:
        gaps.append((at, hi))

    selfs, order = self_times(ops)
    totals = {}
    other = []
    for (name, self_ns, leaf), (_, s, d) in zip(selfs, order):
        totals[name] = totals.get(name, 0.0) + self_ns
        if leaf and not is_collective(name):
            other.append((s, s + d))
    coll = [(s, s + d) for n, s, d in ops if is_collective(n)]
    coll += [(s, s + d) for n, s, d in lines.get("Async XLA Ops", [])
             if is_collective(n)]
    coll = union(clip(coll, lo, hi))
    hidden = overlap(coll, union(clip(other, lo, hi)))
    return {
        "span_ns": hi - lo,
        "busy_ns": length(busy),
        "modules": len(modules),
        "gaps": gaps,
        "collective_ns": length(coll),
        "collective_exposed_ns": length(coll) - hidden,
        "op_self_ns": totals,
    }


def name_gap(gap, host_spans) -> str:
    """The ``bench.*`` host span that covers most of ``gap``."""
    best, best_cover = NO_SPAN, 0.0
    for name, s, d in host_spans:
        cover = min(gap[1], s + d) - max(gap[0], s)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce_trace(planes: dict, top: int = 10) -> dict:
    """Every device plane reduced, the worst idle share, device 0's
    collectives, and the ``breakdown`` the result line carries."""
    devices = {}
    for name in sorted(planes):
        if name.startswith(DEVICE_PLANE):
            reduced = reduce_device(planes[name])
            if reduced:
                devices[name] = reduced
    if not devices:
        return {}
    host_spans = [ev for line in planes.get(HOST_PLANE, {}).values()
                  for ev in line]
    first = devices[min(devices, key=lambda n: int(n[len(DEVICE_PLANE):]))]
    worst = max(devices.values(), key=lambda d: 1 - d["busy_ns"] / d["span_ns"])
    ops = sorted(first["op_self_ns"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(worst["gaps"], key=lambda g: g[0] - g[1])[:top]
    n = len(devices)
    return {
        "devices": n,
        "modules": first["modules"],
        "busy_s": sum(d["busy_ns"] for d in devices.values()) / n / 1e9,
        "window_s": sum(d["span_ns"] for d in devices.values()) / n / 1e9,
        "idle_share_worst": 1 - worst["busy_ns"] / worst["span_ns"],
        "collective_s": first["collective_ns"] / 1e9,
        "collective_exposed_s": first["collective_exposed_ns"] / 1e9,
        "collective_exposed_share":
            first["collective_exposed_ns"] / first["span_ns"],
        "breakdown": {
            "device_ops": [[name[:120], ns / 1e9] for name, ns in ops],
            "idle_gaps": [[name_gap(g, host_spans), (g[1] - g[0]) / 1e9]
                          for g in gaps],
        },
    }
