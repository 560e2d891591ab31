"""Clocks and listeners: what jax reports about compilation, and the
percentile of a window's intervals.

``CompileMeter`` is a copy of ``chip_smoke._CompileMeter`` (sound, PERF.md
verdict table).
"""


class CompileMeter:
    """Sums jax's own ``backend_compile_duration`` events (the seconds in the
    backend compiler or, on a persistent-cache hit, in the cache read), the
    programs they cover and the persistent-cache hits."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.slowest = ("-", 0.0)
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.programs += 1
            if seconds > self.slowest[1]:
                self.slowest = (fun_name, seconds)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def summary(self) -> dict:
        return {
            "seconds": self.seconds,
            "programs": self.programs,
            "cache_hits": self.cache_hits,
            "slowest": list(self.slowest),
        }


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between order
    statistics, numpy's default rule, without numpy."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of no values")
    pos = (len(s) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)
