"""moe_load_max_over_mean: rows of the fullest expert held over the mean, the
worst layer of a step, the median over the window's steps (the step's own
counter, returned beside its loss)."""

import statistics


def read(run):
    seen = (run.get("counters") or {}).get("moe_load_max_over_mean")
    return statistics.median(seen) if seen else None
