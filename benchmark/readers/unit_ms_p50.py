"""unit_ms_p50: median interval between completed units, trace control left out."""

import statistics


def read(run):
    clean = run.get("clean_intervals_s")
    return statistics.median(clean) * 1e3 if clean else None
