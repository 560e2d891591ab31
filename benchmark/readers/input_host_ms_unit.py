"""input_host_ms_unit: host time in ``bench.input`` a unit, median."""

import statistics


def read(run):
    per_unit = run.get("input_host_s_unit")
    return statistics.median(per_unit) * 1e3 if per_unit else None
