"""device_idle_pct: share of the traced span with no operation on the device."""


def read(run):
    trace = run.get("trace")
    return 100.0 * trace["idle_share_worst"] if trace else None
