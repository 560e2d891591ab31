"""collective_exposed_pct: collective time nothing else overlaps, device 0."""


def read(run):
    trace = run.get("trace")
    return 100.0 * trace["collective_exposed_share"] if trace else None
