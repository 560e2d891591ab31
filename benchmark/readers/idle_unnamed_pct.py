"""idle_unnamed_pct: device idle time under no host span of the program or the benchmark."""

from benchmark.lib import program_spans


def read(run):
    return program_spans.idle_unnamed_pct(run)
