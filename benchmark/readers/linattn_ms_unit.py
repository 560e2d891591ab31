"""linattn_ms_unit: device time under the scope linattn (the whole gated-delta-rule mixer) a unit."""

from benchmark.lib import delta_spans


def read(run):
    return delta_spans.under_ms_unit(run, "linattn")
