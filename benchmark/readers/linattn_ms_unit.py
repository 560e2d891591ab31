"""linattn_ms_unit: device time under the scope linattn (the whole gated-delta-rule mixer) a unit."""

from benchmark.lib import scope_spans


def read(run):
    return scope_spans.under_ms_unit(run, "delta_kernels", "linattn")
