"""cca_mix_ms_unit: device time under the scope cca_mix (CCA's value shift, convolutions, q-k mean and L2 norms) a unit."""

from benchmark.lib import scope_spans


def read(run):
    return scope_spans.under_ms_unit(run, "cca_kernels", "cca_mix")
