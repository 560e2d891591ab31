"""router_mlp_ms_unit: device time under the scope router_mlp (the MLP router with its depth averaging, softmax and top-k) a unit."""

from benchmark.lib import scope_spans


def read(run):
    return scope_spans.under_ms_unit(run, "cca_kernels", "router_mlp")
