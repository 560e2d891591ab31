"""elastic_ms_unit: device time in the exchange between workers a unit."""

from benchmark.lib import program_spans


def read(run):
    return program_spans.scope_ms_unit(run, "elastic", "grad_exchange")
