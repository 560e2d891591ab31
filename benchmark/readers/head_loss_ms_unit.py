"""head_loss_ms_unit: device time in the vocabulary head and the loss a unit."""

from benchmark.lib import program_spans


def read(run):
    return program_spans.scope_ms_unit(run, "head", "loss")
