"""init_state_s: seconds in the trainer's init_state."""

from benchmark.lib import program_spans


def read(run):
    return program_spans.init_state_s()
