"""input_path_ms_unit: the host input path a unit, from the program's own spans."""

from benchmark.lib import program_spans


def read(run):
    return program_spans.input_path_ms_unit()
