"""Host seconds per sweep query: the call less the time it waits on device results."""

from bench import program_spans


def read(record):
    return program_spans.outside_s(record, "sweep", "sweep.wait")
