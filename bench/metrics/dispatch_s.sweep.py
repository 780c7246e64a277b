"""Host seconds per sweep query enqueueing device calls (span sweep.dispatch)."""

from bench import program_spans


def read(record):
    return program_spans.child_s(record, "sweep", "sweep.dispatch")
