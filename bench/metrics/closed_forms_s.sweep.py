"""Seconds per sweep query in the DCS and EC2 closed forms (span sweep.closed_forms)."""

from bench import program_spans


def read(record):
    return program_spans.child_s(record, "sweep", "sweep.closed_forms")
