"""Seconds per sweep query the host waits on device results (span sweep.wait)."""

from bench import program_spans


def read(record):
    return program_spans.child_s(record, "sweep", "sweep.wait")
