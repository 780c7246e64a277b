"""Seconds per fault-sweep query building fault stops and fold axes (span rounds.fault_tables)."""

from bench import program_spans


def read(record):
    return program_spans.child_s(record, "sweep", "rounds.fault_tables")
