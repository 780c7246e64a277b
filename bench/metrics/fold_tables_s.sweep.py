"""Seconds per sweep query looking up or building fold tables (span rounds.fold_tables)."""

from bench import program_spans


def read(record):
    return program_spans.child_s(record, "sweep", "rounds.fold_tables")
