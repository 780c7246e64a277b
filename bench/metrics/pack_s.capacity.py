"""Seconds per capacity answer packing events, fold tables and grids (span sweep.pack)."""

from bench import program_spans


def read(record):
    return program_spans.child_s(record, "capacity", "sweep.pack")
