"""Seconds per capacity answer outside its sweep calls: the bisection's own logic."""

from bench import program_spans


def read(record):
    return program_spans.outside_s(record, "capacity", "sweep")
