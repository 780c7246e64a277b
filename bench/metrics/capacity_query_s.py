"""Seconds from (template, workloads, SLO) to the answer, over the window."""

from bench import readings


def read(record):
    return readings.seconds_per_query(record)
