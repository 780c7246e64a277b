"""Sweep batches min_capacity ran per answer (CapacityReport.iterations)."""

from bench import readings


def read(record):
    return readings.mean(record["counters"]["sweep_calls"])
