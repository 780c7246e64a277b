"""Mean over the window's device calls of the most rounds any lane of the call ran."""

from bench import readings


def read(record):
    return readings.mean(record["counters"]["rounds_max"])
