"""Policy-evaluation rows delivered per second of the window."""

from bench import readings


def read(record):
    return readings.rows_per_s(record)
