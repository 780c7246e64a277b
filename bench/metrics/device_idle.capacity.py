"""Per cent of the traced capacity answers with no operation on the device."""

from bench import readings


def read(record):
    return readings.idle_pct(record)
