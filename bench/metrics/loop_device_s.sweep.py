"""Device seconds per sweep query inside the rounds program (profiler trace)."""

from bench import readings


def read(record):
    return readings.program_s_per_query(record)
