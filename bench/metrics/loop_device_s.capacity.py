"""Device seconds per capacity answer inside the rounds program (profiler trace)."""

from bench import readings


def read(record):
    return readings.program_s_per_query(record)
