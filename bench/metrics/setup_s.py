"""Set-up seconds: process start to the end of the warm-up query."""


def read(record):
    return record["setup_s"]
