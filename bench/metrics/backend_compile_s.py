"""Set-up seconds in backend compiles, persistent-cache reads included."""


def read(record):
    return record["setup_compile"]["backend_s"]
