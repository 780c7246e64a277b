"""Set-up seconds in jaxpr tracing and MLIR lowering (jax.monitoring)."""


def read(record):
    clock = record["setup_compile"]
    return clock["trace_s"] + clock["lower_s"]
