"""Per cent of fold-table lookups per capacity answer that the cache answered."""

from bench import program_spans


def read(record):
    return program_spans.share_pct(record, "capacity", "fold_tables.hits",
                                   "fold_tables.hits", "fold_tables.misses")
