"""Per cent of lane rounds that stopped at a fault instant (counters rounds.fault_rounds over rounds.lane_rounds)."""

from bench import program_spans


def read(record):
    return program_spans.share_pct(record, "sweep", "rounds.fault_rounds",
                                   "rounds.lane_rounds")
